"""Which device operations of a trace are collectives.

A TPU v5e trace names each device operation by its HLO instruction text,
so a collective is picked by its opcode: the synchronous forms, and the
start and done halves the compiler splits an asynchronous one into.
"""
from __future__ import annotations

from ops import opcode

COLLECTIVE_OPCODES = frozenset({
    "all-gather", "all-gather-start", "all-gather-done",
    "reduce-scatter",
    "all-reduce", "all-reduce-start", "all-reduce-done",
    "collective-permute", "collective-permute-start", "collective-permute-done",
})


def is_collective(op) -> bool:
    return opcode(op) in COLLECTIVE_OPCODES
