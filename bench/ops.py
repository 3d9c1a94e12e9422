"""Which device operations of a trace belong to which layer.

A TPU v5e trace of this program names each device operation by its HLO
instruction text (``%sort.3 = (s32[4,574464]...) sort(...), ...``); the
program sets no named scopes and its Pallas kernels no names. So a layer's
operations are picked by opcode, and the rasterizer's by the Mosaic custom
call target that only Pallas kernels carry.
"""
from __future__ import annotations

import re

_OPCODE = re.compile(r"^%\S+ = .*?\s([a-z][a-z0-9-]*)\(")


def opcode(op) -> str:
    m = _OPCODE.match(op.name)
    return m.group(1) if m else ""


def is_raster(op) -> bool:
    """The Pallas tile compositor, forward and backward: the program's only
    Mosaic kernels (``custom_call_target="tpu_custom_call"``)."""
    return opcode(op) == "custom-call" and 'custom_call_target="tpu_custom_call"' in op.name


def is_sort(op) -> bool:
    """Sorts: the depth sort of every projected splat and the tile binner's
    merge sorts (they run inside its scan, and are reported as their own
    operations)."""
    return opcode(op) == "sort"
