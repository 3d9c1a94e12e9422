"""Production mesh definitions (TPU v5e; CPU host devices in the dry-run).

A FUNCTION, not a module constant — importing this module must never touch
jax device state.
"""
from __future__ import annotations

from repro.core.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# TPU v5e hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link
