"""Named scopes of the device stages of the train step and the render programs.

Each stage function carries one ``jax.named_scope``, set where the stage is
defined so that every program calling it gets it. The scope lands in the HLO
``op_name`` metadata of the stage's operations (``.../binning/while/...``);
its backward pass reads ``transpose(jvp(<scope>))``. A profiler trace then
attributes device time to a stage by name instead of by opcode. A scope only
changes metadata: the programs compute and run as before.
"""
from __future__ import annotations

import functools

import jax

__all__ = ["STAGE_SCOPES", "scoped"]

STAGE_SCOPES = (
    "project",      # P.project: 3D Gaussians -> packed 2D splats
    "depth_sort",   # P.sort_by_depth: the sort, the permutation gather and,
                    # backward, the gather through the inverse permutation
    "binning",      # R.build_tile_lists(_hier): the per-tile front-most-K scan
    "tile_gather",  # each tile's splats gathered for the compositor
    "raster",       # the tile compositor (Pallas kernel or the jnp oracle)
    "loss",         # distributed_gs_loss: L1 + D-SSIM and its reductions
    "splat_exchange",  # exchange_splats: the model-axis all_gather of the
                    # splats (or 3D state) and, backward, its psum_scatter
    "grad_reduce",  # packed-gradient psum and the densification statistics
    "adam",         # learning-rate schedule and the Adam update
)


def scoped(name: str):
    """Decorator: run the function under ``jax.named_scope(name)``.

    A fresh scope per call (``named_scope`` keeps its previous name stack
    on the instance, so one shared instance is not safe across threads)."""
    assert name in STAGE_SCOPES, name

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco
