"""jit-ready wrapper around the tile rasterizer with backend dispatch.

backend="ref"    — pure-jnp oracle (differentiable via XLA autodiff).
backend="pallas" — Pallas TPU kernel (interpret mode on CPU), custom VJP.

Both produce identical images/gradients; tests assert allclose across a
shape/dtype sweep.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.tile_raster import ref as _ref
from repro.kernels.tile_raster import tile_raster as _pallas


def rasterize_tiles(
    packed: jax.Array,      # (N, 11) depth-sorted packed splats
    tile_idx: jax.Array,    # (T, K) int32
    tile_valid: jax.Array,  # (T, K) bool
    *,
    img_h: int,
    img_w: int,
    tile_h: int,
    tile_w: int,
    bg: jax.Array,
    backend: str = "ref",
    row_offset: int = 0,
    interpret=None,
) -> tuple[jax.Array, jax.Array]:
    """Rasterize to ((H,W,3) image, (H,W) transmittance)."""
    if backend == "ref":
        with jax.named_scope("raster"):  # the oracle gathers inside itself
            return _ref.rasterize_tiles_ref(
                packed, tile_idx, tile_valid, img_h, img_w, tile_h, tile_w, bg, row_offset
            )
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")

    tiles_y = img_h // tile_h
    tiles_x = img_w // tile_w
    with jax.named_scope("tile_gather"):
        # Gather per-tile splat slabs; XLA autodiff turns this into the
        # scatter-add that accumulates per-splat grads across tiles.
        tile_splats = packed[tile_idx]                      # (T,K,11)
        splats_t = jnp.swapaxes(tile_splats, 1, 2)          # (T,11,K)
    with jax.named_scope("raster"):
        composite = _pallas.make_composite(tiles_x, tile_h, tile_w, row_offset, interpret)
        raw, tfin = composite(splats_t.astype(jnp.float32), tile_valid.astype(jnp.float32))
        # (T,3,P) -> (H,W,3)
        img = (
            raw.reshape(tiles_y, tiles_x, 3, tile_h, tile_w)
            .transpose(0, 3, 1, 4, 2)
            .reshape(img_h, img_w, 3)
        )
        tmap = tfin.reshape(tiles_y, tiles_x, tile_h, tile_w).transpose(0, 2, 1, 3).reshape(img_h, img_w)
        img = img + tmap[..., None] * bg[None, None, :]
    return img, tmap


rasterize_naive = _ref.rasterize_naive
