"""Differentiable render pipeline: project -> sort -> tile-bin -> composite.

The tile-binning step is the TPU adaptation of the CUDA duplicate+radix-sort
binning in 3D-GS/Grendel-GS: instead of data-dependent duplication, every
tile keeps the front-most K overlapping splats (fixed capacity), built with a
memory-bounded running top-K scan so it scales to millions of Gaussians.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import gaussians as G
from repro.core import projection as P
from repro.core.scopes import scoped
from repro.kernels.tile_raster import ops as raster_ops

BIG_IDX = jnp.iinfo(jnp.int32).max


@partial(jax.jit, static_argnames=("img_h", "img_w", "tile_h", "tile_w", "k_per_tile", "chunk"))
@scoped("binning")
def build_tile_lists(
    packed_sorted: jax.Array,  # (N, 11) depth-sorted splats
    *,
    img_h: int,
    img_w: int,
    tile_h: int = 16,
    tile_w: int = 16,
    k_per_tile: int = 256,
    chunk: int = 2048,
    row_offset: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Per-tile front-most-K overlapping splat lists.

    Overlap test: splat bounding circle (mean, radius) vs tile rectangle.
    Because input is depth-sorted, the K smallest overlapping indices are the
    K front-most splats — exactly what front-to-back compositing needs.

    ``row_offset`` shifts tile origins vertically: pixel-parallel workers
    rendering a horizontal strip pass their strip's first image row.

    Returns (idx (T,K) int32 clamped to valid range, valid (T,K) bool).
    """
    n = packed_sorted.shape[0]
    tiles_y = img_h // tile_h
    tiles_x = img_w // tile_w
    t_count = tiles_y * tiles_x

    tids = jnp.arange(t_count)
    tx0 = (tids % tiles_x) * tile_w
    ty0 = (tids // tiles_x) * tile_h + row_offset
    tx1 = tx0 + tile_w
    ty1 = ty0 + tile_h

    pad = (-n) % chunk
    mx = jnp.pad(packed_sorted[:, P.MX], (0, pad))
    my = jnp.pad(packed_sorted[:, P.MY], (0, pad))
    rad = jnp.pad(packed_sorted[:, P.RAD], (0, pad))  # pad radius 0 -> never overlaps
    n_chunks = mx.shape[0] // chunk

    def step(carry, ci):
        best = carry  # (T, K) ascending candidate indices (BIG_IDX = empty)
        sl = ci * chunk
        cmx = jax.lax.dynamic_slice_in_dim(mx, sl, chunk)
        cmy = jax.lax.dynamic_slice_in_dim(my, sl, chunk)
        crad = jax.lax.dynamic_slice_in_dim(rad, sl, chunk)
        overlap = (
            (cmx[None, :] + crad[None, :] >= tx0[:, None])
            & (cmx[None, :] - crad[None, :] <= tx1[:, None])
            & (cmy[None, :] + crad[None, :] >= ty0[:, None])
            & (cmy[None, :] - crad[None, :] <= ty1[:, None])
            & (crad[None, :] > 0)
        )  # (T, chunk)
        cand = jnp.where(overlap, sl + jnp.arange(chunk)[None, :], BIG_IDX)
        merged = jnp.sort(jnp.concatenate([best, cand], axis=1), axis=1)[:, : best.shape[1]]
        return merged, None

    # typed like the splats (full_like), so that inside a shard_map the
    # scan's carry varies over the same mesh axes as what it merges
    init = jnp.full_like(mx, BIG_IDX, dtype=jnp.int32, shape=(t_count, k_per_tile))
    best, _ = jax.lax.scan(step, init, jnp.arange(n_chunks))
    valid = best != BIG_IDX
    idx = jnp.where(valid, best, 0)
    return idx, valid


@partial(
    jax.jit,
    static_argnames=("img_h", "img_w", "tile_h", "tile_w", "k_per_tile", "block", "k_block_mult", "chunk"),
)
@scoped("binning")
def build_tile_lists_hier(
    packed_sorted: jax.Array,
    *,
    img_h: int,
    img_w: int,
    tile_h: int = 16,
    tile_w: int = 16,
    k_per_tile: int = 256,
    block: int = 8,
    k_block_mult: int = 4,
    chunk: int = 4096,
    row_offset: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Two-level tile binning (§Perf GS iteration: beyond-paper).

    Flat binning tests every (tile, splat) pair — O(T*N) bytes, the dominant
    memory term at 2048px/4M+ splats. Level 1 bins splats into coarse
    (block x block)-tile superblocks (O(T/block^2 * N)); level 2 tests each
    tile only against its block's K1 = k_block_mult*K front candidates
    (O(T * K1)). A splat overlapping a tile always overlaps its block, so
    with adequate K1 the result is identical to flat binning (tested).
    """
    tiles_y = img_h // tile_h
    tiles_x = img_w // tile_w
    by = max(min(block, tiles_y), 1)
    bx = max(min(block, tiles_x), 1)
    assert tiles_y % by == 0 and tiles_x % bx == 0, (tiles_y, tiles_x, by, bx)
    k1 = k_per_tile * k_block_mult

    idx1, valid1 = build_tile_lists(
        packed_sorted,
        img_h=img_h,
        img_w=img_w,
        tile_h=tile_h * by,
        tile_w=tile_w * bx,
        k_per_tile=k1,
        chunk=chunk,
        row_offset=row_offset,
    )  # (Tb, K1) ascending (= front-to-back) within each block
    blocks_x = tiles_x // bx
    cand = packed_sorted[idx1]  # (Tb, K1, 11)
    cand_mx = jnp.where(valid1, cand[..., P.MX], jnp.inf)
    cand_my = jnp.where(valid1, cand[..., P.MY], jnp.inf)
    cand_rad = jnp.where(valid1, cand[..., P.RAD], 0.0)

    def per_block(bid, mx, my, rad, gidx):
        # tile rectangles of this block
        t_local = jnp.arange(by * bx)
        ty = (bid // blocks_x) * by + t_local // bx
        tx = (bid % blocks_x) * bx + t_local % bx
        x0 = (tx * tile_w).astype(jnp.float32)
        y0 = (ty * tile_h + row_offset).astype(jnp.float32)
        overlap = (
            (mx[None, :] + rad[None, :] >= x0[:, None])
            & (mx[None, :] - rad[None, :] <= (x0 + tile_w)[:, None])
            & (my[None, :] + rad[None, :] >= y0[:, None])
            & (my[None, :] - rad[None, :] <= (y0 + tile_h)[:, None])
            & (rad[None, :] > 0)
        )  # (tiles_in_block, K1)
        score = jnp.where(overlap, jnp.arange(k1)[None, :], k1)
        sel = jnp.sort(score, axis=1)[:, :k_per_tile]        # front-most K
        ok = sel < k1
        sel = jnp.where(ok, sel, 0)
        return gidx[sel], ok

    tile_idx, tile_valid = jax.vmap(per_block)(
        jnp.arange(idx1.shape[0]), cand_mx, cand_my, cand_rad, idx1
    )  # (Tb, tiles_in_block, K)
    # reorder (block-major) -> row-major flat tile order
    blocks_y = tiles_y // by
    tile_idx = (
        tile_idx.reshape(blocks_y, blocks_x, by, bx, k_per_tile)
        .transpose(0, 2, 1, 3, 4)
        .reshape(tiles_y * tiles_x, k_per_tile)
    )
    tile_valid = (
        tile_valid.reshape(blocks_y, blocks_x, by, bx, k_per_tile)
        .transpose(0, 2, 1, 3, 4)
        .reshape(tiles_y * tiles_x, k_per_tile)
    )
    return tile_idx, tile_valid


def render_packed(
    packed_sorted: jax.Array,
    *,
    img_h: int,
    img_w: int,
    tile_h: int = 16,
    tile_w: int = 16,
    k_per_tile: int = 256,
    bg: jax.Array | None = None,
    backend: str = "ref",
    row_offset: int = 0,
    binning: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """Rasterize depth-sorted packed splats to an (img_h, img_w, 3) image."""
    if bg is None:
        bg = jnp.zeros((3,), jnp.float32)
    tiles = (img_h // tile_h) * (img_w // tile_w)
    if binning == "auto":
        binning = "hier" if tiles >= 256 else "flat"
    if binning == "hier":
        idx, valid = build_tile_lists_hier(
            packed_sorted,
            img_h=img_h,
            img_w=img_w,
            tile_h=tile_h,
            tile_w=tile_w,
            k_per_tile=k_per_tile,
            row_offset=row_offset,
        )
    else:
        idx, valid = build_tile_lists(
            packed_sorted,
            img_h=img_h,
            img_w=img_w,
            tile_h=tile_h,
            tile_w=tile_w,
            k_per_tile=k_per_tile,
            row_offset=row_offset,
        )
    return raster_ops.rasterize_tiles(
        packed_sorted,
        idx,
        valid,
        img_h=img_h,
        img_w=img_w,
        tile_h=tile_h,
        tile_w=tile_w,
        bg=bg,
        backend=backend,
        row_offset=row_offset,
    )


def render(
    g: G.GaussianModel,
    cam: P.Camera,
    *,
    img_h: int,
    img_w: int,
    tile_h: int = 16,
    tile_w: int = 16,
    k_per_tile: int = 256,
    bg: jax.Array | None = None,
    backend: str = "ref",
    binning: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """End-to-end single-device render of a GaussianModel from one camera."""
    packed = P.project(g, cam)
    packed_sorted, _ = P.sort_by_depth(packed)
    return render_packed(
        packed_sorted,
        img_h=img_h,
        img_w=img_w,
        tile_h=tile_h,
        tile_w=tile_w,
        k_per_tile=k_per_tile,
        bg=bg,
        backend=backend,
        binning=binning,
    )
