"""Pure-jnp oracle for tile rasterization (differentiable).

This is the canonical definition of the compositing math. The Pallas kernel
in ``tile_raster.py`` must match this bit-for-bit (same masking rules as the
CUDA 3D-GS rasterizer: alpha clamp at 0.99, skip alpha < 1/255, stop when
transmittance would drop below 1e-4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.projection import MX, MY, CA, CB, CC, OP, CR, CG, CB_, RAD

ALPHA_MAX = 0.99
ALPHA_MIN = 1.0 / 255.0
T_EPS = 1e-4


def compose_tile(
    tile_splats: jax.Array,  # (K, 11) packed splats, front-to-back depth order
    valid: jax.Array,        # (K,) bool
    pix_x: jax.Array,        # (P,) pixel center x coords
    pix_y: jax.Array,        # (P,) pixel center y coords
    bg: jax.Array,           # (3,)
) -> tuple[jax.Array, jax.Array]:
    """Front-to-back alpha compositing of K splats over P pixels.

    Returns (rgb (P,3), transmittance (P,)).
    """
    mx = tile_splats[:, MX][:, None]
    my = tile_splats[:, MY][:, None]
    ca = tile_splats[:, CA][:, None]
    cb = tile_splats[:, CB][:, None]
    cc = tile_splats[:, CC][:, None]
    op = tile_splats[:, OP][:, None]
    rgb = tile_splats[:, CR : CB_ + 1]  # (K,3)

    dx = pix_x[None, :] - mx  # (K,P)
    dy = pix_y[None, :] - my
    power = -0.5 * (ca * dx * dx + cc * dy * dy) - cb * dx * dy
    alpha = op * jnp.exp(jnp.minimum(power, 0.0))
    alpha = jnp.minimum(alpha, ALPHA_MAX)
    live = valid[:, None] & (power <= 0.0) & (alpha >= ALPHA_MIN)
    alpha = jnp.where(live, alpha, 0.0)

    one_minus = 1.0 - alpha
    t_incl = jnp.cumprod(one_minus, axis=0)                     # T after splat k
    t_excl = jnp.concatenate([jnp.ones_like(t_incl[:1]), t_incl[:-1]], axis=0)
    # CUDA rasterizer stop rule: splat k only composited if T would stay >= eps
    alive = t_incl >= T_EPS
    w = jnp.where(alive, alpha * t_excl, 0.0)                   # (K,P)
    # transmittance after the last composited splat (1.0 if none composited;
    # t_incl is non-increasing so the min over alive entries is the last one)
    t_final = jnp.min(jnp.where(alive, t_incl, 1.0), axis=0)
    out = jnp.einsum("kp,kc->pc", w, rgb, precision=jax.lax.Precision.HIGHEST)
    out = out + t_final[:, None] * bg[None, :]
    return out, t_final


def tile_pixel_coords(tile_id, tiles_x, tile_h, tile_w, row_offset=0):
    """Pixel-center coordinates for a flat row-major tile id."""
    ty = tile_id // tiles_x
    tx = tile_id % tiles_x
    ys = ty * tile_h + row_offset + jnp.arange(tile_h)
    xs = tx * tile_w + jnp.arange(tile_w)
    yy, xx = jnp.meshgrid(ys, xs, indexing="ij")
    return xx.reshape(-1) + 0.5, yy.reshape(-1) + 0.5  # (P,), (P,)


def rasterize_tiles_ref(
    packed: jax.Array,      # (N, 11) depth-sorted packed splats
    tile_idx: jax.Array,    # (T, K) int32 indices into packed (depth order)
    tile_valid: jax.Array,  # (T, K) bool
    img_h: int,
    img_w: int,
    tile_h: int,
    tile_w: int,
    bg: jax.Array,
    row_offset: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Full-image tiled rasterization. Returns (image (H,W,3), T (H,W))."""
    tiles_x = img_w // tile_w
    t_count = tile_idx.shape[0]
    tile_splats = packed[tile_idx]  # (T,K,11)

    def one(tid, splats, valid):
        px, py = tile_pixel_coords(tid, tiles_x, tile_h, tile_w, row_offset)
        return compose_tile(splats, valid, px, py, bg)

    rgb, trans = jax.vmap(one)(jnp.arange(t_count), tile_splats, tile_valid)
    # (T, P, 3) -> (H, W, 3)
    tiles_y = img_h // tile_h
    img = rgb.reshape(tiles_y, tiles_x, tile_h, tile_w, 3).transpose(0, 2, 1, 3, 4).reshape(img_h, img_w, 3)
    tmap = trans.reshape(tiles_y, tiles_x, tile_h, tile_w).transpose(0, 2, 1, 3).reshape(img_h, img_w)
    return img, tmap


def rasterize_naive(packed: jax.Array, img_h: int, img_w: int, bg: jax.Array, chunk: int = 4096):
    """Untiled golden oracle: every splat vs every pixel (front-to-back).

    Used for quality tests and to validate the tile-list builder (a tiled
    render with sufficient K must match this).
    """
    ys, xs = jnp.meshgrid(jnp.arange(img_h) + 0.5, jnp.arange(img_w) + 0.5, indexing="ij")
    px = xs.reshape(-1)
    py = ys.reshape(-1)
    n_pix = px.shape[0]
    pad = (-n_pix) % chunk
    px = jnp.pad(px, (0, pad))
    py = jnp.pad(py, (0, pad))
    valid = packed[:, RAD] > 0

    def one(args):
        cx, cy = args
        return compose_tile(packed, valid, cx, cy, bg)

    rgb, trans = jax.lax.map(one, (px.reshape(-1, chunk), py.reshape(-1, chunk)))
    rgb = rgb.reshape(-1, 3)[:n_pix].reshape(img_h, img_w, 3)
    trans = trans.reshape(-1)[:n_pix].reshape(img_h, img_w)
    return rgb, trans
