"""Pallas TPU tile rasterizer (forward + backward).

TPU adaptation of the CUDA 3D-GS tile rasterizer. The CUDA kernel walks the
depth-sorted splat list sequentially per warp with shared-memory staging and
early exit. TPUs have no warp shuffles or atomics, so we restructure:

  1. alpha matrix        A[k,p] = clamped opacity*exp(quadratic) — fully
                         vectorized over (K splats × P pixels) in VMEM.
  2. transmittance       T via a log-space Hillis-Steele inclusive scan along
                         K (log2(K) static doubling steps — no sequential
                         K-loop, no dynamic control flow).
  3. composite           out[c,p] = sum_k C[c,k] * W[k,p] — a (3,K)x(K,P)
                         MXU matmul. Early termination becomes masking
                         (W=0 once T < 1e-4), which costs nothing on a
                         systolic/vector machine.

The backward kernel recomputes A,T (flash-attention-style rematerialization:
nothing but the inputs and the output cotangents are needed) and emits
per-splat parameter gradients with two more MXU matmuls plus a reverse scan.

Block sizes: one grid step = one image tile. VMEM footprint ~ a few (K,P)
f32 temporaries: K=1024, P=256 -> 1 MB each. The matmuls run at HIGHEST
precision (Mosaic's default for f32 is bf16 passes); at K=1024 the backward
then needs ~17 MB of scoped VMEM, over Mosaic's 16 MB default, so the
kernels ask for 32 MB (a v5e core has 128 MB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode
from repro.kernels.tile_raster.ref import ALPHA_MAX, ALPHA_MIN, T_EPS

_NEG_BIG = -1e30
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)


def _inclusive_cumsum_doubling(x: jax.Array) -> jax.Array:
    """Inclusive cumsum along axis 0 via static Hillis-Steele doubling.

    K static shift+add steps (log2 K) — Mosaic-friendly (static slices only).
    """
    k = x.shape[0]
    d = 1
    while d < k:
        shifted = jnp.concatenate([jnp.zeros_like(x[:d]), x[:-d]], axis=0)
        x = x + shifted
        d *= 2
    return x


def _reverse_exclusive_cumsum(x: jax.Array) -> jax.Array:
    """Reverse *exclusive* cumsum along axis 0: out[k] = sum_{j>k} x[j]."""
    total = jnp.sum(x, axis=0, keepdims=True)
    incl = _inclusive_cumsum_doubling(x)
    return total - incl


def _pixel_coords(tile_id, tiles_x: int, tile_h: int, tile_w: int, row_offset: int):
    """Pixel-center coords (1,P) f32 for a flat row-major tile id (traced)."""
    p = tile_h * tile_w
    flat = jax.lax.broadcasted_iota(jnp.int32, (1, p), 1)
    yy = flat // tile_w
    xx = flat - yy * tile_w
    ty = tile_id // tiles_x
    tx = tile_id - ty * tiles_x
    px = (tx * tile_w + xx).astype(jnp.float32) + 0.5
    py = (ty * tile_h + row_offset + yy).astype(jnp.float32) + 0.5
    return px, py


def _alpha_and_trans(splats, valid, px, py):
    """Shared forward math: splats (11,K), valid (1,K), px/py (1,P).

    Returns (alpha (K,P), t_incl (K,P), t_excl (K,P), alive (K,P), colors (3,K)).
    """
    k = splats.shape[1]
    mx = splats[0, :].reshape(k, 1)
    my = splats[1, :].reshape(k, 1)
    ca = splats[2, :].reshape(k, 1)
    cb = splats[3, :].reshape(k, 1)
    cc = splats[4, :].reshape(k, 1)
    op = splats[5, :].reshape(k, 1)
    colors = splats[6:9, :]  # (3,K)
    vmask = valid.reshape(k, 1) > 0.5

    dx = px - mx  # (K,P)
    dy = py - my
    power = -0.5 * (ca * dx * dx + cc * dy * dy) - cb * dx * dy
    alpha_raw = op * jnp.exp(jnp.minimum(power, 0.0))
    alpha = jnp.minimum(alpha_raw, ALPHA_MAX)
    live = vmask & (power <= 0.0) & (alpha >= ALPHA_MIN)
    alpha = jnp.where(live, alpha, 0.0)

    lm = jnp.log1p(-alpha)
    s_incl = _inclusive_cumsum_doubling(lm)
    t_incl = jnp.exp(s_incl)
    t_excl = jnp.exp(s_incl - lm)
    alive = t_incl >= T_EPS
    return alpha, alpha_raw, live, t_incl, t_excl, alive, colors, (dx, dy, power)


def _fwd_kernel(splats_ref, valid_ref, out_ref, tfin_ref, *, tiles_x, tile_h, tile_w, row_offset):
    t = pl.program_id(0)
    splats = splats_ref[0]  # (11,K)
    valid = valid_ref[0]    # (1,K)
    px, py = _pixel_coords(t, tiles_x, tile_h, tile_w, row_offset)
    alpha, _, _, t_incl, t_excl, alive, colors, _ = _alpha_and_trans(splats, valid, px, py)
    w = jnp.where(alive, alpha * t_excl, 0.0)  # (K,P)
    out = jax.lax.dot_general(
        colors, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (3,P)
    t_final = jnp.min(jnp.where(alive, t_incl, 1.0), axis=0, keepdims=True)  # (1,P)
    out_ref[0] = out
    tfin_ref[0] = t_final


def _bwd_kernel(
    splats_ref, valid_ref, gout_ref, gtfin_ref, dsplats_ref, *, tiles_x, tile_h, tile_w, row_offset
):
    t = pl.program_id(0)
    splats = splats_ref[0]       # (11,K)
    valid = valid_ref[0]         # (1,K)
    gout = gout_ref[0]           # (3,P)
    gtfin = gtfin_ref[0]         # (1,P)
    px, py = _pixel_coords(t, tiles_x, tile_h, tile_w, row_offset)

    alpha, alpha_raw, live, t_incl, t_excl, alive, colors, (dx, dy, power) = _alpha_and_trans(
        splats, valid, px, py
    )
    w = jnp.where(alive, alpha * t_excl, 0.0)  # (K,P)

    # d colors: out = C @ W  =>  dC = gout @ W^T   (3,P)x(P,K) -> (3,K)
    dcolors = jax.lax.dot_general(
        gout, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (3,K)
    # dW = C^T @ gout : (K,3)x(3,P) -> (K,P)
    dw = jax.lax.dot_general(
        colors, gout, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (K,P)
    dw = jnp.where(alive, dw, 0.0)

    # t_final grad: t_final = t_incl at last alive (or 1). d t_final / d alpha_k
    # = -t_final/(1-alpha_k) for alive k. Downstream-weight term:
    #   B[k,p] = sum_{j>k} dW[j,p]*W[j,p] + gtfin[p]*t_final[p]
    t_final = jnp.min(jnp.where(alive, t_incl, 1.0), axis=0, keepdims=True)  # (1,P)
    b = _reverse_exclusive_cumsum(dw * w) + gtfin * t_final  # (K,P)

    one_minus = 1.0 - alpha
    dalpha = jnp.where(alive, dw * t_excl - b / one_minus, 0.0)  # (K,P)

    # chain through masking & clamp: alpha = live ? min(op*exp(min(power,0)), 0.99) : 0
    unclamped = live & (alpha_raw < ALPHA_MAX)
    dalpha_raw = jnp.where(unclamped, dalpha, 0.0)
    e = jnp.exp(jnp.minimum(power, 0.0))
    op = splats[5, :].reshape(-1, 1)
    dop = jnp.sum(dalpha_raw * e, axis=1)  # (K,)
    dpower = jnp.where(power < 0.0, dalpha_raw * op * e, 0.0)  # (K,P)

    ca = splats[2, :].reshape(-1, 1)
    cb = splats[3, :].reshape(-1, 1)
    cc = splats[4, :].reshape(-1, 1)
    dca = jnp.sum(dpower * (-0.5 * dx * dx), axis=1)
    dcb = jnp.sum(dpower * (-dx * dy), axis=1)
    dcc = jnp.sum(dpower * (-0.5 * dy * dy), axis=1)
    ddx = dpower * (-ca * dx - cb * dy)
    ddy = dpower * (-cc * dy - cb * dx)
    dmx = -jnp.sum(ddx, axis=1)
    dmy = -jnp.sum(ddy, axis=1)

    k = splats.shape[1]
    zeros_k = jnp.zeros((k,), jnp.float32)
    dsplats = jnp.stack(
        [dmx, dmy, dca, dcb, dcc, dop, dcolors[0], dcolors[1], dcolors[2], zeros_k, zeros_k],
        axis=0,
    )  # (11,K)
    dsplats_ref[0] = dsplats


@functools.lru_cache(maxsize=None)
def make_composite(tiles_x: int, tile_h: int, tile_w: int, row_offset: int, interpret=None):
    """Build the custom_vjp'd tile compositor for a static tile layout.

    Returned fn: (tile_splats_t (T,11,K) f32, valid (T,K) f32) ->
                 (out (T,3,P) f32, t_final (T,P) f32)
    Differentiable w.r.t. tile_splats_t only (valid gets zero cotangent).

    Inside the kernels ``valid`` and ``t_final`` travel as (T,1,K) and
    (T,1,P): Mosaic needs a block's last two dims to be (8,128)-aligned or
    whole, and a (1,K) block of a (T,K) array is neither.
    """
    def _run_fwd(splats_t, valid):
        t_count, _, k = splats_t.shape
        p = tile_h * tile_w
        vma = jax.typeof(splats_t).vma  # inside a shard_map: the outputs vary as the splats do
        interp = interpret_mode(varying=bool(vma)) if interpret is None else interpret
        kern = functools.partial(
            _fwd_kernel, tiles_x=tiles_x, tile_h=tile_h, tile_w=tile_w, row_offset=row_offset
        )
        out, tfin = pl.pallas_call(
            kern,
            grid=(t_count,),
            in_specs=[
                pl.BlockSpec((1, 11, k), lambda t: (t, 0, 0)),
                pl.BlockSpec((1, 1, k), lambda t: (t, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 3, p), lambda t: (t, 0, 0)),
                pl.BlockSpec((1, 1, p), lambda t: (t, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((t_count, 3, p), jnp.float32, vma=vma),
                jax.ShapeDtypeStruct((t_count, 1, p), jnp.float32, vma=vma),
            ],
            interpret=interp,
            compiler_params=_COMPILER_PARAMS,
            name="tile_raster_fwd",
        )(splats_t, valid[:, None, :])
        return out, tfin[:, 0, :]

    def _run_bwd(splats_t, valid, gout, gtfin):
        t_count, _, k = splats_t.shape
        p = tile_h * tile_w
        vma = jax.typeof(splats_t).vma
        interp = interpret_mode(varying=bool(vma)) if interpret is None else interpret
        kern = functools.partial(
            _bwd_kernel, tiles_x=tiles_x, tile_h=tile_h, tile_w=tile_w, row_offset=row_offset
        )
        return pl.pallas_call(
            kern,
            grid=(t_count,),
            in_specs=[
                pl.BlockSpec((1, 11, k), lambda t: (t, 0, 0)),
                pl.BlockSpec((1, 1, k), lambda t: (t, 0, 0)),
                pl.BlockSpec((1, 3, p), lambda t: (t, 0, 0)),
                pl.BlockSpec((1, 1, p), lambda t: (t, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 11, k), lambda t: (t, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((t_count, 11, k), jnp.float32, vma=vma),
            interpret=interp,
            compiler_params=_COMPILER_PARAMS,
            name="tile_raster_bwd",
        )(splats_t, valid[:, None, :], gout, gtfin[:, None, :])

    @jax.custom_vjp
    def composite(splats_t, valid):
        return _run_fwd(splats_t, valid)

    def composite_fwd(splats_t, valid):
        out = _run_fwd(splats_t, valid)
        return out, (splats_t, valid)

    def composite_bwd(res, cts):
        splats_t, valid = res
        gout, gtfin = cts
        dsplats = _run_bwd(splats_t, valid, gout, gtfin)
        return dsplats, jnp.zeros_like(valid)

    composite.defvjp(composite_fwd, composite_bwd)
    return composite
