"""Pallas TPU attention kernel: row-blocked, K/V resident in VMEM.

Grid = (batch*heads, S/BQ). Each program computes one (BQ, hd) output block:
scores (BQ, Skv) live entirely in VMEM/VREGs — the (S, S) matrix never
touches HBM (the flash property). K/V for one head fit VMEM for Skv <= ~8k
at hd=128 (2 x 4 MB); longer sequences use the production chunked-scan path
(repro.models.common.chunked_attention), which is also this kernel's oracle.

MXU work per program: (BQ x hd)x(hd x Skv) + (BQ x Skv)x(Skv x hd).
Causal/sliding-window masking is positional (iota vs program offset).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import interpret_mode

BQ = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, *, causal, window, q_offset, scale):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (BQ, hd)
    k = k_ref[0].astype(jnp.float32)                  # (Skv, hd)
    v = v_ref[0].astype(jnp.float32)
    skv = k.shape[0]

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (BQ, Skv)

    q_pos = q_offset + qi * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, skv), 0)
    kv_pos = jax.lax.broadcasted_iota(jnp.int32, (BQ, skv), 1)
    mask = jnp.ones((BQ, skv), jnp.bool_)
    if causal:
        mask = mask & (kv_pos <= q_pos)
    if window is not None:
        mask = mask & (q_pos - kv_pos < window)
    scores = jnp.where(mask, scores, -1e30)

    m = jnp.max(scores, axis=1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    o = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) / jnp.maximum(l, 1e-30)
    o_ref[0] = o.astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def make_flash(bh: int, sq: int, skv: int, hd: int, causal: bool, window, q_offset: int,
               dtype_name: str, interpret=None):
    if interpret is None:
        interpret = interpret_mode()
    scale = 1.0 / (hd ** 0.5)
    kern = functools.partial(_kernel, causal=causal, window=window, q_offset=q_offset, scale=scale)
    dtype = jnp.dtype(dtype_name)

    def run(q, k, v):
        return pl.pallas_call(
            kern,
            grid=(bh, sq // BQ),
            in_specs=[
                pl.BlockSpec((1, BQ, hd), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, skv, hd), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, skv, hd), lambda b, i: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, BQ, hd), lambda b, i: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, sq, hd), dtype),
            interpret=interpret,
        )(q, k, v)

    return run
