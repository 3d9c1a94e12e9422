"""xLSTM blocks (Beck et al., arXiv:2405.04517): mLSTM + sLSTM.

mLSTM: matrix-memory LSTM with exponential gating. Train path uses a
chunkwise-parallel form (flash-linear-attention style) carrying the matrix
state C, normalizer n and log-scale stabilizer m across chunks — the TPU
adaptation of the paper's CUDA kernels. Decode is the plain recurrence.

sLSTM: scalar-memory LSTM with recurrent (per-head block-diagonal) weights;
inherently sequential -> lax.scan over time (the paper itself notes sLSTM is
not parallelizable).
"""
from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

from repro.models.common import dense_init, rmsnorm, rmsnorm_init
from repro.models.sharding import lshard

CHUNK = 64


def _dims(cfg):
    h = cfg.n_heads
    hd = cfg.d_model // h
    return h, hd


# ================================================================== mLSTM ==
def mlstm_init(key, cfg, dtype):
    d = cfg.d_model
    h, hd = _dims(cfg)
    ks = jax.random.split(key, 7)
    return {
        "wq": dense_init(ks[0], (d, d), dtype),
        "wk": dense_init(ks[1], (d, d), dtype),
        "wv": dense_init(ks[2], (d, d), dtype),
        "wi": dense_init(ks[3], (d, h), jnp.float32, scale=0.02),
        "wf": dense_init(ks[4], (d, h), jnp.float32, scale=0.02),
        "wo_gate": dense_init(ks[5], (d, d), dtype),
        "fbias": jnp.full((h,), 3.0, jnp.float32),  # open forget gates at init
        "norm": rmsnorm_init(d, dtype),
        "out_proj": dense_init(ks[6], (d, d), dtype),
    }


def _mlstm_qkvif(p, cfg, x):
    bsz, s, d = x.shape
    h, hd = _dims(cfg)
    q = (x @ p["wq"]).reshape(bsz, s, h, hd)
    k = (x @ p["wk"]).reshape(bsz, s, h, hd) / jnp.sqrt(hd).astype(x.dtype)
    v = (x @ p["wv"]).reshape(bsz, s, h, hd)
    ilog = (x.astype(jnp.float32) @ p["wi"])                  # (B,S,H) input gate logit
    flog = jax.nn.log_sigmoid(x.astype(jnp.float32) @ p["wf"] + p["fbias"])  # (B,S,H)
    return q, k, v, ilog, flog


def mlstm_train(p, cfg, x):
    bsz, s, d = x.shape
    h, hd = _dims(cfg)
    q, k, v, ilog, flog = _mlstm_qkvif(p, cfg, x)
    q = lshard(q, "batch", "seq", "heads", None)
    k = lshard(k, "batch", "seq", "heads", None)
    v = lshard(v, "batch", "seq", "heads", None)

    c = min(CHUNK, s)
    pad = (-s) % c
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (q, k, v))
        ilog = jnp.pad(ilog, ((0, 0), (0, pad), (0, 0)))
        flog = jnp.pad(flog, ((0, 0), (0, pad), (0, 0)), constant_values=-1e4)
    nc = q.shape[1] // c

    def rs(t):
        return t.reshape(bsz, nc, c, *t.shape[2:]).transpose(1, 0, *range(2, t.ndim + 1))

    qs, ks_, vs = (rs(t).astype(jnp.float32) for t in (q, k, v))   # (nc,B,c,H,*)
    ils, fls = rs(ilog), rs(flog)                                   # (nc,B,c,H)

    def chunk_step(carry, inp):
        cstate, nstate, m = carry       # (B,H,hd,hd), (B,H,hd), (B,H)
        qc, kc, vc, il, fl = inp
        cf = jnp.cumsum(fl, axis=1)                                 # (B,c,H) inclusive
        total_f = cf[:, -1]                                         # (B,H)
        # intra-chunk log weights w_ij = cf_i - cf_j + il_j  (j <= i)
        wlog = cf[:, :, None, :] - cf[:, None, :, :] + il[:, None, :, :]   # (B,i,j,H)
        causal = jnp.tril(jnp.ones((wlog.shape[1], wlog.shape[1]), bool))
        wlog = jnp.where(causal[None, :, :, None], wlog, -jnp.inf)
        carry_log = cf + m[:, None]                                 # (B,i,H) carry-in scale per row
        m_row = jnp.maximum(jnp.max(wlog, axis=2), carry_log)       # (B,i,H)
        m_row = jnp.maximum(m_row, -1e30)
        wa = jnp.exp(wlog - m_row[:, :, None, :])                   # (B,i,j,H)
        cscale = jnp.exp(carry_log - m_row)                         # (B,i,H)

        scores = jnp.einsum("bihd,bjhd->bijh", qc, kc)              # (B,i,j,H)
        num_intra = jnp.einsum("bijh,bijh,bjhp->bihp", wa, scores, vc)
        num_carry = jnp.einsum("bihd,bhdp,bih->bihp", qc, cstate, cscale)
        den_intra = jnp.einsum("bijh,bijh->bih", wa, scores)
        den_carry = jnp.einsum("bihd,bhd,bih->bih", qc, nstate, cscale)
        num = num_intra + num_carry
        den = den_intra + den_carry
        denom = jnp.maximum(jnp.abs(den), jnp.exp(-m_row))          # xLSTM max(|n q|, 1) at scale m
        y = num / denom[..., None]                                  # (B,i,H,P)

        # ---- state to next chunk, restabilized at m_new
        m_new = jnp.maximum(m + total_f, jnp.max(total_f[:, None] - cf + il, axis=1))
        upd_log = total_f[:, None] - cf + il                        # (B,j,H)
        uw = jnp.exp(upd_log - m_new[:, None])                      # (B,j,H)
        c_next = cstate * jnp.exp(m + total_f - m_new)[:, :, None, None] + jnp.einsum(
            "bjh,bjhd,bjhp->bhdp", uw, kc, vc
        )
        n_next = nstate * jnp.exp(m + total_f - m_new)[:, :, None] + jnp.einsum("bjh,bjhd->bhd", uw, kc)
        return (c_next, n_next, m_new), y

    c0 = jnp.zeros((bsz, h, hd, hd), jnp.float32)
    n0 = jnp.zeros((bsz, h, hd), jnp.float32)
    m0 = jnp.full((bsz, h), -1e30, jnp.float32)
    _, ys = jax.lax.scan(chunk_step, (c0, n0, m0), (qs, ks_, vs, ils, fls))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(bsz, nc * c, h, hd)[:, :s]

    o = jax.nn.sigmoid(x @ p["wo_gate"])
    y = (y.reshape(bsz, s, d).astype(x.dtype)) * o
    y = rmsnorm(p["norm"], y, cfg.norm_eps)
    return lshard(y @ p["out_proj"], "batch", "seq", "embed")


def mlstm_cache_init(cfg, batch):
    h, hd = _dims(cfg)
    return {
        "c": jnp.zeros((batch, h, hd, hd), jnp.float32),
        "n": jnp.zeros((batch, h, hd), jnp.float32),
        "m": jnp.full((batch, h), -1e30, jnp.float32),
    }


def mlstm_decode(p, cfg, x, cache):
    bsz = x.shape[0]
    h, hd = _dims(cfg)
    q, k, v, ilog, flog = _mlstm_qkvif(p, cfg, x)   # seq dim = 1
    qf, kf, vf = (t[:, 0].astype(jnp.float32) for t in (q, k, v))
    il, fl = ilog[:, 0], flog[:, 0]                                 # (B,H)
    m_new = jnp.maximum(cache["m"] + fl, il)
    scale_old = jnp.exp(cache["m"] + fl - m_new)
    scale_in = jnp.exp(il - m_new)
    c_new = cache["c"] * scale_old[:, :, None, None] + jnp.einsum("bhd,bhp->bhdp", kf, vf) * scale_in[:, :, None, None]
    n_new = cache["n"] * scale_old[:, :, None] + kf * scale_in[:, :, None]
    num = jnp.einsum("bhd,bhdp->bhp", qf, c_new)
    den = jnp.einsum("bhd,bhd->bh", qf, n_new)
    denom = jnp.maximum(jnp.abs(den), jnp.exp(-m_new))
    y = (num / denom[..., None]).reshape(bsz, 1, h * hd).astype(x.dtype)
    o = jax.nn.sigmoid(x @ p["wo_gate"])
    y = y * o
    y = rmsnorm(p["norm"], y, cfg.norm_eps)
    return y @ p["out_proj"], {"c": c_new, "n": n_new, "m": m_new}


# ================================================================== sLSTM ==
def slstm_init(key, cfg, dtype):
    d = cfg.d_model
    h, hd = _dims(cfg)
    ks = jax.random.split(key, 3)
    return {
        "wx": dense_init(ks[0], (d, 4 * d), dtype),        # z,i,f,o pre-activations
        "r": (jax.random.normal(ks[1], (h, hd, 4 * hd)) * 0.02).astype(dtype),  # recurrent per head
        "fbias": jnp.full((d,), 3.0, jnp.float32),
        "norm": rmsnorm_init(d, dtype),
        "out_proj": dense_init(ks[2], (d, d), dtype),
    }


def _slstm_scan(wx, r, fbias):
    """Pure local recurrence. wx: (B,S,4,H,hd) f32. Returns ys (B,S,H,hd)."""
    bsz, s, four, h, hd = wx.shape

    def step(carry, inp):
        cs, ns, ms, ys = carry           # cell, normalizer, stabilizer, hidden
        pre = inp + jnp.einsum("bhd,hdk->bhk", ys, r).reshape(bsz, 4, h, hd)
        z = jnp.tanh(pre[:, 0])
        ilog = pre[:, 1]
        flog = jax.nn.log_sigmoid(pre[:, 2] + fbias.reshape(h, hd)[None])
        o = jax.nn.sigmoid(pre[:, 3])
        m_new = jnp.maximum(flog + ms, ilog)
        i_s = jnp.exp(ilog - m_new)
        f_s = jnp.exp(flog + ms - m_new)
        c_new = f_s * cs + i_s * z
        n_new = f_s * ns + i_s
        y = o * c_new / jnp.maximum(n_new, 1.0)
        return (c_new, n_new, m_new, y), y

    zeros = jnp.zeros((bsz, h, hd), jnp.float32)
    init = (zeros, zeros, jnp.full((bsz, h, hd), -1e30, jnp.float32), zeros)
    _, ys = jax.lax.scan(step, init, wx.transpose(1, 0, 2, 3, 4))
    return ys.transpose(1, 0, 2, 3)


def slstm_train(p, cfg, x):
    bsz, s, d = x.shape
    h, hd = _dims(cfg)
    wx = (x @ p["wx"]).reshape(bsz, s, 4, h, hd).astype(jnp.float32)
    r = p["r"].astype(jnp.float32)
    fbias = p["fbias"]

    # Recurrent-scan sharding (§Perf xlstm iteration 2): run the time scan
    # under shard_map — batch stays on "data", everything else replicated, so
    # the S sequential steps emit ZERO collectives. Left to GSPMD, the loop
    # body re-shards per step (12k+ tiny all-reduces per train step at 4k).
    from repro.models.sharding import current_mesh, current_rules
    from jax.sharding import PartitionSpec as PS

    mesh = current_mesh()
    if mesh is None:
        ys = _slstm_scan(wx, r, fbias)
    else:
        batch_rule = (current_rules() or {}).get("batch") or ("pod", "data")
        baxes = tuple(a for a in batch_rule if a in mesh.shape)
        bspec = baxes if bsz % max(
            1, int(np.prod([mesh.shape[a] for a in baxes]))
        ) == 0 else None
        ys = jax.shard_map(  # analysis: allow(retrace.jit_outside_factory, runs under the caller's jitted train step: constructed once per outer trace, not per call)
            _slstm_scan,
            mesh=mesh,
            in_specs=(PS(bspec), PS(), PS()),
            out_specs=PS(bspec),
            check_vma=False,
        )(wx, r, fbias)
    y = ys.reshape(bsz, s, d).astype(x.dtype)
    y = rmsnorm(p["norm"], y, cfg.norm_eps)
    return lshard(y @ p["out_proj"], "batch", "seq", "embed")


def slstm_cache_init(cfg, batch):
    h, hd = _dims(cfg)
    z = jnp.zeros((batch, h, hd), jnp.float32)
    return {"c": z, "n": z, "m": jnp.full((batch, h, hd), -1e30, jnp.float32), "y": z}


def slstm_decode(p, cfg, x, cache):
    bsz = x.shape[0]
    h, hd = _dims(cfg)
    wx = (x[:, 0] @ p["wx"]).reshape(bsz, 4, h, hd).astype(jnp.float32)
    pre = wx + jnp.einsum("bhd,hdk->bhk", cache["y"], p["r"].astype(jnp.float32)).reshape(bsz, 4, h, hd)
    z = jnp.tanh(pre[:, 0])
    ilog = pre[:, 1]
    flog = jax.nn.log_sigmoid(pre[:, 2] + p["fbias"].reshape(h, hd)[None])
    o = jax.nn.sigmoid(pre[:, 3])
    m_new = jnp.maximum(flog + cache["m"], ilog)
    i_s = jnp.exp(ilog - m_new)
    f_s = jnp.exp(flog + cache["m"] - m_new)
    c_new = f_s * cache["c"] + i_s * z
    n_new = f_s * cache["n"] + i_s
    y = o * c_new / jnp.maximum(n_new, 1.0)
    d = h * hd
    out = y.reshape(bsz, 1, d).astype(x.dtype)
    out = rmsnorm(p["norm"], out, cfg.norm_eps)
    new_cache = {"c": c_new, "n": n_new, "m": m_new, "y": y}
    return out @ p["out_proj"], new_cache
