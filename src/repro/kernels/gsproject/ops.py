"""jit wrapper for the projection kernel: custom_vjp with the oracle's
backward (projection is ~3% of step FLOPs; its backward fuses fine in XLA,
so only the forward gets a hand kernel — see DESIGN.md §6)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import gaussians as G
from repro.core import projection as P
from repro.kernels.gsproject import gsproject as _k
from repro.kernels.gsproject.ref import project_ref

_CAM_USED = 16 + 5 + 3  # viewmat(4x4 row-major), fx/fy/cx/cy/near, campos


def project_packed(g: G.GaussianModel, cam: P.Camera, *, backend: str = "ref", near: float = 0.01):
    """(N, 11) packed splats. backend="pallas" requires sh_degree == 0."""
    if backend == "ref":
        return project_ref(g, cam, near=near)
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    if g.sh.shape[1] != 1:
        raise ValueError(
            f"the Pallas projection kernel evaluates degree-0 SH only; this model "
            f"has sh_degree {g.sh_degree} — use backend='ref'"
        )

    @jax.custom_vjp
    def fwd(gm):
        n = gm.means.shape[0]
        pad = (-n) % _k.BLOCK_N
        mt = jnp.pad(gm.means, ((0, pad), (0, 0))).T
        st = jnp.pad(gm.log_scales, ((0, pad), (0, 0))).T
        qt = jnp.pad(gm.quats, ((0, pad), (0, 0))).T        # zero quats: rsqrt guard
        ot = jnp.pad(gm.opacity_logit, (0, pad), constant_values=-20.0)[None]
        sh0 = jnp.pad(gm.sh[:, 0, :], ((0, pad), (0, 0))).T
        cam_vec = jnp.concatenate(
            [
                cam.viewmat.reshape(-1),                     # 16 (kernel reads rows 0..2)
                jnp.stack([cam.fx, cam.fy, cam.cx, cam.cy]),
                jnp.asarray([near], jnp.float32),
                cam.campos,
                jnp.zeros((_k.CAM_SLOTS - _CAM_USED,), jnp.float32),
            ]
        )[None].astype(jnp.float32)
        run = _k.make_project(n + pad)
        out_t = run(
            mt.astype(jnp.float32), st.astype(jnp.float32), qt.astype(jnp.float32),
            ot.astype(jnp.float32), sh0.astype(jnp.float32), cam_vec,
        )
        return out_t.T[:n]

    def fwd_fwd(gm):
        return fwd(gm), gm

    def fwd_bwd(gm, ct):
        _, vjp = jax.vjp(lambda m: project_ref(m, cam, near=near), gm)
        return vjp(ct)

    fwd.defvjp(fwd_fwd, fwd_bwd)
    return fwd(g)
