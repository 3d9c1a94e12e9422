"""Gaussian primitive parameterization.

The model state is a pytree of per-Gaussian parameters, matching the 3D-GS
formulation (Kerbl et al. 2023) as used by Sewell et al. and the paper:
means, anisotropic scales (log-space), rotations (quaternions), opacity
(logit-space) and color (spherical-harmonic coefficients; degree 0 by default
for isosurface visualization where color is view-independent shading baked
from the transfer function).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

SH_C0 = 0.28209479177387814


class GaussianModel(NamedTuple):
    """Per-Gaussian learnable parameters. Leading dim N is the Gaussian count."""

    means: jax.Array          # (N, 3) world-space centers
    log_scales: jax.Array     # (N, 3) log of per-axis std-dev
    quats: jax.Array          # (N, 4) rotation quaternion (wxyz, unnormalized)
    opacity_logit: jax.Array  # (N,)  sigmoid^-1 of opacity
    sh: jax.Array             # (N, K, 3) SH coefficients, K = (deg+1)^2

    @property
    def n(self) -> int:
        return self.means.shape[0]

    @property
    def sh_degree(self) -> int:
        return int(np.sqrt(self.sh.shape[1])) - 1


def scales(g: GaussianModel) -> jax.Array:
    return jnp.exp(g.log_scales)


def opacities(g: GaussianModel) -> jax.Array:
    return jax.nn.sigmoid(g.opacity_logit)


def num_params(g: GaussianModel) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(g))


def default_init_scale(points: jax.Array) -> jax.Array:
    """Mean nearest-neighbor spacing estimated from the bounding-box density."""
    points = jnp.asarray(points, jnp.float32)
    lo = jnp.min(points, axis=0)
    hi = jnp.max(points, axis=0)
    vol = jnp.prod(jnp.maximum(hi - lo, 1e-6))
    return jnp.clip((vol / jnp.maximum(points.shape[0], 1)) ** (1.0 / 3.0), 1e-4, 1e2)


def init_from_points(
    points: jax.Array,
    colors: jax.Array | None = None,
    *,
    sh_degree: int = 0,
    init_opacity: float = 0.1,
    init_scale: float | jax.Array | None = None,
    seed: int = 0,
) -> GaussianModel:
    """Seed Gaussians from an isosurface point cloud (the paper's init path).

    ``init_scale`` defaults to a heuristic mean nearest-neighbor distance
    estimated from the bounding-box density (exact kNN is done host-side in
    ``repro.volume.isosurface`` when points come from a real extraction).
    """
    points = jnp.asarray(points, jnp.float32)
    n = points.shape[0]
    if colors is None:
        colors = jnp.full((n, 3), 0.5, jnp.float32)
    k = (sh_degree + 1) ** 2
    sh = jnp.zeros((n, k, 3), jnp.float32)
    # DC term chosen so that degree-0 eval reproduces `colors` exactly.
    sh = sh.at[:, 0, :].set((jnp.asarray(colors, jnp.float32) - 0.5) / SH_C0)

    if init_scale is None:
        init_scale = default_init_scale(points)
    log_scales = jnp.broadcast_to(jnp.log(jnp.asarray(init_scale, jnp.float32)), (n, 3)).astype(jnp.float32)

    quats = jnp.zeros((n, 4), jnp.float32).at[:, 0].set(1.0)
    opacity_logit = jnp.full((n,), float(np.log(init_opacity / (1 - init_opacity))), jnp.float32)
    return GaussianModel(points, log_scales, quats, opacity_logit, sh)


def quat_to_rotmat(quats: jax.Array) -> jax.Array:
    """(N,4) wxyz quaternions (unnormalized) -> (N,3,3) rotation matrices."""
    q = quats / (jnp.linalg.norm(quats, axis=-1, keepdims=True) + 1e-12)
    w, x, y, z = q[..., 0], q[..., 1], q[..., 2], q[..., 3]
    r00 = 1 - 2 * (y * y + z * z)
    r01 = 2 * (x * y - w * z)
    r02 = 2 * (x * z + w * y)
    r10 = 2 * (x * y + w * z)
    r11 = 1 - 2 * (x * x + z * z)
    r12 = 2 * (y * z - w * x)
    r20 = 2 * (x * z - w * y)
    r21 = 2 * (y * z + w * x)
    r22 = 1 - 2 * (x * x + y * y)
    return jnp.stack(
        [jnp.stack([r00, r01, r02], -1), jnp.stack([r10, r11, r12], -1), jnp.stack([r20, r21, r22], -1)], -2
    )


def small_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` for stacks of tiny matrices (2x3, 3x3), as exact f32
    multiply-adds. As a dot, XLA on TPU runs them on the MXU in one bf16 pass
    and pads every 3x3 block to a full tile (GiBs of temporaries at 500k
    Gaussians)."""
    return jnp.sum(a[..., :, :, None] * b[..., None, :, :], axis=-2)


def covariance3d(g: GaussianModel) -> jax.Array:
    """(N,3,3) world-space covariance R S S^T R^T."""
    R = quat_to_rotmat(g.quats)
    s = scales(g)
    RS = R * s[:, None, :]
    return small_matmul(RS, jnp.swapaxes(RS, -1, -2))


def eval_sh(sh: jax.Array, dirs: jax.Array) -> jax.Array:
    """Evaluate SH color for view directions.

    sh: (N, K, 3), dirs: (N, 3) unit vectors (camera->gaussian). Returns (N,3)
    in [0,1]-ish (clipped downstream). Supports degrees 0..3.
    """
    k = sh.shape[1]
    c = SH_C0 * sh[:, 0, :]
    if k > 1:
        x, y, z = dirs[:, 0:1], dirs[:, 1:2], dirs[:, 2:3]
        c = c + 0.4886025119029199 * (-y * sh[:, 1, :] + z * sh[:, 2, :] - x * sh[:, 3, :])
    if k > 4:
        x, y, z = dirs[:, 0:1], dirs[:, 1:2], dirs[:, 2:3]
        xx, yy, zz, xy, yz, xz = x * x, y * y, z * z, x * y, y * z, x * z
        c = c + (
            1.0925484305920792 * xy * sh[:, 4, :]
            + -1.0925484305920792 * yz * sh[:, 5, :]
            + 0.31539156525252005 * (2.0 * zz - xx - yy) * sh[:, 6, :]
            + -1.0925484305920792 * xz * sh[:, 7, :]
            + 0.5462742152960396 * (xx - yy) * sh[:, 8, :]
        )
    if k > 9:
        x, y, z = dirs[:, 0:1], dirs[:, 1:2], dirs[:, 2:3]
        xx, yy, zz = x * x, y * y, z * z
        c = c + (
            -0.5900435899266435 * y * (3 * xx - yy) * sh[:, 9, :]
            + 2.890611442640554 * x * y * z * sh[:, 10, :]
            + -0.4570457994644658 * y * (4 * zz - xx - yy) * sh[:, 11, :]
            + 0.3731763325901154 * z * (2 * zz - 3 * xx - 3 * yy) * sh[:, 12, :]
            + -0.4570457994644658 * x * (4 * zz - xx - yy) * sh[:, 13, :]
            + 1.445305721320277 * z * (xx - yy) * sh[:, 14, :]
            + -0.5900435899266435 * x * (xx - 3 * yy) * sh[:, 15, :]
        )
    return c + 0.5
