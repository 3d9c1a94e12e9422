"""EWA projection of 3D Gaussians to screen-space splats.

Produces the packed splat representation that the distributed pipeline
communicates between Gaussian-owner shards and pixel-renderer shards.
This is the key data-volume insight adapted from Grendel-GS: the projected
2D state (PACKED_DIM=11 floats) is what crosses the interconnect, not the
full 3D parameter state (11 + 3K·floats with SH).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gaussians as G
from repro.core.scopes import scoped

# Packed splat layout (dim PACKED_DIM along last axis)
MX, MY, CA, CB, CC, OP, CR, CG, CB_, DEPTH, RAD = range(11)
PACKED_DIM = 11


class Camera(NamedTuple):
    """Pinhole camera. All leaves are arrays so cameras batch/vmap cleanly."""

    viewmat: jax.Array  # (4,4) world -> camera
    fx: jax.Array       # ()
    fy: jax.Array       # ()
    cx: jax.Array       # ()
    cy: jax.Array       # ()

    @property
    def campos(self) -> jax.Array:
        R = self.viewmat[:3, :3]
        t = self.viewmat[:3, 3]
        return -jnp.matmul(R.T, t, precision=jax.lax.Precision.HIGHEST)


def look_at_camera(eye, target, up, fx, fy, cx, cy) -> Camera:
    eye = jnp.asarray(eye, jnp.float32)
    target = jnp.asarray(target, jnp.float32)
    up = jnp.asarray(up, jnp.float32)
    fwd = target - eye
    fwd = fwd / (jnp.linalg.norm(fwd) + 1e-12)
    right = jnp.cross(fwd, up)
    right = right / (jnp.linalg.norm(right) + 1e-12)
    down = jnp.cross(fwd, right)  # camera +y points down (image convention)
    R = jnp.stack([right, down, fwd], axis=0)  # world -> cam rows
    t = -jnp.matmul(R, eye, precision=jax.lax.Precision.HIGHEST)
    viewmat = jnp.eye(4, dtype=jnp.float32).at[:3, :3].set(R).at[:3, 3].set(t)
    return Camera(viewmat, jnp.float32(fx), jnp.float32(fy), jnp.float32(cx), jnp.float32(cy))


@scoped("project")
def project(
    g: G.GaussianModel,
    cam: Camera,
    *,
    near: float = 0.01,
    blur: float = 0.3,
    max_radius: float = 1e4,
) -> jax.Array:
    """Project all Gaussians for one camera. Returns packed splats (N, 11).

    Invalid (behind-camera) Gaussians get opacity 0, radius 0, depth +inf so a
    depth sort pushes them to the back and compositing ignores them.
    """
    R = cam.viewmat[:3, :3]
    tvec = cam.viewmat[:3, 3]
    p_cam = G.small_matmul(g.means[:, None, :], R.T)[:, 0] + tvec  # (N,3)
    x, y, z = p_cam[:, 0], p_cam[:, 1], p_cam[:, 2]
    valid = z > near
    zc = jnp.where(valid, z, 1.0)  # avoid div-by-0 in dead lanes

    mean_x = cam.fx * x / zc + cam.cx
    mean_y = cam.fy * y / zc + cam.cy

    # EWA: cov2d = J W cov3d W^T J^T (+ low-pass blur)
    cov3d = G.covariance3d(g)  # (N,3,3)
    inv_z = 1.0 / zc
    inv_z2 = inv_z * inv_z
    # J rows: d(u)/d(p_cam), d(v)/d(p_cam)
    J = jnp.zeros((g.n, 2, 3), jnp.float32)
    J = J.at[:, 0, 0].set(cam.fx * inv_z)
    J = J.at[:, 0, 2].set(-cam.fx * x * inv_z2)
    J = J.at[:, 1, 1].set(cam.fy * inv_z)
    J = J.at[:, 1, 2].set(-cam.fy * y * inv_z2)
    JW = G.small_matmul(J, R)  # (N,2,3)
    cov2d = G.small_matmul(G.small_matmul(JW, cov3d), jnp.swapaxes(JW, -1, -2))  # (N,2,2)
    a = cov2d[:, 0, 0] + blur
    b = cov2d[:, 0, 1]
    c = cov2d[:, 1, 1] + blur

    det = a * c - b * b
    det = jnp.maximum(det, 1e-12)
    inv_det = 1.0 / det
    conic_a = c * inv_det
    conic_b = -b * inv_det
    conic_c = a * inv_det

    mid = 0.5 * (a + c)
    lam1 = mid + jnp.sqrt(jnp.maximum(mid * mid - det, 0.0))
    radius = jnp.minimum(jnp.ceil(3.0 * jnp.sqrt(jnp.maximum(lam1, 0.0))), max_radius)

    opac = G.opacities(g)
    dirs = g.means - cam.campos
    dirs = dirs / (jnp.linalg.norm(dirs, axis=-1, keepdims=True) + 1e-12)
    rgb = jnp.clip(G.eval_sh(g.sh, dirs), 0.0, 1.0)

    opac = jnp.where(valid, opac, 0.0)
    radius = jnp.where(valid, radius, 0.0)
    depth = jnp.where(valid, z, jnp.inf)

    packed = jnp.stack(
        [mean_x, mean_y, conic_a, conic_b, conic_c, opac, rgb[:, 0], rgb[:, 1], rgb[:, 2], depth, radius],
        axis=-1,
    )
    return packed


def project_bounds_np(
    g: G.GaussianModel,
    cam: Camera,
    idx: np.ndarray | None = None,
    *,
    near: float = 0.01,
    blur: float = 0.3,
    max_radius: float = 1e4,
    rel_pad: float = 1e-3,
    pad_px: float = 1.0,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Conservative host-side screen bounds for a subset of Gaussians.

    Float64 numpy mirror of :func:`project`'s (mean_x, mean_y, radius) math
    — the only splat quantities tile binning looks at — for world-space
    invalidation: the serving stack maps changed Gaussians to the screen
    tiles they can touch without a device round-trip. Returns ``(mx, my,
    rad)`` with ``rad == 0`` for Gaussians the rasterizer would cull.

    Conservatism, not bit-equality, is the contract: the jitted f32 path
    rounds differently, so every radius is padded by ``rel_pad``
    (relative) plus ``pad_px`` pixels, and the near-plane cut keeps a
    slack band of splats the f32 test might admit. A Gaussian outside the
    padded bound here is guaranteed outside the rasterizer's bound.
    """
    means = np.asarray(g.means, np.float64)
    log_scales = np.asarray(g.log_scales, np.float64)
    quats = np.asarray(g.quats, np.float64)
    if idx is not None:
        sel = np.asarray(idx).reshape(-1)
        means, log_scales, quats = means[sel], log_scales[sel], quats[sel]
    vm = np.asarray(cam.viewmat, np.float64)
    R, tvec = vm[:3, :3], vm[:3, 3]
    p_cam = means @ R.T + tvec
    x, y, z = p_cam[:, 0], p_cam[:, 1], p_cam[:, 2]
    valid = z > near * (1.0 - 1e-4)  # slack: admit what f32 might admit
    zc = np.where(valid, z, 1.0)

    fx = float(np.asarray(cam.fx))
    fy = float(np.asarray(cam.fy))
    mx = fx * x / zc + float(np.asarray(cam.cx))
    my = fy * y / zc + float(np.asarray(cam.cy))

    # world covariance R S S^T R^T (gaussians.quat_to_rotmat / covariance3d)
    q = quats / (np.linalg.norm(quats, axis=-1, keepdims=True) + 1e-12)
    w, qx, qy, qz = q[:, 0], q[:, 1], q[:, 2], q[:, 3]
    rot = np.empty((q.shape[0], 3, 3), np.float64)
    rot[:, 0, 0] = 1 - 2 * (qy * qy + qz * qz)
    rot[:, 0, 1] = 2 * (qx * qy - w * qz)
    rot[:, 0, 2] = 2 * (qx * qz + w * qy)
    rot[:, 1, 0] = 2 * (qx * qy + w * qz)
    rot[:, 1, 1] = 1 - 2 * (qx * qx + qz * qz)
    rot[:, 1, 2] = 2 * (qy * qz - w * qx)
    rot[:, 2, 0] = 2 * (qx * qz - w * qy)
    rot[:, 2, 1] = 2 * (qy * qz + w * qx)
    rot[:, 2, 2] = 1 - 2 * (qx * qx + qy * qy)
    RS = rot * np.exp(log_scales)[:, None, :]
    cov3d = RS @ np.swapaxes(RS, -1, -2)

    inv_z = 1.0 / zc
    J = np.zeros((means.shape[0], 2, 3), np.float64)
    J[:, 0, 0] = fx * inv_z
    J[:, 0, 2] = -fx * x * inv_z * inv_z
    J[:, 1, 1] = fy * inv_z
    J[:, 1, 2] = -fy * y * inv_z * inv_z
    JW = J @ R
    cov2d = JW @ cov3d @ np.swapaxes(JW, -1, -2)
    a = cov2d[:, 0, 0] + blur
    b = cov2d[:, 0, 1]
    c = cov2d[:, 1, 1] + blur
    det = np.maximum(a * c - b * b, 1e-12)
    mid = 0.5 * (a + c)
    lam1 = mid + np.sqrt(np.maximum(mid * mid - det, 0.0))
    rad = np.minimum(np.ceil(3.0 * np.sqrt(np.maximum(lam1, 0.0))), max_radius)
    rad = rad * (1.0 + rel_pad) + pad_px
    rad = np.where(valid, rad, 0.0)
    return mx, my, rad


@jax.custom_vjp
def permute_rows(x: jax.Array, order: jax.Array) -> jax.Array:
    """``x[order]`` for a permutation ``order``, whose backward is the
    cotangent gathered through the inverse permutation. Autodiff of the plain
    gather would transpose it to a scatter-add, which does not know that
    ``order`` is a permutation; a row gather of the same shape is cheaper."""
    return x[order]


def _permute_rows_fwd(x, order):
    return x[order], order


def _permute_rows_bwd(order, g):
    # the inverse permutation by an int32 sort of the order
    return g[jnp.argsort(order)], None


permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


@scoped("depth_sort")
def sort_by_depth(packed: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Depth-sort packed splats front-to-back. Returns (sorted_packed, order).

    The ordering is treated as non-differentiable (as in the CUDA 3D-GS
    rasterizer): gradients flow through the gathered values, not the order,
    and go back as a gather through the inverse permutation
    (``permute_rows``).
    """
    depth = jax.lax.stop_gradient(packed[:, DEPTH])
    # depth is > near or +inf, and non-negative floats order like their int32
    # bit patterns; XLA compiles an int32 sort for the TPU in half the time
    order = jnp.argsort(jax.lax.bitcast_convert_type(depth, jnp.int32))
    return permute_rows(packed, order), order
