"""Pallas TPU kernel: EWA projection of a block of Gaussians (deg-0 SH).

Pure VPU work — every quantity is an elementwise formula over a lane-block
of Gaussians, laid out SoA-transposed so the Gaussian index is the 128-lane
dimension: means (3,N), scales (3,N), quats (4,N), opacity (N,), sh0 (3,N)
-> packed (11,N). Camera scalars ride in a replicated (1,32) VMEM block.

Covariance path avoids any 3x3 matrix ops: cov3d's six unique entries are
computed as sums over the three scaled rotation columns, then folded with
the two JW rows — ~90 fused vector ops per lane-block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import interpret_mode

BLOCK_N = 1024
CAM_SLOTS = 32  # viewmat(16), fx, fy, cx, cy, near, campos(3) -> padded to 32


def _kernel(means_ref, scales_ref, quats_ref, opac_ref, sh0_ref, cam_ref, out_ref, *, blur):
    cam = cam_ref[0]
    rv = [[cam[4 * i + j] for j in range(4)] for i in range(3)]  # rows of viewmat[:3]
    fx, fy, cx, cy, near = cam[16], cam[17], cam[18], cam[19], cam[20]

    mx, my_, mz = means_ref[0], means_ref[1], means_ref[2]
    sx = jnp.exp(scales_ref[0])
    sy = jnp.exp(scales_ref[1])
    sz = jnp.exp(scales_ref[2])
    qw, qx, qy, qz = quats_ref[0], quats_ref[1], quats_ref[2], quats_ref[3]
    qn = jax.lax.rsqrt(qw * qw + qx * qx + qy * qy + qz * qz + 1e-24)
    qw, qx, qy, qz = qw * qn, qx * qn, qy * qn, qz * qn

    # rotation matrix columns scaled: col_k = s_k * R[:, k]
    r = [
        [1 - 2 * (qy * qy + qz * qz), 2 * (qx * qy - qw * qz), 2 * (qx * qz + qw * qy)],
        [2 * (qx * qy + qw * qz), 1 - 2 * (qx * qx + qz * qz), 2 * (qy * qz - qw * qx)],
        [2 * (qx * qz - qw * qy), 2 * (qy * qz + qw * qx), 1 - 2 * (qx * qx + qy * qy)],
    ]
    s2 = [sx * sx, sy * sy, sz * sz]
    # cov3d_ij = sum_k s_k^2 r[i][k] r[j][k]
    cov = {}
    for i in range(3):
        for j in range(i, 3):
            cov[(i, j)] = sum(s2[k] * r[i][k] * r[j][k] for k in range(3))

    def cov3(i, j):
        return cov[(i, j)] if i <= j else cov[(j, i)]

    # camera-space position
    pc = [rv[i][0] * mx + rv[i][1] * my_ + rv[i][2] * mz + rv[i][3] for i in range(3)]
    x, y, z = pc
    valid = z > near
    zc = jnp.where(valid, z, 1.0)
    inv_z = 1.0 / zc
    inv_z2 = inv_z * inv_z

    mean_x = fx * x * inv_z + cx
    mean_y = fy * y * inv_z + cy

    # JW rows (2x3): jw[a][k] = J[a,:] @ Rv[:,k]
    jw0 = [fx * inv_z * rv[0][k] - fx * x * inv_z2 * rv[2][k] for k in range(3)]
    jw1 = [fy * inv_z * rv[1][k] - fy * y * inv_z2 * rv[2][k] for k in range(3)]
    v0 = [sum(cov3(k, l) * jw0[l] for l in range(3)) for k in range(3)]
    v1 = [sum(cov3(k, l) * jw1[l] for l in range(3)) for k in range(3)]
    a = sum(jw0[k] * v0[k] for k in range(3)) + blur
    b = sum(jw1[k] * v0[k] for k in range(3))
    c = sum(jw1[k] * v1[k] for k in range(3)) + blur

    det = jnp.maximum(a * c - b * b, 1e-12)
    inv_det = 1.0 / det
    conic_a = c * inv_det
    conic_b = -b * inv_det
    conic_c = a * inv_det
    mid = 0.5 * (a + c)
    lam1 = mid + jnp.sqrt(jnp.maximum(mid * mid - det, 0.0))
    radius = jnp.minimum(jnp.ceil(3.0 * jnp.sqrt(jnp.maximum(lam1, 0.0))), 1e4)

    opac = jax.nn.sigmoid(opac_ref[0])
    sh_c0 = 0.28209479177387814
    cr = jnp.clip(sh_c0 * sh0_ref[0] + 0.5, 0.0, 1.0)
    cg = jnp.clip(sh_c0 * sh0_ref[1] + 0.5, 0.0, 1.0)
    cb = jnp.clip(sh_c0 * sh0_ref[2] + 0.5, 0.0, 1.0)

    opac = jnp.where(valid, opac, 0.0)
    radius = jnp.where(valid, radius, 0.0)
    depth = jnp.where(valid, z, jnp.inf)

    for slot, val in enumerate(
        [mean_x, mean_y, conic_a, conic_b, conic_c, opac, cr, cg, cb, depth, radius]
    ):
        out_ref[slot] = val


@functools.lru_cache(maxsize=None)
def make_project(n_padded: int, blur: float = 0.3, interpret=None):
    if interpret is None:
        interpret = interpret_mode()
    kern = functools.partial(_kernel, blur=blur)
    grid = (n_padded // BLOCK_N,)

    def run(means_t, scales_t, quats_t, opac, sh0_t, cam_vec):
        return pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[
                pl.BlockSpec((3, BLOCK_N), lambda i: (0, i)),
                pl.BlockSpec((3, BLOCK_N), lambda i: (0, i)),
                pl.BlockSpec((4, BLOCK_N), lambda i: (0, i)),
                pl.BlockSpec((1, BLOCK_N), lambda i: (0, i)),
                pl.BlockSpec((3, BLOCK_N), lambda i: (0, i)),
                pl.BlockSpec((1, CAM_SLOTS), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((11, BLOCK_N), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((11, n_padded), jnp.float32),
            interpret=interpret,
        )(means_t, scales_t, quats_t, opac, sh0_t, cam_vec)

    return run
