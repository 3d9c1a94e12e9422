"""Ground-truth isosurface renderer (ray-marched, jnp).

Stand-in for the ParaView renders the paper trains against: fixed-step ray
marching with sign-change detection, bisection refinement, central-difference
normals and Lambertian shading (identical shading constants to
``isosurface.shade`` so point-cloud color init matches the GT images).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.projection import Camera
from repro.volume.datasets import VolumeSpec
from repro.volume.isosurface import AMBIENT, BASE_COLOR, LIGHT_DIR


def _trilinear(field: jax.Array, p: jax.Array, extent: float) -> jax.Array:
    """Sample scalar field at world points p (..., 3); clamps at the border."""
    res = field.shape[0]
    g = (p + extent) / (2 * extent) * (res - 1)
    g = jnp.clip(g, 0.0, res - 1.001)
    i0 = jnp.floor(g).astype(jnp.int32)
    f = g - i0
    i1 = jnp.minimum(i0 + 1, res - 1)

    def at(ix, iy, iz):
        return field[ix, iy, iz]

    c000 = at(i0[..., 0], i0[..., 1], i0[..., 2])
    c100 = at(i1[..., 0], i0[..., 1], i0[..., 2])
    c010 = at(i0[..., 0], i1[..., 1], i0[..., 2])
    c110 = at(i1[..., 0], i1[..., 1], i0[..., 2])
    c001 = at(i0[..., 0], i0[..., 1], i1[..., 2])
    c101 = at(i1[..., 0], i0[..., 1], i1[..., 2])
    c011 = at(i0[..., 0], i1[..., 1], i1[..., 2])
    c111 = at(i1[..., 0], i1[..., 1], i1[..., 2])
    fx, fy, fz = f[..., 0], f[..., 1], f[..., 2]
    c00 = c000 * (1 - fx) + c100 * fx
    c10 = c010 * (1 - fx) + c110 * fx
    c01 = c001 * (1 - fx) + c101 * fx
    c11 = c011 * (1 - fx) + c111 * fx
    c0 = c00 * (1 - fy) + c10 * fy
    c1 = c01 * (1 - fy) + c11 * fy
    return c0 * (1 - fz) + c1 * fz


@partial(jax.jit, static_argnames=("img_h", "img_w", "n_steps", "extent"))
def render_isosurface(
    vol_field: jax.Array,
    isovalue: float,
    cam: Camera,
    *,
    img_h: int,
    img_w: int,
    extent: float = 1.0,
    n_steps: int = 192,
    bg=(0.0, 0.0, 0.0),
) -> jax.Array:
    """Render one GT view, (H, W, 3) in [0,1]."""
    field = vol_field - isovalue
    R = cam.viewmat[:3, :3]
    campos = cam.campos

    ys, xs = jnp.meshgrid(jnp.arange(img_h) + 0.5, jnp.arange(img_w) + 0.5, indexing="ij")
    dirs_cam = jnp.stack(
        [(xs - cam.cx) / cam.fx, (ys - cam.cy) / cam.fy, jnp.ones_like(xs)], -1
    )
    dirs = jnp.matmul(dirs_cam, R, precision=jax.lax.Precision.HIGHEST)  # cam->world (R rows are world axes of cam frame)
    dirs = dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)

    # march from the camera through the volume's bounding sphere
    t0 = jnp.maximum(jnp.linalg.norm(campos) - 1.9 * extent, 0.02)
    t1 = jnp.linalg.norm(campos) + 1.9 * extent
    ts = jnp.linspace(t0, t1, n_steps)

    def sample(t):
        return _trilinear(field, campos + t * dirs[..., None, :].squeeze(-2), extent)

    vals = jax.vmap(lambda t: _trilinear(field, campos + t * dirs, extent))(ts)  # (S,H,W)
    sign_change = (vals[:-1] * vals[1:]) < 0
    first = jnp.argmax(sign_change, axis=0)  # (H,W) first crossing step
    hit = jnp.any(sign_change, axis=0)
    f0 = jnp.take_along_axis(vals, first[None], axis=0)[0]
    f1 = jnp.take_along_axis(vals, (first + 1)[None], axis=0)[0]
    tt = ts[first] + (ts[first + 1] - ts[first]) * f0 / (f0 - f1 + 1e-12)
    p_hit = campos + tt[..., None] * dirs

    # bisection refinement (4 rounds)
    lo = ts[first]
    hi = ts[first + 1]
    flo = f0
    for _ in range(4):
        mid = 0.5 * (lo + hi)
        fm = _trilinear(field, campos + mid[..., None] * dirs, extent)
        go_lo = (flo * fm) < 0
        hi = jnp.where(go_lo, mid, hi)
        lo = jnp.where(go_lo, lo, mid)
        flo = jnp.where(go_lo, flo, fm)
    tt = 0.5 * (lo + hi)
    p_hit = campos + tt[..., None] * dirs

    eps = 2 * extent / field.shape[0]
    grad = jnp.stack(
        [
            _trilinear(field, p_hit + jnp.float32([eps, 0, 0]), extent)
            - _trilinear(field, p_hit - jnp.float32([eps, 0, 0]), extent),
            _trilinear(field, p_hit + jnp.float32([0, eps, 0]), extent)
            - _trilinear(field, p_hit - jnp.float32([0, eps, 0]), extent),
            _trilinear(field, p_hit + jnp.float32([0, 0, eps]), extent)
            - _trilinear(field, p_hit - jnp.float32([0, 0, eps]), extent),
        ],
        -1,
    )
    n = grad / (jnp.linalg.norm(grad, axis=-1, keepdims=True) + 1e-12)
    l = jnp.asarray(LIGHT_DIR) / jnp.linalg.norm(jnp.asarray(LIGHT_DIR))
    lam = jnp.clip(-jnp.matmul(n, l, precision=jax.lax.Precision.HIGHEST), 0.0, 1.0)
    color = jnp.asarray(BASE_COLOR) * (AMBIENT + (1 - AMBIENT) * lam[..., None])
    bg_arr = jnp.broadcast_to(jnp.asarray(bg, jnp.float32), color.shape)
    return jnp.clip(jnp.where(hit[..., None], color, bg_arr), 0.0, 1.0)
