"""Distribution primitives for Grendel-style 3D-GS training on a TPU mesh.

Mapping (see DESIGN.md §5):
  - Gaussians sharded over mesh axis ``model``  (Grendel: "each GPU holds a
    shard of the global point cloud and Gaussian parameters").
  - Training views sharded over mesh axis ``data`` (and ``pod`` when present).
  - Within one view, horizontal pixel strips sharded over ``model`` — so every
    device owns both a Gaussian shard and a pixel block, exactly Grendel's
    worker model, expressed on a 2D mesh.

Communication per step (all JAX-native collectives inside shard_map):
  all_gather(projected splats, "model")   owner shard -> renderers (11 floats
                                          per Gaussian, not the full 3D state;
                                          ``exchange_splats``)
  psum_scatter(splat grads, "model")      renderers -> owner shard (implicit:
                                          this is just the autodiff transpose
                                          of the all_gather)
  psum(loss sums, "data" + "model")       the global loss, replicated
  psum(packed param grads, "data")        the paper's fused all-reduce
  ppermute(strip halos, "model")          distributed SSIM boundary exchange

The train step's ``shard_map`` checks which mesh axes each value varies
over (``check_vma``), so autodiff transposes each collective by its type:
the replicated loss hands every shard an unsummed cotangent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh

from repro.core.scopes import scoped


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...] = ("data", "model"), *,
              devices=None) -> Mesh:
    """The mesh every entry point trains and serves on.

    Axes are ``Auto``: shardings come from ``jax.device_put``, ``jit``
    out_shardings and ``shard_map`` specs, and eager ops on sharded arrays
    need no mesh context (``jax.make_mesh`` defaults to ``Explicit`` axes,
    under which they do). ``devices`` picks a subset, e.g. one chip of four.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices)


@scoped("splat_exchange")
def exchange_splats(x: jax.Array, axis_name: str, *, axis: int) -> jax.Array:
    """Every model shard's rows of ``x``, concatenated along ``axis`` in
    shard order: what each shard owns goes to every renderer. Backward, the
    transpose is a psum_scatter that hands each owner the cotangent of its
    own rows, summed over the renderers."""
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)


def halo_exchange_rows(x: jax.Array, halo: int, axis_name: str) -> jax.Array:
    """Extend a (h, W, C) row-strip with `halo` rows from mesh neighbors.

    Workers at the image boundary receive zeros (ppermute semantics), which
    matches zero-padded SAME convolution on the full image.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        pad = jnp.zeros((halo,) + x.shape[1:], x.dtype)
        return jnp.concatenate([pad, x, pad], axis=0)
    # worker i's top rows go to worker i-1 (they sit just below i-1's strip)
    below = jax.lax.ppermute(x[:halo], axis_name, [(i, i - 1) for i in range(1, n)])
    # worker i's bottom rows go to worker i+1 (just above i+1's strip)
    above = jax.lax.ppermute(x[-halo:], axis_name, [(i, i + 1) for i in range(n - 1)])
    return jnp.concatenate([above, x, below], axis=0)


def _window(size: int = 11, sigma: float = 1.5) -> jax.Array:
    x = jnp.arange(size, dtype=jnp.float32) - (size - 1) / 2.0
    g = jnp.exp(-(x**2) / (2 * sigma**2))
    g = g / jnp.sum(g)
    return jnp.outer(g, g)


def ssim_l1_sums(
    pred: jax.Array,   # (h, W, 3) local pixel strip
    gt: jax.Array,     # (h, W, 3)
    axis_name: str | None,
    *,
    window_size: int = 11,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Local (ssim_map_sum, l1_sum, pixel_count) for the distributed loss.

    When ``axis_name`` is given, the strip is extended with neighbor halos so
    the result psum'd across workers is *bit-identical in exact arithmetic*
    to single-device SAME-padded SSIM over the full image.
    """
    halo = window_size // 2
    stack = jnp.concatenate(
        [pred, gt, pred * pred, gt * gt, pred * gt], axis=-1
    )  # (h, W, 15)
    if axis_name is not None:
        ext = halo_exchange_rows(stack, halo, axis_name)
    else:
        pad = jnp.zeros((halo,) + stack.shape[1:], stack.dtype)
        ext = jnp.concatenate([pad, stack, pad], axis=0)
    # zero-pad W (SAME behavior), VALID conv over the extended strip
    ext = jnp.pad(ext, ((0, 0), (halo, halo), (0, 0)))
    w = _window(window_size)
    # depthwise: run each of the 15 stat channels independently
    y = jax.lax.conv_general_dilated(
        jnp.moveaxis(ext, -1, 0)[None],  # (1,15,h+2p,W+2p)
        jnp.tile(w[None, None], (15, 1, 1, 1)),  # (15,1,k,k)
        (1, 1),
        "VALID",
        feature_group_count=15,
        # E[x^2] - E[x]^2 cancels: one bf16 pass (the TPU default for f32)
        # leaves variances of noise, and SSIM outside [-1, 1]
        precision=jax.lax.Precision.HIGHEST,
    )[0]  # (15, h, W)
    mu0, mu1 = y[0:3], y[3:6]
    e00, e11, e01 = y[6:9], y[9:12], y[12:15]
    s00 = e00 - mu0 * mu0
    s11 = e11 - mu1 * mu1
    s01 = e01 - mu0 * mu1
    c1, c2 = 0.01**2, 0.03**2
    ssim_map = ((2 * mu0 * mu1 + c1) * (2 * s01 + c2)) / ((mu0 * mu0 + mu1 * mu1 + c1) * (s00 + s11 + c2))
    l1_sum = jnp.sum(jnp.abs(pred - gt))
    count = jnp.asarray(pred.size, jnp.float32)
    return jnp.sum(ssim_map), l1_sum, count


@scoped("loss")
def distributed_gs_loss(
    pred: jax.Array,
    gt: jax.Array,
    *,
    lam: float = 0.2,
    strip_axis: str | None = None,
    reduce_axes: tuple[str, ...] = (),
) -> jax.Array:
    """(1-lam)*L1 + lam*D-SSIM over globally distributed pixels.

    ``pred``/``gt``: (B_local, h_local, W, 3). Returns the *global* scalar
    loss, replicated: the sums are psum'd over ``reduce_axes``, the axes the
    pixels are spread over. Under ``check_vma`` that psum transposes to a
    cotangent each shard takes as it is, so the gradient is that of the one
    global loss on every mesh.
    """
    def per_view(p, g):
        return ssim_l1_sums(p, g, strip_axis)

    ssim_s, l1_s, cnt = jax.vmap(per_view)(pred, gt)
    ssim_s, l1_s, cnt = jnp.sum(ssim_s), jnp.sum(l1_s), jnp.sum(cnt)
    if reduce_axes:
        ssim_s = jax.lax.psum(ssim_s, reduce_axes)
        l1_s = jax.lax.psum(l1_s, reduce_axes)
        cnt = jax.lax.psum(cnt, reduce_axes)
    mean_ssim = ssim_s / cnt
    mean_l1 = l1_s / cnt
    return (1.0 - lam) * mean_l1 + lam * (1.0 - mean_ssim) / 2.0
