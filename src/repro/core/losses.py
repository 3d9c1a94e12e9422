"""Training losses and image-quality metrics (the paper's metric stack).

- L1 + D-SSIM training loss with lambda=0.2 (3D-GS defaults, used by both
  Sewell et al. and the paper).
- PSNR / SSIM metrics for Tables II-III analogues.
- LPIPS proxy: we cannot ship pretrained VGG weights offline, so we report a
  multi-scale gradient-magnitude perceptual distance ("gmsd_proxy") clearly
  labeled as a proxy in EXPERIMENTS.md.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def l1_loss(pred: jax.Array, target: jax.Array) -> jax.Array:
    return jnp.mean(jnp.abs(pred - target))


def _gaussian_window(size: int = 11, sigma: float = 1.5) -> jax.Array:
    x = jnp.arange(size, dtype=jnp.float32) - (size - 1) / 2.0
    g = jnp.exp(-(x**2) / (2 * sigma**2))
    g = g / jnp.sum(g)
    return jnp.outer(g, g)


def ssim(img0: jax.Array, img1: jax.Array, *, window_size: int = 11) -> jax.Array:
    """SSIM over (H,W,C) images in [0,1]. Matches the standard formulation."""
    c1, c2 = 0.01**2, 0.03**2
    win = _gaussian_window(window_size)[:, :, None, None]  # (k,k,1,1)

    def filt(x):
        # (H,W,C) -> depthwise conv
        x = jnp.moveaxis(x, -1, 0)[:, None]  # (C,1,H,W)
        k = jnp.broadcast_to(jnp.moveaxis(win, (0, 1), (2, 3)), (1, 1, window_size, window_size))
        y = jax.lax.conv_general_dilated(x, k, (1, 1), "SAME", precision=jax.lax.Precision.HIGHEST)
        return jnp.moveaxis(y[:, 0], 0, -1)

    mu0, mu1 = filt(img0), filt(img1)
    mu00, mu11, mu01 = mu0 * mu0, mu1 * mu1, mu0 * mu1
    s00 = filt(img0 * img0) - mu00
    s11 = filt(img1 * img1) - mu11
    s01 = filt(img0 * img1) - mu01
    num = (2 * mu01 + c1) * (2 * s01 + c2)
    den = (mu00 + mu11 + c1) * (s00 + s11 + c2)
    return jnp.mean(num / den)


def dssim(img0: jax.Array, img1: jax.Array) -> jax.Array:
    return (1.0 - ssim(img0, img1)) / 2.0


def gs_loss(pred: jax.Array, target: jax.Array, *, lam: float = 0.2) -> jax.Array:
    """(1-lam)*L1 + lam*D-SSIM — the 3D-GS training loss used in the paper."""
    return (1.0 - lam) * l1_loss(pred, target) + lam * dssim(pred, target)


def psnr(pred: jax.Array, target: jax.Array) -> jax.Array:
    mse = jnp.mean((pred - target) ** 2)
    return -10.0 * jnp.log10(jnp.maximum(mse, 1e-12))


def _grad_mag(img: jax.Array) -> jax.Array:
    g = jnp.mean(img, axis=-1)
    gx = g[:, 1:] - g[:, :-1]
    gy = g[1:, :] - g[:-1, :]
    return jnp.sqrt(gx[:-1, :] ** 2 + gy[:, :-1] ** 2 + 1e-12)


def lpips_proxy(img0: jax.Array, img1: jax.Array, *, scales: int = 3) -> jax.Array:
    """Multi-scale gradient-magnitude dissimilarity in [0,~1] (LPIPS stand-in).

    NOT LPIPS — a deterministic perceptual-distance proxy usable offline.
    Lower is better, like LPIPS; reported as `lpips_proxy` everywhere.
    """
    total = 0.0
    a, b = img0, img1
    for _ in range(scales):
        ga, gb = _grad_mag(a), _grad_mag(b)
        c = 0.0026
        sim = (2 * ga * gb + c) / (ga * ga + gb * gb + c)
        total = total + (1.0 - jnp.mean(sim))
        if min(a.shape[0], a.shape[1]) >= 4:
            a = 0.25 * (a[0::2, 0::2] + a[1::2, 0::2] + a[0::2, 1::2] + a[1::2, 1::2])
            b = 0.25 * (b[0::2, 0::2] + b[1::2, 0::2] + b[0::2, 1::2] + b[1::2, 1::2])
    return total / scales
