"""Plain reference for the Gaussian-splatting configurations.

Written from the 3D Gaussian Splatting formulation (Kerbl et al. 2023) as the
configuration files state it, in straightforward ``jax.numpy`` and
``numpy``, and independent of the program under test: it imports nothing
of it and takes nothing it made (the scene's points, colours and views are
inputs, like a data set).

Per view: EWA projection of every Gaussian (near plane 0.01, low-pass blur
0.3 px, radius = ceil(3 sqrt(largest eigenvalue))); a stable depth sort; for
each 16x16 tile the front-most ``k_per_tile`` splats whose bounding circle
touches the tile, where ``binning`` "hier" first keeps the front-most
``k_per_tile * bin_k_block_mult`` per block of ``bin_block`` x ``bin_block``
tiles (the configuration's two-level rule); front-to-back compositing with
alpha = min(opacity * exp(power), 0.99), splats under 1/255 skipped and the
stop when transmittance would fall under 1e-4; (1 - lambda) L1 +
lambda (1 - SSIM) / 2 with an 11-tap Gaussian window (sigma 1.5, zero
padding); Adam with the stated rates. Tile lists are built on the host by
duplicating each splat into the cells it touches and sorting, as the CUDA
rasterizer does; the compositing, the loss and their gradients run on the
device, in blocks of tiles so that a view fits.

``dtype`` is float32 for the reference. The control renders (projection,
tile lists and compositing) in bfloat16, the precision below the
configuration's float32, and takes the loss and Adam in float32: a
rasterizer in bfloat16 is the step a faster program would take.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SH_C0 = 0.28209479177387814
NEAR = 0.01
BLUR = 0.3
MAX_RADIUS = 1e4
ALPHA_MAX = 0.99
ALPHA_MIN = 1.0 / 255.0
T_EPS = 1e-4
PAD_POSITION = 1e6        # padding Gaussians sit far away ...
PAD_OPACITY_LOGIT = -20.0  # ... and all but transparent
INIT_OPACITY = 0.1
LEAVES = ("means", "log_scales", "quats", "opacity_logit", "sh")
TILES_PER_BLOCK = 256      # tiles composited together on the device


# ------------------------------------------------------------------ model


def init_params(points: np.ndarray, colors: np.ndarray, n_total: int) -> dict:
    """Gaussians at the points: isotropic scale from the points' density,
    opacity 0.1, colour as the degree-0 SH term; padded to ``n_total``."""
    pts = np.asarray(points, np.float32)
    n0 = pts.shape[0]
    lo, hi = pts.min(axis=0), pts.max(axis=0)
    vol = np.prod(np.maximum(hi - lo, np.float32(1e-6)), dtype=np.float32)
    scale = np.clip(np.float32(vol / np.float32(n0)) ** np.float32(1.0 / 3.0), 1e-4, 1e2)
    pad = n_total - n0
    means = np.concatenate([pts, np.full((pad, 3), PAD_POSITION, np.float32)])
    cols = np.concatenate([np.asarray(colors, np.float32), np.zeros((pad, 3), np.float32)])
    logit = np.float32(math.log(INIT_OPACITY / (1 - INIT_OPACITY)))
    opacity = np.full((n_total,), logit, np.float32)
    opacity[n0:] = PAD_OPACITY_LOGIT
    quats = np.zeros((n_total, 4), np.float32)
    quats[:, 0] = 1.0
    return {
        "means": means,
        "log_scales": np.full((n_total, 3), np.log(np.float32(scale)), np.float32),
        "quats": quats,
        "opacity_logit": opacity,
        "sh": ((cols - 0.5) / SH_C0).astype(np.float32),  # degree-0 term per colour
    }


def project(p: dict, cam: dict, dtype=jnp.float32) -> dict:
    """Per-Gaussian screen splat: centre, inverse 2-D covariance, opacity,
    colour, depth and radius (0 for Gaussians behind the near plane)."""
    f = lambda x: jnp.asarray(x).astype(dtype)  # noqa: E731
    vm = f(cam["viewmat"])
    W, t = vm[:3, :3], vm[:3, 3]
    means = f(p["means"])
    pc = jnp.sum(means[:, None, :] * W[None, :, :], axis=-1) + t
    x, y, z = pc[:, 0], pc[:, 1], pc[:, 2]
    valid = z > NEAR
    zc = jnp.where(valid, z, 1)
    fx, fy, cx, cy = (f(cam[k]) for k in ("fx", "fy", "cx", "cy"))
    mx = fx * x / zc + cx
    my = fy * y / zc + cy

    q = f(p["quats"])
    q = q / (jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True)) + 1e-12)
    w, qx, qy, qz = q[:, 0], q[:, 1], q[:, 2], q[:, 3]
    rot = jnp.stack([
        jnp.stack([1 - 2 * (qy * qy + qz * qz), 2 * (qx * qy - w * qz), 2 * (qx * qz + w * qy)], -1),
        jnp.stack([2 * (qx * qy + w * qz), 1 - 2 * (qx * qx + qz * qz), 2 * (qy * qz - w * qx)], -1),
        jnp.stack([2 * (qx * qz - w * qy), 2 * (qy * qz + w * qx), 1 - 2 * (qx * qx + qy * qy)], -1),
    ], -2)
    rs = rot * jnp.exp(f(p["log_scales"]))[:, None, :]
    cov3 = jnp.sum(rs[:, :, None, :] * rs[:, None, :, :], axis=-1)          # R S S^T R^T
    zero = jnp.zeros_like(zc)
    jac = jnp.stack([
        jnp.stack([fx / zc, zero, -fx * x / (zc * zc)], -1),
        jnp.stack([zero, fy / zc, -fy * y / (zc * zc)], -1),
    ], -2)                                                                  # (N, 2, 3)
    m = jnp.sum(jac[:, :, :, None] * W[None, None, :, :], axis=2)           # J W
    mc = jnp.sum(m[:, :, :, None] * cov3[:, None, :, :], axis=2)            # J W cov
    cov2 = jnp.sum(mc[:, :, None, :] * m[:, None, :, :], axis=-1)           # J W cov W^T J^T
    a = cov2[:, 0, 0] + BLUR
    b = cov2[:, 0, 1]
    c = cov2[:, 1, 1] + BLUR
    det = jnp.maximum(a * c - b * b, 1e-12)
    mid = 0.5 * (a + c)
    lam1 = mid + jnp.sqrt(jnp.maximum(mid * mid - det, 0))
    radius = jnp.minimum(jnp.ceil(3 * jnp.sqrt(jnp.maximum(lam1, 0))), MAX_RADIUS)
    opacity = jax.nn.sigmoid(f(p["opacity_logit"]))
    rgb = jnp.clip(SH_C0 * f(p["sh"]) + 0.5, 0, 1)
    return {
        "mx": mx, "my": my, "ca": c / det, "cb": -b / det, "cc": a / det,
        "opacity": jnp.where(valid, opacity, 0), "rgb": rgb,
        "depth": jnp.where(valid, z, jnp.inf), "radius": jnp.where(valid, radius, 0),
    }


# ---------------------------------------------------------- tile lists


def _cells_touched(mx, my, rad, cell_h: int, cell_w: int, ny: int, nx: int):
    """Inclusive cell ranges each bounding circle touches (float32 edges):
    cell (cy, cx) is touched when mx + r >= x0, mx - r <= x0 + w, likewise
    in y, and r > 0."""
    mx, my, rad = (np.asarray(v, np.float32) for v in (mx, my, rad))
    right, left = (mx + rad).astype(np.float64), (mx - rad).astype(np.float64)
    low, high = (my + rad).astype(np.float64), (my - rad).astype(np.float64)
    x0 = np.maximum(np.ceil(left / cell_w - 1), 0)
    x1 = np.minimum(np.floor(right / cell_w), nx - 1)
    y0 = np.maximum(np.ceil(high / cell_h - 1), 0)
    y1 = np.minimum(np.floor(low / cell_h), ny - 1)
    ok = (rad > 0) & (x1 >= x0) & (y1 >= y0)
    nxs = np.where(ok, x1 - x0 + 1, 0).astype(np.int64)
    nys = np.where(ok, y1 - y0 + 1, 0).astype(np.int64)
    return x0.astype(np.int64), y0.astype(np.int64), nxs, nys


def front_k(mx, my, rad, *, cell_h: int, cell_w: int, ny: int, nx: int, k: int):
    """For each cell of an ny x nx grid, the first ``k`` splats (in input
    order, front to back) whose circle touches it. Returns ((cells, k)
    indices, (cells, k) validity)."""
    x0, y0, nxs, nys = _cells_touched(mx, my, rad, cell_h, cell_w, ny, nx)
    per = nxs * nys
    total = int(per.sum())
    if total > 400_000_000:
        raise ValueError(f"{total} splat-cell pairs: the reference cannot hold them")
    splat = np.repeat(np.arange(per.shape[0], dtype=np.int64), per)
    off = np.arange(total, dtype=np.int64) - np.repeat(np.cumsum(per) - per, per)
    cell = (y0[splat] + off // nxs[splat]) * nx + (x0[splat] + off % nxs[splat])
    order = np.argsort(cell, kind="stable")  # keeps splat order within a cell
    cell, splat = cell[order], splat[order]
    first = np.searchsorted(cell, np.arange(ny * nx))
    rank = np.arange(total) - first[cell]
    keep = rank < k
    idx = np.zeros((ny * nx, k), np.int64)
    valid = np.zeros((ny * nx, k), bool)
    idx[cell[keep], rank[keep]] = splat[keep]
    valid[cell[keep], rank[keep]] = True
    return idx, valid


def tile_lists(mx, my, rad, *, img_h: int, img_w: int, tile: int, k: int, binning: str,
               block: int, k_block_mult: int):
    """(tiles, k) front-most splat indices per tile, row-major tiles."""
    ty, tx = img_h // tile, img_w // tile
    if binning == "auto":
        binning = "hier" if ty * tx >= 256 else "flat"
    if binning == "flat":
        return front_k(mx, my, rad, cell_h=tile, cell_w=tile, ny=ty, nx=tx, k=k)
    by, bx = max(min(block, ty), 1), max(min(block, tx), 1)
    k1 = k * k_block_mult
    nby, nbx = ty // by, tx // bx
    bidx, bvalid = front_k(mx, my, rad, cell_h=tile * by, cell_w=tile * bx, ny=nby, nx=nbx, k=k1)
    mx, my, rad = (np.asarray(v, np.float32)[bidx] for v in (mx, my, rad))  # (blocks, k1)
    rad = np.where(bvalid, rad, np.float32(0))
    b = np.arange(nby * nbx)
    t = np.arange(by * bx)
    tile_y = (b // nbx)[:, None] * by + t[None, :] // bx                     # (blocks, by*bx)
    tile_x = (b % nbx)[:, None] * bx + t[None, :] % bx
    x0 = (tile_x * tile).astype(np.float32)[..., None]
    y0 = (tile_y * tile).astype(np.float32)[..., None]
    m = lambda v: v[:, None, :]  # noqa: E731
    touch = ((m(mx) + m(rad) >= x0) & (m(mx) - m(rad) <= x0 + np.float32(tile))
             & (m(my) + m(rad) >= y0) & (m(my) - m(rad) <= y0 + np.float32(tile))
             & (m(rad) > 0))                                                # (blocks, by*bx, k1)
    rank = np.cumsum(touch, axis=-1) - 1
    keep = touch & (rank < k)
    bi, ti, ci = np.nonzero(keep)
    flat_tile = tile_y[bi, ti] * tx + tile_x[bi, ti]
    idx = np.zeros((ty * tx, k), np.int64)
    valid = np.zeros((ty * tx, k), bool)
    idx[flat_tile, rank[bi, ti, ci]] = bidx[bi, ci]
    valid[flat_tile, rank[bi, ti, ci]] = True
    return idx, valid


def view_lists(p: dict, cam: dict, cfg: dict, dtype=jnp.float32):
    """Global Gaussian indices (tiles, k) and validity for one view."""
    s = _project_jit(p, cam, dtype)
    depth = np.asarray(s["depth"], np.float32)
    order = np.argsort(depth, kind="stable")
    mx, my, rad = (np.asarray(s[k], np.float32)[order] for k in ("mx", "my", "radius"))
    res = cfg["img_res"]
    idx, valid = tile_lists(mx, my, rad, img_h=res, img_w=res, tile=cfg["tile"],
                            k=cfg["k_per_tile"], binning=cfg["binning"],
                            block=cfg["bin_block"], k_block_mult=cfg["bin_k_block_mult"])
    return order[idx].astype(np.int32), valid


@functools.partial(jax.jit, static_argnums=2)
def _project_jit(p, cam, dtype):
    with jax.default_matmul_precision("highest"):
        return project(p, cam, dtype)


# ------------------------------------------------------------ rendering


def composite(s: dict, gidx, valid, *, img_res: int, tile: int, dtype=jnp.float32):
    """Front-to-back compositing of each tile's splats; (H, W, 3) image."""
    nt = img_res // tile
    n_tiles = nt * nt
    chunk = min(TILES_PER_BLOCK, n_tiles)
    n_chunks = -(-n_tiles // chunk)
    pad = n_chunks * chunk - n_tiles
    gidx = jnp.pad(gidx, ((0, pad), (0, 0))).reshape(n_chunks, chunk, -1)
    valid = jnp.pad(valid, ((0, pad), (0, 0))).reshape(n_chunks, chunk, -1)
    tid = jnp.arange(n_chunks * chunk).reshape(n_chunks, chunk)
    pix = jnp.arange(tile * tile)

    @jax.checkpoint
    def one_chunk(s, g, v, t):
        px = ((t % nt)[:, None] * tile + pix[None, :] % tile).astype(dtype) + 0.5
        py = ((t // nt)[:, None] * tile + pix[None, :] // tile).astype(dtype) + 0.5
        at = lambda k: s[k][g][:, :, None]  # noqa: E731  (chunk, K, 1)
        dx = px[:, None, :] - at("mx")
        dy = py[:, None, :] - at("my")
        power = -0.5 * (at("ca") * dx * dx + at("cc") * dy * dy) - at("cb") * dx * dy
        alpha = jnp.minimum(at("opacity") * jnp.exp(jnp.minimum(power, 0)), ALPHA_MAX)
        live = v[:, :, None] & (power <= 0) & (alpha >= ALPHA_MIN)
        alpha = jnp.where(live, alpha, 0)
        t_incl = jnp.cumprod(1 - alpha, axis=1)
        t_excl = jnp.concatenate([jnp.ones_like(t_incl[:, :1]), t_incl[:, :-1]], axis=1)
        alive = t_incl >= T_EPS
        w = jnp.where(alive, alpha * t_excl, 0)                            # (chunk, K, P)
        rgb = s["rgb"][g]                                                   # (chunk, K, 3)
        return jnp.sum(w[..., None] * rgb[:, :, None, :], axis=1)          # (chunk, P, 3)

    out = jax.lax.map(lambda a: one_chunk(s, *a), (gidx, valid, tid))
    out = out.reshape(n_chunks * chunk, tile, tile, 3)[:n_tiles]
    # background is black: the transmittance left over adds nothing
    return out.reshape(nt, nt, tile, tile, 3).transpose(0, 2, 1, 3, 4).reshape(img_res, img_res, 3)


def render(p: dict, cam: dict, gidx, valid, *, img_res: int, tile: int, dtype=jnp.float32):
    return composite(project(p, cam, dtype), gidx, valid, img_res=img_res, tile=tile, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("img_res", "tile", "dtype"))
def render_jit(p, cam, gidx, valid, *, img_res, tile, dtype=jnp.float32):
    with jax.default_matmul_precision("highest"):
        return render(p, cam, gidx, valid, img_res=img_res, tile=tile, dtype=dtype).astype(jnp.float32)


# ------------------------------------------------------------------ loss


def _gauss_taps(size: int = 11, sigma: float = 1.5) -> np.ndarray:
    x = np.arange(size, dtype=np.float64) - (size - 1) / 2
    g = np.exp(-x * x / (2 * sigma * sigma))
    return (g / g.sum()).astype(np.float32)


def _filter(x, taps):
    """Separable Gaussian filter of an (H, W, C) image, zero padding."""
    r = len(taps) // 2
    h, w = x.shape[0], x.shape[1]
    xp = jnp.pad(x, ((r, r), (0, 0), (0, 0)))
    x = sum(taps[j] * xp[j:j + h] for j in range(len(taps)))
    xp = jnp.pad(x, ((0, 0), (r, r), (0, 0)))
    return sum(taps[j] * xp[:, j:j + w] for j in range(len(taps)))


def view_loss(img, gt, lam: float):
    """(1 - lam) * mean |img - gt| + lam * (1 - mean SSIM) / 2 of one view."""
    taps = jnp.asarray(_gauss_taps(), img.dtype)
    gt = gt.astype(img.dtype)
    c1, c2 = 0.01 ** 2, 0.03 ** 2
    mu0, mu1 = _filter(img, taps), _filter(gt, taps)
    s00 = _filter(img * img, taps) - mu0 * mu0
    s11 = _filter(gt * gt, taps) - mu1 * mu1
    s01 = _filter(img * gt, taps) - mu0 * mu1
    ssim = ((2 * mu0 * mu1 + c1) * (2 * s01 + c2)) / ((mu0 * mu0 + mu1 * mu1 + c1) * (s00 + s11 + c2))
    l1 = jnp.mean(jnp.abs(img - gt))
    return (1 - lam) * l1 + lam * (1 - jnp.mean(ssim)) / 2


@functools.partial(jax.jit, static_argnames=("img_res", "tile", "lam", "dtype"))
def view_loss_and_grad(p, cam, gt, gidx, valid, *, img_res, tile, lam, dtype=jnp.float32):
    def loss(p):
        img = render(p, cam, gidx, valid, img_res=img_res, tile=tile, dtype=dtype)
        return view_loss(img.astype(jnp.float32), gt, lam)

    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(loss)(p)
    return value, jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)


# -------------------------------------------------------------- training


def learning_rates(cfg: dict, step: int, batch: int) -> dict:
    """Per-leaf rates at ``step`` (0-based); sqrt(batch) scaling of all rates."""
    lr = cfg["lr"]
    t = min(max(step / cfg["max_steps"], 0.0), 1.0)
    means = math.exp(math.log(lr["means_init"]) * (1 - t) + math.log(lr["means_final"]) * t)
    s = math.sqrt(batch) if cfg["sqrt_batch_lr_scaling"] else 1.0
    return {"means": means * s, "log_scales": lr["scales"] * s, "quats": lr["quats"] * s,
            "opacity_logit": lr["opacity"] * s, "sh": lr["sh"] * s}


def adam(p: dict, g: dict, m: dict, v: dict, count: int, lrs: dict, cfg: dict):
    b1, b2, eps = cfg["adam"]["b1"], cfg["adam"]["b2"], cfg["adam"]["eps"]
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
    out_p, out_m, out_v = {}, {}, {}
    for k in LEAVES:
        out_m[k] = b1 * m[k] + (1 - b1) * g[k]
        out_v[k] = b2 * v[k] + (1 - b2) * g[k] * g[k]
        out_p[k] = p[k] - lrs[k] * (out_m[k] / bc1) / (jnp.sqrt(out_v[k] / bc2) + eps)
    return out_p, out_m, out_v


def black_background(cfg: dict) -> None:
    if any(cfg["bg"]):
        raise ValueError("the reference composites onto a black background only")


def train(params: dict, cams: list, gts: list, cfg: dict, *, dtype=jnp.float32) -> dict:
    """Train ``len(cams)`` steps, step i on the views ``cams[i]`` with GT
    ``gts[i]`` (each a list of one batch). Returns the loss of each step,
    the first step's gradient and the parameters after the last."""
    black_background(cfg)
    p = {k: jnp.asarray(v) for k, v in params.items()}
    m = {k: jnp.zeros_like(v) for k, v in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    losses, first_grad = [], None
    for step, (batch_cams, batch_gt) in enumerate(zip(cams, gts)):
        total, grads = 0.0, {k: jnp.zeros_like(x) for k, x in p.items()}
        for cam, gt in zip(batch_cams, batch_gt):
            gidx, valid = view_lists(p, cam, cfg, dtype)
            value, g = view_loss_and_grad(
                p, cam, jnp.asarray(gt), jnp.asarray(gidx), jnp.asarray(valid),
                img_res=cfg["img_res"], tile=cfg["tile"], lam=cfg["lambda_dssim"], dtype=dtype)
            total += float(value)
            grads = {k: grads[k] + g[k] for k in grads}
        n = len(batch_cams)
        grads = {k: x / n for k, x in grads.items()}
        losses.append(total / n)
        if first_grad is None:
            first_grad = {k: np.asarray(x) for k, x in grads.items()}
        p, m, v = adam(p, grads, m, v, step + 1, learning_rates(cfg, step, n), cfg)
    return {"losses": losses, "first_grad": first_grad,
            "params": {k: np.asarray(x) for k, x in p.items()}}


def render_view(params: dict, cam: dict, cfg: dict, *, dtype=jnp.float32) -> np.ndarray:
    """One served frame: (H, W, 3) float32."""
    black_background(cfg)
    p = {k: jnp.asarray(v) for k, v in params.items()}
    gidx, valid = view_lists(p, cam, cfg, dtype)
    img = render_jit(p, cam, jnp.asarray(gidx), jnp.asarray(valid),
                     img_res=cfg["img_res"], tile=cfg["tile"], dtype=dtype)
    return np.asarray(img)
