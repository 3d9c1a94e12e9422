"""Operations and bytes the configurations' work requires, from shapes alone.

Counts are of the mathematics, the same whatever implements it: a compare,
a select or a clamp is not an operation; exp, sqrt and a division count one
each; nothing recomputed counts. They are approximate per item, exact in how
they scale with the shapes, and written out term by term below.
"""
from __future__ import annotations

# --- projection of one Gaussian into one view (EWA), forward ------------
#   camera transform 3x3 @ 3 + 3: 18; perspective divide and intrinsics: 6;
#   quaternion normalise (8) + rotation matrix (30); R S (9) and
#   (R S)(R S)^T (45); Jacobian (8); J W (30); (J W) cov (30); (.)(J W)^T
#   (20); blur, determinant and conic (10); eigenvalue and radius (8);
#   sigmoid opacity (4); degree-0 colour (6)
PROJECT_FWD = 232
BACKWARD_FACTOR = 2  # a backward pass costs about twice its forward
# --- compositing one splat slot at one pixel, forward ---------------------
#   offsets (2); quadratic form (9); exp (1); opacity product (1);
#   1 - alpha (1); transmittance product (1); weight (1); colour (6)
RASTER_FWD = 22
# --- loss per pixel and channel, forward ---------------------------------
#   |x - y| and its sum (3); five statistics (x, y, x^2, y^2, xy: 3 to
#   form) each through an 11-tap separable Gaussian filter (5 x 2 x 22);
#   variances and covariance (6); SSIM map (12)
LOSS_FWD = 3 + 3 + 5 * 2 * 22 + 6 + 12
# --- Adam per parameter: moments (6), bias corrections (2), sqrt, divide,
#     scale and subtract (4)
ADAM = 12
PARAMS_PER_GAUSSIAN = 3 + 3 + 4 + 1 + 3  # means, scales, quaternion, opacity, colour
SPLAT_FLOATS = 11                         # a projected splat as the rasterizer reads it


def tiles(config: dict) -> int:
    return (config["img_res"] // config["tile"]) ** 2


def pixels(config: dict) -> int:
    return config["img_res"] ** 2


def raster_flops(config: dict, views: int, *, backward: bool) -> float:
    """Rasterizer operations for ``views`` views (forward, plus backward)."""
    per_view = tiles(config) * config["k_per_tile"] * config["tile"] ** 2 * RASTER_FWD
    return views * per_view * (1 + (BACKWARD_FACTOR if backward else 0))


def raster_bytes(config: dict, views: int, *, backward: bool) -> float:
    """HBM bytes the rasterizer must move for ``views`` views: per tile the
    (K, 11) splats and K validity flags in, (P, 3) colours and (P,)
    transmittance out; the backward reads those again with the outputs'
    cotangents and writes (K, 11) splat gradients."""
    k, p = config["k_per_tile"], config["tile"] ** 2
    fwd = 4 * (k * SPLAT_FLOATS + k + 3 * p + p)
    bwd = 4 * (k * SPLAT_FLOATS + k + 3 * p + p + k * SPLAT_FLOATS)
    return views * tiles(config) * (fwd + (bwd if backward else 0))


def train_step_flops(config: dict, batch: int, n_gaussians: int) -> float:
    """One training step over ``batch`` views: projection, rasterizer and
    loss, forward and backward, and Adam over every parameter."""
    fb = 1 + BACKWARD_FACTOR
    project = batch * n_gaussians * PROJECT_FWD * fb
    loss = batch * pixels(config) * 3 * LOSS_FWD * fb
    adam = n_gaussians * PARAMS_PER_GAUSSIAN * ADAM
    return project + raster_flops(config, batch, backward=True) + loss + adam
