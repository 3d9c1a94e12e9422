"""Operation and byte counts of the benchmark, against hand-worked shapes."""
from __future__ import annotations

import pytest
from tiny import ROOT  # noqa: F401  (puts bench/ on the path)

import counts as C
from peaks import peak_for

# 32 px in 16 px tiles: 4 tiles of 256 pixels; 8 splat slots per tile
CFG = {"img_res": 32, "tile": 16, "k_per_tile": 8}


def test_raster_flops_forward_and_backward():
    fwd = 4 * 8 * 256 * 22                   # tiles x slots x pixels x 22
    assert C.raster_flops(CFG, 1, backward=False) == fwd
    assert C.raster_flops(CFG, 3, backward=True) == 3 * fwd * 3


def test_raster_bytes():
    fwd = 4 * (8 * 11 + 8 + 3 * 256 + 256)    # splats and flags in, colour and T out
    bwd = fwd + 4 * 8 * 11                    # the same again, splat gradients out
    assert C.raster_bytes(CFG, 1, backward=False) == 4 * fwd
    assert C.raster_bytes(CFG, 2, backward=True) == 2 * 4 * (fwd + bwd)


def test_train_step_flops_sums_its_terms():
    batch, n = 4, 1000
    project = batch * n * C.PROJECT_FWD * 3
    loss = batch * 32 * 32 * 3 * C.LOSS_FWD * 3
    adam = n * 14 * C.ADAM
    raster = C.raster_flops(CFG, batch, backward=True)
    assert C.train_step_flops(CFG, batch, n) == project + raster + loss + adam


def test_loss_count_is_the_separable_filter():
    assert C.LOSS_FWD == 3 + 3 + 5 * 2 * 22 + 6 + 12


def test_peaks_are_published_ones_and_unknown_chips_fail():
    v5e = peak_for("TPU v5 lite")
    assert v5e["flops_bf16_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peak_for("cpu")
