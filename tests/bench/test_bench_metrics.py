"""Each per-layer reader against a hand-made trace and hand-worked numbers."""
from __future__ import annotations

import pytest
from tiny import ROOT, spec

import counts
import trace_reduce as T
from harness import load_module

CFG = {"img_res": 32, "tile": 16, "k_per_tile": 8}
PEAK = {"flops_bf16_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


def traced(ops, window_ns=1_000_000_000):
    r = T.reduce(ops, [T.Span("bench.window", 0, window_ns)])
    return {"trace": r, "config": CFG, "peak": PEAK, "counts": counts, "chips": 1,
            "window_s": r.window_s}


def test_every_per_layer_metric_has_a_reader():
    for m in spec()["per_layer"]:
        assert callable(reader(m["name"]))


def test_idle_share_is_one_minus_busy_over_window():
    ops = [T.Op(0, "fusion.1", 0, 250_000_000), T.Op(0, "fusion.2", 500_000_000, 750_000_000)]
    for name in ("device_idle_share.train", "device_idle_share.serve"):
        assert reader(name)(traced(ops)) == pytest.approx(50.0)
    assert reader("device_idle_share.train")(traced([])) is None


def test_mfu_counts_the_whole_step_over_the_window():
    run = {**traced([]), "steps": 10, "batch": 4, "n_gaussians": 1000, "window_s": 2.0}
    want = counts.train_step_flops(CFG, 4, 1000) * 10 / 2.0 / 197e12 * 100
    assert reader("train_step_mfu")(run) == pytest.approx(want)


def test_input_ms_is_the_mean_batch_span():
    run = {"host_spans": [("batch", 1.0, 1.004), ("dispatch", 1.004, 1.5), ("batch", 2.0, 2.002)]}
    assert reader("input_ms.train")(run) == pytest.approx(3.0)
    assert reader("input_ms.train")({"host_spans": []}) is None


def test_serving_counters():
    c = {"batch_size_mean": 2.5, "render_calls": 4, "render_rows": 20, "completed": 10, "tiles_y": 2}
    assert reader("batch_occupancy.serve")({"counters": c}) == 2.5
    assert reader("renders_per_frame.serve")({"counters": c}) == 1.0
    assert reader("batch_occupancy.serve")({"counters": {**c, "render_calls": 0}}) is None


# operation names as a TPU v5e trace of the train step gives them (cut short)
RASTER_FWD = ('%jvp_vmap___.1 = (f32[4,4096,3,256]{3,2,1,0:T(4,128)S(1)}, f32[4,4096,1,256]) '
              'custom-call(f32[4,4096,11,256]{3,2,1,0:T(8,128)} %copy_bitcast_fusion), '
              'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}')
RASTER_BWD = ('%transpose_jvp_vmap____.1 = f32[4,4096,11,256]{3,2,1,0:T(8,128)} custom-call('
              'f32[4,4096,11,256]{3,2,1,0:T(8,128)} %copy_bitcast_fusion), custom_call_target="tpu_custom_call"')
DEPTH_SORT = ('%sort.3 = (s32[4,574464]{1,0:T(4,128)}, s32[4,574464]{1,0:T(4,128)S(1)}) sort('
              's32[4,574464]{1,0:T(4,128)S(1)} %copy_bitcast_fusion.2, s32[4,574464] %iota.19), '
              'dimensions={1}, is_stable=true, to_apply=%region_10.17')
CONCAT = ('%custom-call.21 = f32[574464,4]{0,1:T(4,128)S(1)} custom-call(f32[143616,4] %slice-done.28), '
          'custom_call_target="ConcatBitcast"')
GATHER = ('%fusion.2 = s32[4194304]{0:T(1024)S(1)} fusion(s32[4,64,1024]{2,1,0:T(8,128)S(1)} '
          '%custom-call.12, s32[4194304]{0:T(1024)S(1)} %bitcast.319), kind=kCustom, calls=%fused_computation.2')


def test_ops_are_matched_by_opcode_and_kernel_target():
    import ops as O

    def as_op(name):
        return T.Op(0, name, 0.0, 1.0)

    assert [O.opcode(as_op(n)) for n in (RASTER_FWD, DEPTH_SORT, GATHER)] == ["custom-call", "sort", "fusion"]
    assert O.is_raster(as_op(RASTER_FWD)) and O.is_raster(as_op(RASTER_BWD))
    assert not O.is_raster(as_op(CONCAT)) and not O.is_raster(as_op(GATHER))
    assert O.is_sort(as_op(DEPTH_SORT)) and not O.is_sort(as_op(GATHER))


def test_raster_and_sort_times_per_step_and_frame():
    ms = 1_000_000
    ops = [T.Op(0, RASTER_FWD, 0, 10 * ms), T.Op(0, RASTER_BWD, 10 * ms, 30 * ms),
           T.Op(0, DEPTH_SORT, 30 * ms, 34 * ms), T.Op(0, GATHER, 34 * ms, 90 * ms)]
    run = {**traced(ops), "steps": 2, "frames": 4, "views": 8}
    assert reader("raster_ms.train")(run) == pytest.approx(15.0)
    assert reader("sort_ms.train")(run) == pytest.approx(2.0)
    assert reader("raster_ms.serve")(run) == pytest.approx(7.5)
    assert reader("sort_ms.serve")(run) == pytest.approx(1.0)
    flops = counts.raster_flops(CFG, 8, backward=True)
    nbytes = counts.raster_bytes(CFG, 8, backward=True)
    want = 100 * max(flops / 197e12, nbytes / 819e9) / 0.030
    assert reader("raster_roofline.train")(run) == pytest.approx(want)
    assert reader("raster_ms.train")({**traced([]), "steps": 2}) is None
