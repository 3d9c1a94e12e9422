"""The benchmark's reduction of a profiler trace to busy time, idle gaps and
time per operation."""
from __future__ import annotations

import json
from pathlib import Path

import pytest
from tiny import ROOT  # noqa: F401  (puts bench/ on the path)

import trace_reduce as T

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "tpu_train_trace.json"


def op(chip, name, s, e):
    return T.Op(chip, name, float(s), float(e))


def test_busy_is_the_union_of_overlapping_ops():
    ops = [op(0, "a", 0, 100_000), op(0, "b", 50_000, 150_000), op(0, "c", 300_000, 400_000)]
    r = T.reduce(ops, [T.Span("bench.window", 0, 500_000)])
    assert r.busy_ns == [250_000]
    assert r.window_s == pytest.approx(500e-6)
    assert r.busy_s == pytest.approx(250e-6)


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    ops = [op(0, "a", 0, 100_000), op(0, "b", 300_000, 400_000)]
    host = [T.Span("bench.window", 0, 600_000), T.Span("fit", 0, 600_000),
            T.Span("batch", 150_000, 250_000)]
    r = T.reduce(ops, host)
    assert r.gaps == [("batch", 200_000), ("fit", 200_000)]
    assert r.breakdown()["idle_gaps"] == [["batch", 200e-6], ["fit", 200e-6]]


def test_short_idle_stretches_are_not_gaps():
    ops = [op(0, "a", 0, 100_000), op(0, "b", 100_000 + T.MIN_GAP_NS - 1, 200_000)]
    r = T.reduce(ops, [T.Span("bench.window", 0, 200_000)])
    assert r.gaps == []


def test_ops_are_clipped_to_the_window_and_averaged_over_chips():
    ops = [op(0, "sort.1", -50_000, 50_000), op(1, "sort.1", 0, 100_000),
           op(1, "fusion.2", 100_000, 130_000)]
    r = T.reduce(ops, [T.Span("bench.window", 0, 200_000)])
    assert r.chips == 2
    assert r.busy_ns == [50_000, 130_000]
    assert r.op_seconds(lambda o: o.name.startswith("sort")) == pytest.approx(75e-6)
    dev = dict(r.breakdown()["device_ops"])
    assert dev["sort.1"] == pytest.approx(75e-6) and dev["fusion.2"] == pytest.approx(15e-6)


def test_program_spans_are_placed_on_the_trace_clock(tmp_path, monkeypatch):
    ops = [op(0, "a", 1_000_000, 1_100_000), op(0, "b", 1_300_000, 1_400_000)]
    host = [T.Span("bench.window", 1_000_000, 1_500_000)]
    monkeypatch.setattr(T, "find_xplane", lambda d: "trace")
    monkeypatch.setattr(T, "read_xplane", lambda p: (list(ops), list(host)))
    # the window began at perf_counter 10.0 s; the program's span at 10.00015 s
    r = T.reduce_dir(tmp_path, host_spans=[("batch", 10.00011, 10.00029)], host_offset=10.0)
    assert [g[0] for g in r.gaps] == ["batch", "(no host span)"]


def test_recorded_tpu_trace_reduces_consistently():
    """A slice of a real train-step trace on a TPU v5e (its first ops)."""
    import ops as O

    rec = json.loads(FIXTURE.read_text())
    ops = [T.Op(**o) for o in rec["ops"]]
    host = [T.Span(**s) for s in rec["host"]]
    r = T.reduce(ops, host)
    assert 0 < r.busy_s <= r.window_s
    assert r.busy_s == pytest.approx(rec["busy_s"], rel=1e-9)
    per_op = sum(v for _, v in r.breakdown(n=10**6)["device_ops"])
    assert per_op >= r.busy_s  # ops may overlap on the chip, never leave busy time uncounted
    idle = sum(ns for _, ns in r.gaps) * 1e-9
    assert idle <= r.window_s - r.busy_s + 1e-12
    assert sum(O.is_sort(o) for o in ops) == 11  # depth sorts and binning merges
    assert not any(O.is_raster(o) for o in ops)  # ConcatBitcast custom calls are not kernels


def test_a_trace_recorded_here_is_read(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x * 2.0))
    x = jnp.arange(4096.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ops, host = T.read_xplane(T.find_xplane(tmp_path))
    assert any(s.name == "bench.window" for s in host)
    r = T.reduce(ops, host)
    assert r.window_s > 0
