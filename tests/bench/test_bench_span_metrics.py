"""The per-layer metrics read from the program's own spans: queue wait and
tile-cache host time in serving, compiles in both cells."""
from __future__ import annotations

import pytest
from tiny import ROOT

from harness import load_module


def reader(metric: str):
    return load_module(ROOT / "bench" / "metrics" / f"{metric}.py")


def serve_run(spans, *, frames=2, window_s=10.0) -> dict:
    return {"host_spans": spans, "frames": frames, "requests": frames, "window_s": window_s}


# the first request is due at the window's start (t = 100 s); a compile of
# the traced run's memory report comes after the window
SERVE = [
    ("submit", 100.0, 100.002), ("cache", 100.0005, 100.0015), ("queue", 100.0, 100.010),
    ("dispatch", 100.010, 100.011), ("cache", 100.030, 100.036),
    ("submit", 104.0, 104.002), ("cache", 104.0005, 104.0025), ("queue", 104.0, 104.030),
    ("cache", 104.050, 104.054),
    ("compile", 111.0, 112.0), ("queue", 111.0, 111.5),
]


def test_queue_ms_is_the_mean_wait_of_the_window_requests():
    assert reader("queue_ms.serve").read(serve_run(SERVE)) == pytest.approx(20.0)


def test_cache_ms_is_the_cache_host_time_per_served_frame():
    assert reader("cache_ms.serve").read(serve_run(SERVE)) == pytest.approx(
        (1.0 + 6.0 + 2.0 + 4.0) / 2)


@pytest.mark.parametrize("metric", ["queue_ms.serve", "cache_ms.serve"])
def test_serve_span_metrics_find_nothing_without_their_spans(metric):
    """The parent program records no queue or cache spans."""
    bare = [s for s in SERVE if s[0] not in ("queue", "cache")]
    assert reader(metric).read(serve_run(bare)) is None
    assert reader(metric).read(serve_run([])) is None


def test_compiles_serve_counts_only_the_window():
    r = reader("compiles.serve")
    assert r.read(serve_run(SERVE)) == 0
    late = SERVE + [("compile", 105.0, 105.2), ("compile", 109.9, 110.3)]
    assert r.read(serve_run(late)) == 2
    assert r.read(serve_run([])) == 0


def test_compiles_train_counts_the_window_spans():
    r = reader("compiles.train")
    spans = [("batch", 1.0, 1.07), ("dispatch", 1.07, 1.08), ("compile", 1.07, 1.5)]
    assert r.read({"host_spans": spans, "steps": 1}) == 1
    assert r.read({"host_spans": spans[:2], "steps": 1}) == 0
