"""The benchmark's traffic generators and its end-to-end arithmetic."""
from __future__ import annotations

import time

import numpy as np
import pytest
from tiny import ROOT  # puts bench/ on the path

from harness import load_module

OPEN = load_module(ROOT / "bench" / "traffic" / "open_loop_frames.py")
TRAIN = load_module(ROOT / "bench" / "traffic" / "train_views.py")
SERVE_MIX = {"azimuth_deg": [0.0, 360.0], "elevation_deg": [-30.0, 30.0], "radius": [2.8, 3.6],
             "fov_deg": 40.0}
BIG_SEED = 2**31 + 977


def test_arrivals_are_one_fixed_schedule_filling_the_window():
    a = OPEN.arrivals(4.0, 30.0)
    assert len(a) == 120 and np.array_equal(a, OPEN.arrivals(4.0, 30.0))
    assert a[0] == 0.0 and np.all(np.diff(a) > 0) and a[-1] < 30.0
    gaps = np.diff(np.append(a, 30.0))
    assert gaps.sum() == pytest.approx(30.0)
    assert 0.5 < gaps.std() / gaps.mean() < 1.5  # exponential gaps: bursts and lulls


def test_poses_are_seeded_and_inside_their_ranges():
    p = OPEN.poses(SERVE_MIX, 64, BIG_SEED, 128)
    q = OPEN.poses(SERVE_MIX, 64, BIG_SEED, 128)
    r = OPEN.poses(SERVE_MIX, 64, BIG_SEED + 1, 128)
    assert all(np.array_equal(x["viewmat"], y["viewmat"]) for x, y in zip(p, q))
    assert not np.array_equal(p[0]["viewmat"], r[0]["viewmat"])
    for cam in p:
        rot, t = cam["viewmat"][:3, :3].astype(np.float64), cam["viewmat"][:3, 3]
        eye = -rot.T @ t
        dist = np.linalg.norm(eye)
        assert 2.8 - 1e-4 <= dist <= 3.6 + 1e-4
        assert abs(np.degrees(np.arcsin(eye[2] / dist))) <= 30.0 + 1e-3
        assert np.allclose(rot[2], -eye / dist, atol=1e-5)  # looks at the origin


def test_view_order_is_seeded_and_an_epoch_sees_every_view():
    from cameras import orbit

    cfg = {"n_views": 8, "orbit_radius": 3.0,
           "orbit": {"fov_deg": 40.0, "elev_cycles": 3.0, "elev_max_deg": 55.0}}
    cams = orbit(cfg, 32)
    gt = np.zeros((8, 32, 32, 3), np.float32)
    vm = np.stack([c["viewmat"] for c in cams])

    def order(seed):
        feed = TRAIN.make_feed(cams, gt, seed)
        return [TRAIN.view_ids(c, vm) for c, _ in feed.batches(4, steps=4)]

    a, b, c = order(BIG_SEED), order(BIG_SEED), order(BIG_SEED + 5)
    assert a == b and a != c
    assert sorted(a[0] + a[1]) == list(range(8)) and sorted(a[2] + a[3]) == list(range(8))


def test_feed_stops_at_its_deadline_and_keeps_its_stream():
    from cameras import orbit

    cfg = {"n_views": 8, "orbit_radius": 3.0,
           "orbit": {"fov_deg": 40.0, "elev_cycles": 3.0, "elev_max_deg": 55.0}}
    feed = TRAIN.make_feed(orbit(cfg, 32), np.zeros((8, 32, 32, 3), np.float32), 3)
    assert len(list(feed.batches(4, steps=1))) == 1
    feed.deadline = time.perf_counter() - 1.0
    assert list(feed.batches(4, steps=5)) == []


class _Future:
    def __init__(self):
        self.frame = None

    def done(self):
        return self.frame is not None

    def result(self):
        return self.frame


class _StallingServer:
    """Renders one request per step in ``render_s``; the first render stalls."""

    def __init__(self, render_s, stall_s):
        self.queue, self.render_s, self.stall_s = [], render_s, stall_s

    def submit(self, cam, **kw):
        f = _Future()
        self.queue.append(f)
        return f

    def step(self):
        if not self.queue:
            return 0
        time.sleep(self.render_s + self.stall_s)
        self.stall_s = 0.0
        self.queue.pop(0).frame = np.zeros(1)
        return 1


def test_latency_counts_from_due_time_so_a_stall_delays_later_requests():
    due = np.arange(6) * 0.02
    t0 = time.perf_counter()
    done, frames, lag = OPEN.drive(_StallingServer(0.001, 0.15), OPEN.poses(SERVE_MIX, 6, 1, 32), due,
                                   late_s=5.0, keep={5}, t0=t0)
    latency = done - (t0 + due)
    assert np.all(np.isfinite(done)) and set(frames) == {5}
    # every request due during the stall waits for it, timed from its due time
    assert np.all(latency[:6] >= 0.15 - due[:6] - 0.005)
    assert OPEN.percentile_ms(latency) >= np.sort(latency)[-1] * 1e3 - 1e-9


def test_percentile_is_a_sample_over_all_requests():
    lat = np.concatenate([np.full(90, 0.1), np.full(9, 0.2), [np.inf]])
    assert OPEN.percentile_ms(lat) == pytest.approx(200.0)
    assert OPEN.percentile_ms(lat, 90) == pytest.approx(200.0)
    assert OPEN.percentile_ms(lat, 50) == pytest.approx(100.0)
    lat[-6:] = np.inf
    assert OPEN.percentile_ms(lat) == np.inf


def test_a_request_that_never_lands_fails():
    class Never(_StallingServer):
        def step(self):
            return 0

    due = np.array([0.0, 0.01])
    done, frames, _ = OPEN.drive(Never(0.0, 0.0), OPEN.poses(SERVE_MIX, 2, 1, 32), due, late_s=0.05, keep={0},
                                 t0=time.perf_counter())
    assert np.all(np.isnan(done)) and frames == {}
