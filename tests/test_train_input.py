"""The train loop's input: ``GSTrainer.fit`` draws batch i + 1 while step i
runs, never a batch it does not train, and puts the ground truth straight
into the train step's own sharding."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import GSConfig
from repro.core.sharding import make_mesh
from repro.core.train import gt_sharding
from repro.data.views import ViewDataset, draw_batch
from repro.launch.train import GSTrainer
from repro.obs import Obs
from repro.volume.cameras import orbit_cameras

from conftest import make_scene

H = W = 32
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class RecordingFeed(ViewDataset):
    """Random views drawn by the program's epoch rule from one stream across
    ``fit`` calls (as the benchmark's feed draws them), recording the view
    ids of every batch drawn. ``batches`` yields for as long as it is asked,
    whatever ``steps`` says, so ``fit`` alone decides how many are drawn;
    ``limit`` ends the stream after that many."""

    def __init__(self, n_views: int = 5, seed: int = 0, limit: int | None = None, res: int = H):
        self.img_h = self.img_w = res
        self.n_views = n_views
        self.cams = orbit_cameras(n_views, img_h=res, img_w=res, radius=2.6)
        self.gt = np.random.default_rng(seed).uniform(0, 1, (n_views, res, res, 3)).astype(np.float32)
        self.rng = np.random.default_rng(seed + 1)
        self.limit = limit
        self.drawn: list[list[int]] = []
        self._stream = None

    def ids(self, gt) -> list[int]:
        """The view ids of a batch, by its images."""
        return [int(np.flatnonzero((self.gt == g).all(axis=(1, 2, 3)))[0]) for g in np.asarray(gt)]

    def batches(self, batch_size: int, *, steps: int):
        if self._stream is None:
            self._stream = ViewDataset.batches(self, batch_size, steps=1 << 62)
        while self.limit is None or len(self.drawn) < self.limit:
            cams, gt = next(self._stream)
            assert isinstance(gt, np.ndarray)  # the loop, not the data, places it
            self.drawn.append(self.ids(gt))
            yield cams, gt


def trainer(obs: Obs | None = None) -> GSTrainer:
    g = make_scene(n=256, scale=0.06)
    cfg = GSConfig(img_h=H, img_w=W, k_per_tile=32, batch_size=2, pad_quantum=128)
    return GSTrainer(cfg, make_mesh((1, 1)), np.asarray(g.means), np.random.default_rng(1)
                     .uniform(0.1, 0.9, (256, 3)).astype(np.float32), verbose=False, obs=obs)


def spy(tr: GSTrainer, feed: RecordingFeed) -> tuple[list, object]:
    """Record the view ids of every batch the train step is handed."""
    inner = tr.step_fn
    trained = []

    def step(state, cams, gt):
        trained.append(feed.ids(gt))
        return inner(state, cams, gt)

    tr._step_fn = step
    return trained, inner


def test_fit_draws_exactly_the_batches_it_trains():
    tr, feed = trainer(), RecordingFeed()
    trained, inner = spy(tr, feed)
    calls = (1, 3, 1, 3)
    losses = []
    for n in calls:
        got = tr.fit(feed, steps=n, densify=False)
        assert len(got) == n
        assert feed.drawn == trained  # no batch drawn ahead of a step that never ran
        losses += got
    assert len(trained) == sum(calls)
    assert inner._cache_size() == 1  # every call hands the step gt placed alike

    # the same views, one fit(steps=1) call each, train to the same losses bit for bit
    tr1, feed1 = trainer(), RecordingFeed()
    one_by_one = [tr1.fit(feed1, steps=1, densify=False)[0] for _ in range(sum(calls))]
    assert feed1.drawn == feed.drawn
    assert one_by_one == losses


def test_stream_ending_early_trains_every_batch_drawn():
    obs = Obs(trace=True)
    tr, feed = trainer(obs), RecordingFeed(limit=3)
    trained, _ = spy(tr, feed)
    losses = tr.fit(feed, steps=6, densify=False)
    assert len(losses) == 3 and trained == feed.drawn and len(feed.drawn) == 3
    snap = obs.metrics.snapshot()
    assert snap["train.steps"] == 3 and snap["train.batches_ahead"] == 2
    spans = obs.trace.drain()
    assert [s.meta["step"] for s in spans if s.name == "batch"] == [0, 1, 2]
    assert tr.fit(feed, steps=2, densify=False) == []  # nothing left: no step, no span
    assert [s for s in obs.trace.drain() if s.name == "batch"] == []


def test_drawing_a_batch_does_not_wait_for_the_step_in_flight():
    """The draw overlaps the running step only if nothing in it waits for
    the device: indexing the cameras with a device array would."""
    feed = RecordingFeed()
    batches = iter(feed.batches(2, steps=3))
    place = gt_sharding(make_mesh((1, 1)), GSConfig(img_h=H, img_w=W))
    x = jnp.full((1024, 1024), 1e-3, jnp.float32)
    busy = jax.jit(lambda x: jax.lax.fori_loop(0, 200, lambda i, c: jnp.tanh(c @ x), x))
    jax.block_until_ready((busy(x), draw_batch(batches, place, None, 0, 0)))  # compile both
    out = busy(x)
    cams, gt = draw_batch(batches, place, None, 0, 1)
    assert not out.is_ready()
    jax.block_until_ready((out, cams, gt))


# ============================================ four CPU devices, (1, 4) mesh
MESH_SCRIPT = textwrap.dedent(
    """
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, "tests")
    import jax, numpy as np
    from repro.core.config import GSConfig
    from repro.core.sharding import make_mesh
    from repro.core.train import gt_sharding
    from repro.launch.train import GSTrainer
    from repro.obs import Obs
    from conftest import make_scene
    from test_train_input import RecordingFeed

    H = W = 64  # four strips of one 16-px tile row
    mesh = make_mesh((1, 4))
    cfg = GSConfig(img_h=H, img_w=W, tile_h=16, tile_w=16, k_per_tile=32, batch_size=2,
                   pad_quantum=128)
    g = make_scene(n=512, scale=0.06)
    obs = Obs(trace=True)
    tr = GSTrainer(cfg, mesh, np.asarray(g.means),
                   np.random.default_rng(1).uniform(0.1, 0.9, (512, 3)).astype(np.float32),
                   verbose=False, obs=obs)
    feed = RecordingFeed(res=H)
    inner = tr.step_fn
    handed = []

    def step(state, cams, gt):
        handed.append(gt)
        return inner(state, cams, gt)

    tr._step_fn = step
    out = {"calls": []}
    for n in (1, 3, 2):
        before = obs.metrics.snapshot().get("train.batches_ahead", 0)
        losses = tr.fit(feed, steps=n, densify=False)
        spans = [s for s in obs.trace.drain() if s.name == "batch"]
        out["calls"].append({"steps": n, "losses": len(losses), "batch_spans": len(spans),
                             "ahead": obs.metrics.snapshot()["train.batches_ahead"] - before})
    want = gt_sharding(mesh, cfg)
    out["spec"] = str(want.spec)
    out["sharded"] = all(isinstance(gt, jax.Array) and gt.sharding == want for gt in handed)
    strips = []
    for gt, ids in zip(handed, feed.drawn):
        host = feed.gt[ids]
        for sh in gt.addressable_shards:
            rows = sh.index[1]
            strips.append([sh.device.id, rows.start, rows.stop,
                           bool(np.array_equal(np.asarray(sh.data), host[:, rows]))])
    out["strips"] = strips
    out["mesh_devices"] = [d.id for d in mesh.devices.flat]
    out["compiles"] = inner._cache_size()
    print(json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", MESH_SCRIPT], capture_output=True, text=True,
                       timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_gt_reaches_the_step_in_its_strip_sharding(four_devices):
    """Each device holds just its 16-row strip of every view, placed before
    the step: the compiled step is handed gt as it reads it."""
    out = four_devices
    assert out["spec"] == "PartitionSpec('data', 'model')" and out["sharded"]
    assert len(out["strips"]) == 6 * 4  # six steps, four devices
    for dev, start, stop, same in out["strips"]:
        j = start // 16
        assert (start, stop, same) == (16 * j, 16 * (j + 1), True)
        assert dev == out["mesh_devices"][j]  # the strip the step reads on that device
    assert sorted(s[1] for s in out["strips"]) == sorted([0, 16, 32, 48] * 6)
    assert out["compiles"] == 1


@pytest.mark.parametrize("call", [0, 1, 2])
def test_batches_ahead_and_batch_spans_per_fit_call(four_devices, call):
    c = four_devices["calls"][call]
    assert c["losses"] == c["steps"] and c["batch_spans"] == c["steps"]
    assert c["ahead"] == c["steps"] - 1  # train.batches_ahead
