"""The program's host spans as profiler regions (``TraceRecorder.span``),
and the ``compile`` spans of JAX's backend compiles."""
from __future__ import annotations

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import projection as P
from repro.core.config import GSConfig
from repro.core.sharding import make_mesh
from repro.launch.train import GSTrainer
from repro.obs import NO_SPAN, Obs, TraceRecorder
from repro.serve_gs import RenderServer

from conftest import make_cam, make_scene

H = W = 32


class Feed:
    """Views for ``GSTrainer.fit``: fixed cameras around the origin."""

    def __init__(self, n_views: int = 4):
        self.cams = [make_cam(H, W, dist=2.4 + 0.1 * i) for i in range(n_views)]
        self.gt = np.random.default_rng(0).uniform(0, 1, (n_views, H, W, 3)).astype(np.float32)

    def batches(self, batch_size: int, *, steps: int):
        for s in range(steps):
            ids = [(s * batch_size + j) % len(self.cams) for j in range(batch_size)]
            cams = P.Camera(*[jnp.stack(x) for x in zip(*(self.cams[i] for i in ids))])
            yield cams, jnp.asarray(self.gt[ids])


def trainer(obs: Obs) -> GSTrainer:
    g = make_scene(n=256, scale=0.06)
    cfg = GSConfig(img_h=H, img_w=W, k_per_tile=32, batch_size=2, pad_quantum=128)
    return GSTrainer(cfg, make_mesh((1, 1)), np.asarray(g.means), np.random.default_rng(1)
                     .uniform(0.1, 0.9, (256, 3)).astype(np.float32), verbose=False, obs=obs)


def host_regions(trace_dir, names) -> list[tuple[str, float]]:
    """(name, seconds) of the host events named ``names``, in start order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    got = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                got += [(ev.start_ns, ev.name, ev.duration_ns * 1e-9)
                        for ev in line.events if ev.name in names]
    return [(n, d) for _, n, d in sorted(got)]


def test_batch_and_cache_regions_are_in_the_profiler_trace_as_in_the_ring(tmp_path):
    obs = Obs(trace=True)
    tr, feed = trainer(obs), Feed()
    tr.fit(feed, steps=1, densify=False)  # compile outside the session
    srv = RenderServer(make_scene(n=128, scale=0.06), GSConfig(img_h=H, img_w=W, k_per_tile=32),
                       n_levels=1, max_batch=2, store_frames=False, obs=obs)
    with srv:
        srv.submit(make_cam(H, W, dist=2.3)).result()
        obs.trace.drain()
        jax.profiler.start_trace(str(tmp_path))
        tr.fit(feed, steps=3, densify=False)
        for d in (2.5, 2.7):
            srv.submit(make_cam(H, W, dist=d)).result()
        jax.profiler.stop_trace()
    ring = [(s.name, s.dur) for s in sorted(obs.trace.drain(), key=lambda s: s.t0)
            if s.name in ("batch", "cache")]
    regions = host_regions(tmp_path, ("batch", "cache"))
    assert [n for n, _ in ring] == ["batch"] * 3 + ["cache"] * 4  # probe, put; twice
    assert [n for n, _ in regions] == [n for n, _ in ring]
    for (_, want), (_, got) in zip(ring, regions):
        assert abs(got - want) <= max(0.05 * want, 0.2e-3), (got, want)


def test_forced_rejit_records_one_compile_span_and_a_warm_step_none():
    obs = Obs(trace=True)
    tr, feed = trainer(obs), Feed()
    tr.fit(feed, steps=1, densify=False)
    obs.trace.drain()
    tr.fit(feed, steps=1, densify=False)
    assert [s for s in obs.trace.drain() if s.name == "compile"] == []
    tr._step_fn = None  # the next step builds and compiles the program anew
    tr.fit(feed, steps=1, densify=False)
    (comp,) = [s for s in obs.trace.drain() if s.name == "compile"]
    assert comp.meta["fun_name"] == "jit(local_step)" and comp.t1 > comp.t0


def test_compile_spans_go_only_to_recorders_that_are_on():
    x = jnp.arange(7.0)
    on, off = Obs(trace=True), Obs(trace=True)
    was_on = off.trace
    off.disable_trace()
    jax.jit(lambda v: v * 3.25 + 1.5)(x).block_until_ready()
    assert [(s.name, s.meta["fun_name"]) for s in on.trace.drain()] == [
        ("compile", "jit(<lambda>)")]
    assert off.trace.drain() == [] and was_on.drain() == []


def test_span_region_records_like_record():
    rec = TraceRecorder(16)
    with rec.span(7, "retire", level=1) as sp:
        sp.meta["frames"] = 2
    with rec.span(8, "submit", t0=1.0) as sp:
        sp.t1 = 2.0
    with rec.span(9, "batch") as sp:
        sp.drop()
    with pytest.raises(RuntimeError), rec.span(10, "cache", op="put"):
        raise RuntimeError("a body that raises records nothing")
    with NO_SPAN as nothing:
        nothing.drop()
    got = rec.drain()
    assert [(s.rid, s.name) for s in got] == [(7, "retire"), (8, "submit")]
    assert got[0].meta == {"level": 1, "frames": 2} and got[0].t1 >= got[0].t0
    assert (got[1].t0, got[1].t1) == (1.0, 2.0)
