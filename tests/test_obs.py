"""Observability tests: typed metrics registry (atomic snapshot, ONE unified
reset), lock-free span recorder, exporters (JSONL contract + Chrome trace),
zero-cost-when-disabled guarantees, and end-to-end span trees over real TCP
for the interesting request fates (miss, cache hit, partial tile hit, dedup,
shed)."""
import asyncio
import json
import threading
import time
import tracemalloc

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import projection as P
from repro.core.config import GSConfig
from repro.core.sharding import make_mesh
from repro.frontend import (
    AsyncFrontendClient,
    FrontendClient,
    Gateway,
    GatewayThread,
    SessionManager,
    ShedError,
)
from repro.obs import (
    NULL_RECORDER,
    STAGES,
    TRAIN_STAGES,
    MetricsRegistry,
    Obs,
    Span,
    TraceRecorder,
    new_request_id,
    spans_to_chrome,
    spans_to_jsonl,
    trace_meta,
    validate_trace_jsonl,
    write_trace,
)
from repro.obs.export import LANE_STRIDE
from repro.serve_gs import RenderServer

from conftest import make_cam, make_scene

H = W = 32


# ================================================================= registry
def test_counter_gauge_and_registry_are_idempotent_and_typed():
    m = MetricsRegistry()
    c = m.counter("tier.count")
    c.inc()
    c.add(2.5)  # float increments: wall-time sums are counters too
    assert c.value == 3.5
    assert m.counter("tier.count") is c  # idempotent re-registration
    g = m.gauge("tier.depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3
    with pytest.raises(TypeError, match="already registered as Counter"):
        m.histogram("tier.count")
    assert m.get("tier.count") is c and m.get("missing") is None
    assert m.names() == ["tier.count", "tier.depth"]


def test_histogram_percentiles_and_snapshot_shape():
    m = MetricsRegistry()
    h = m.histogram("t.lat_ms")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.mean == pytest.approx(50.5)
    assert h.vmin == 1.0 and h.vmax == 100.0
    p50, p95, p99 = h.percentile(50), h.percentile(95), h.percentile(99)
    assert 1.0 <= p50 <= p95 <= p99 <= 100.0
    assert p50 < 75.0  # interpolation keeps the median in the right half
    snap = h.snapshot()
    for key in ("count", "sum", "mean", "min", "max", "p50", "p95", "p99", "buckets"):
        assert key in snap
    assert snap["count"] == 100 and sum(snap["buckets"].values()) == 100
    # overflow: a sample beyond the last bound lands in the "inf" bucket
    h.observe(1e9)
    assert h.snapshot()["buckets"]["inf"] == 1


def test_registry_snapshot_is_sorted_and_reset_clears_everything():
    m = MetricsRegistry()
    m.counter("b.two").inc(7)
    m.counter("a.one").inc(3)
    m.histogram("c.three").observe(1.0)
    ran = []
    m.on_reset(lambda: ran.append(m.counter("a.one").value))  # hooks may read
    snap = m.snapshot()
    assert list(snap) == sorted(snap)
    assert snap["a.one"] == 3 and snap["b.two"] == 7
    m.reset()
    assert ran == [0]  # hook ran under the lock, after the zeroing
    snap2 = m.snapshot()
    assert snap2["a.one"] == 0 and snap2["b.two"] == 0
    assert snap2["c.three"]["count"] == 0 and snap2["c.three"]["min"] is None


def test_registry_is_thread_safe_under_contention():
    m = MetricsRegistry()
    c = m.counter("x.n")
    h = m.histogram("x.h")

    def work():
        for i in range(1000):
            c.inc()
            h.observe(float(i % 7))

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000 and h.count == 8000


# ================================================================= recorder
def test_trace_recorder_orders_spans_and_counts_ring_drops():
    rec = TraceRecorder(capacity=4)
    assert rec  # truthy: instrumentation sites fire
    for i in range(6):
        rec.record(rid=i, name="render", t0=float(i), t1=float(i) + 0.5, batch=i)
    assert rec.recorded == 6 and rec.dropped == 2
    got = rec.spans()  # non-destructive
    assert [s.rid for s in got] == [2, 3, 4, 5]  # oldest two lapped
    assert got[0].dur == pytest.approx(0.5) and got[0].meta == {"batch": 2}
    drained = rec.drain()
    assert [s.rid for s in drained] == [2, 3, 4, 5]
    assert rec.spans() == [] and rec.dropped == 2  # accounting survives drain
    rec.instant(99, "admit", seq=0)
    (s,) = rec.spans()
    assert s.t0 == s.t1 and s.name == "admit"


def test_trace_recorder_multithreaded_writers_lose_nothing():
    rec = TraceRecorder(capacity=4096)

    def work(tid):
        for i in range(500):
            rec.record(rid=tid * 1000 + i, name="write", t0=0.0, t1=1.0)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rec.recorded == 2000 and rec.dropped == 0
    spans = rec.spans()
    assert len(spans) == 2000
    assert [s.seq for s in spans] == sorted(s.seq for s in spans)


def test_null_recorder_is_falsy_noop_and_request_ids_are_monotonic():
    assert not NULL_RECORDER
    NULL_RECORDER.record(1, "render", 0.0, 1.0)
    NULL_RECORDER.instant(1, "admit")
    assert NULL_RECORDER.spans() == [] and NULL_RECORDER.drain() == []
    assert NULL_RECORDER.recorded == 0 and NULL_RECORDER.dropped == 0
    a, b = new_request_id(), new_request_id()
    assert 0 < a < b
    obs = Obs()
    assert obs.trace is NULL_RECORDER and not obs.tracing
    rec = obs.enable_trace(capacity=16)
    assert obs.tracing and obs.enable_trace() is rec  # idempotent
    obs.disable_trace()
    assert obs.trace is NULL_RECORDER


# ================================================================ exporters
def test_exporters_jsonl_contract_and_chrome_lanes(tmp_path):
    rec = TraceRecorder()
    rec.record(1, "admit", 10.0, 10.0, seq=0, stream="static")
    rec.record(2, "mystery_stage", 10.2, 10.4)  # unknown -> overflow lane
    spans = rec.spans()
    # a meta dict can't spoof the reserved keys (record() kwargs can never
    # collide with them, but a hand-built span could): the exporter skips them
    spans.insert(1, Span(0, 1, "render", 10.1, 10.3, {"batch": 2, "rid": "spoof"}))

    text = spans_to_jsonl(spans)
    assert validate_trace_jsonl(text) == 3
    lines = [json.loads(x) for x in text.splitlines()]
    assert lines[0] == {"rid": 1, "span": "admit", "t0": 10.0, "t1": 10.0,
                        "seq": 0, "stream": "static"}
    assert lines[1]["rid"] == 1 and lines[1]["batch"] == 2  # meta can't spoof rid

    chrome = spans_to_chrome(spans)
    events = chrome["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    # one named lane per pipeline stage, plus the overflow lane the unknown
    # stage landed on
    assert len(meta) == len(STAGES) + 1
    xs = {e["name"]: e for e in events if e["ph"] == "X"}
    assert xs["render"]["tid"] == (STAGES.index("render") + 1) * LANE_STRIDE
    # overflow sits past the serving AND training lane blocks
    assert xs["mystery_stage"]["tid"] == (len(STAGES) + len(TRAIN_STAGES) + 1) * LANE_STRIDE
    assert xs["admit"]["ts"] == 0.0  # rebased to the earliest span
    assert xs["render"]["dur"] == pytest.approx(0.2e6, rel=1e-3)
    assert chrome["otherData"]["clock_domain"] == "monotonic"

    jsonl_path, chrome_path = write_trace(str(tmp_path / "t.jsonl"), spans)
    assert chrome_path.endswith(".chrome.json")
    assert validate_trace_jsonl(open(jsonl_path).read()) == 3
    assert json.load(open(chrome_path))["traceEvents"]

    for bad, msg in [
        ('{"rid": -1, "span": "a", "t0": 0, "t1": 1}', "bad rid"),
        ('{"rid": 1, "t0": 0, "t1": 1}', "missing 'span'"),
        ('{"rid": 1, "span": "a", "t0": 2, "t1": 1}', "t1 < t0"),
        ("not json", "not JSON"),
        ('[1, 2]', "not an object"),
    ]:
        with pytest.raises(ValueError, match=msg):
            validate_trace_jsonl(bad + "\n")
    assert validate_trace_jsonl("") == 0


def test_chrome_overlapping_spans_spill_into_sublanes_and_meta_rides_along():
    """Two render spans that overlap in time (a pipelined wave) must land on
    DIFFERENT sub-lanes of the render block — they used to interleave into
    one unreadable bar row — and the export header (drop accounting + knobs)
    must survive both export formats."""
    rec = TraceRecorder(capacity=2)
    for i in range(3):  # capacity 2: the first span is lapped
        rec.record(i, "render", 1.0 + 0.1 * i, 1.25 + 0.1 * i, batch=4)
    spans = rec.spans()
    meta = trace_meta(rec, knobs={"max_batch": 4})
    assert meta["dropped"] == 1 and meta["capacity"] == 2

    chrome = spans_to_chrome(spans, meta=meta)
    xs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    base = (STAGES.index("render") + 1) * LANE_STRIDE
    assert sorted(e["tid"] for e in xs) == [base, base + 1]  # overlap: 2 lanes
    labels = {e["args"]["name"] for e in chrome["traceEvents"] if e["ph"] == "M"}
    assert any(lbl.endswith("render#1") for lbl in labels)
    assert chrome["otherData"]["knobs"] == {"max_batch": 4}
    assert chrome["otherData"]["dropped"] == 1

    n = validate_trace_jsonl(spans_to_jsonl(spans, meta=meta))
    assert n == 2  # the meta line is not a span
    assert n.dropped == 1 and n.capacity == 2 and n.knobs == {"max_batch": 4}
    # meta anywhere but the first line is corruption, not data
    bad = spans_to_jsonl(spans) + '{"trace_meta": {}}\n'
    with pytest.raises(ValueError, match="first line"):
        validate_trace_jsonl(bad)


# ===================================================== zero-cost-when-off
class _Views:
    """Two fixed views for ``GSTrainer.fit``."""

    def __init__(self):
        self.cams = P.Camera(*[jnp.stack(x) for x in zip(
            make_cam(H, W, dist=2.3), make_cam(H, W, dist=2.6))])
        self.gt = jnp.zeros((2, H, W, 3), jnp.float32)

    def batches(self, batch_size, *, steps):
        for _ in range(steps):
            yield self.cams, self.gt


def test_tracing_disabled_allocates_nothing_and_frames_are_bitwise(monkeypatch):
    """The two acceptance guarantees of the no-op recorder: with tracing off
    a render and a train step allocate NOTHING in the recorder module and
    read no clock there (the span regions, the queue and cache spans, the
    compile listener), and enabling tracing changes no pixel of the
    rendered frame."""
    import repro.obs.trace as trace_mod
    from repro.launch.train import GSTrainer

    srv = RenderServer(
        make_scene(n=128, scale=0.06), GSConfig(img_h=H, img_w=W, k_per_tile=64),
        n_levels=1, max_batch=2, store_frames=False,
    )
    g = make_scene(n=128, scale=0.06)
    tr = GSTrainer(GSConfig(img_h=H, img_w=W, k_per_tile=64, batch_size=2, pad_quantum=128),
                   make_mesh((1, 1)), np.asarray(g.means), np.full((128, 3), 0.5, np.float32),
                   verbose=False)
    views = _Views()
    with srv:
        assert srv.obs.trace is NULL_RECORDER and tr.obs.trace is NULL_RECORDER
        cam = make_cam(H, W, dist=2.3)
        srv.submit(cam).result()  # compile + warm every code path
        srv.cache.drop(lambda k: True)
        tr.fit(views, steps=1, densify=False)
        clock_reads = []
        real_now = trace_mod.now
        monkeypatch.setattr(trace_mod, "now", lambda: clock_reads.append(1) or real_now())

        tracemalloc.start()
        s1 = tracemalloc.take_snapshot()
        frame_off = srv.submit(cam).result()
        tr.fit(views, steps=1, densify=False)
        s2 = tracemalloc.take_snapshot()
        tracemalloc.stop()
        filt = [tracemalloc.Filter(True, "*obs/trace*")]
        diff = s2.filter_traces(filt).compare_to(s1.filter_traces(filt), "lineno")
        assert sum(abs(d.size_diff) for d in diff) == 0, diff
        assert clock_reads == []
        monkeypatch.setattr(trace_mod, "now", real_now)

        srv.obs.enable_trace()
        srv.cache.drop(lambda k: True)
        frame_on = srv.submit(cam).result()
        np.testing.assert_array_equal(np.asarray(frame_off), np.asarray(frame_on))
        spans = srv.obs.trace.drain()
        assert {s.name for s in spans} >= {"submit", "render"}
        assert all(s.name in STAGES for s in spans)


# ========================================================== span trees (TCP)
def _obs_manager(*, queue_limit=8, timeline_steps=2):
    g = make_scene(n=256, scale=0.06)
    cfg = GSConfig(img_h=H, img_w=W, k_per_tile=64)
    mgr = SessionManager(
        cfg, obs=Obs(trace=True), n_levels=1, max_batch=4,
        store_frames=False, pipeline_depth=2,
    )
    mgr.register_static("static", g)
    if timeline_steps:
        from repro.launch.frontend import synthetic_timeline

        mgr.register_timeline("timeline", synthetic_timeline(g, timeline_steps))
    return mgr


@pytest.fixture(scope="module")
def traced_gt():
    mgr = _obs_manager()
    mgr.warmup()
    with GatewayThread(Gateway(mgr, port=0, queue_limit=8)) as gt:
        yield gt


# spans that root a tree of their own, outside any request's: a batch's
# launch and a compile
_OWN_TREE = ("dispatch", "compile")


def _trees(spans) -> dict:
    """{rid: [spans in record order]} of the requests"""
    trees = {}
    for s in spans:
        if s.name not in _OWN_TREE:
            trees.setdefault(s.rid, []).append(s)
    for v in trees.values():
        v.sort(key=lambda s: s.seq)
    return trees


def _wait_spans(rec, pred, timeout=30.0):
    """The write span lands on the gateway loop a beat after the client has
    its frame — poll (non-destructively) until the tree is complete."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        spans = rec.spans()
        if pred(spans):
            return spans
        time.sleep(0.01)
    return rec.spans()


def _named(tree, name):
    return [s for s in tree if s.name == name]


def test_tcp_miss_then_cache_hit_span_trees(traced_gt):
    """One TCP request -> ONE complete span tree, admit through socket write;
    a repeated pose yields the short cache-hit tree with no render span."""
    rec = traced_gt.gateway.obs.trace
    rec.drain()
    cam = make_cam(H, W, dist=2.45)
    with FrontendClient("127.0.0.1", traced_gt.port) as cl:
        cl.render("static", cam)
        cl.render("static", cam)
        spans = _wait_spans(
            rec, lambda ss: sum(1 for s in ss if s.name == "write") >= 2
        )
    dispatches = [s for s in spans if s.name == "dispatch"]
    assert len(dispatches) == 1 and dispatches[0].meta["batch"] == 1
    trees = _trees(spans)
    assert len(trees) == 2
    rid_miss, rid_hit = sorted(trees)

    miss = trees[rid_miss]
    assert [s.name for s in miss] == [
        "admit", "coalesce", "cache", "submit", "queue", "render", "cache", "retire",
        "encode", "write",
    ]
    probe, put = _named(miss, "cache")
    assert probe.meta["op"] == "probe" and put.meta["op"] == "put"
    (que,) = _named(miss, "queue")
    assert que.t0 == _named(miss, "submit")[0].t0 and que.t1 >= que.t0
    (sub,) = _named(miss, "submit")
    assert sub.meta["outcome"] == "miss"
    (adm,) = _named(miss, "admit")
    assert adm.meta["stream"] == "static" and adm.t0 == adm.t1  # instant root
    (ren,) = _named(miss, "render")
    assert ren.meta["batch"] >= 1 and ren.dur > 0
    (wr,) = _named(miss, "write")
    assert wr.meta["ok"] and wr.meta["bytes"] > 0
    for s in miss:
        assert s.t1 >= s.t0

    hit = trees[rid_hit]
    assert [s.name for s in hit] == ["admit", "coalesce", "cache", "submit", "encode", "write"]
    (sub,) = _named(hit, "submit")
    assert sub.meta["outcome"] in ("full_hit", "cache_hit")
    assert not _named(hit, "render")

    # the exported forms carry the full trees
    text = spans_to_jsonl(spans)
    assert validate_trace_jsonl(text) == len(spans)
    rids = {json.loads(x)["rid"] for x in text.splitlines()}
    assert rids == {s.rid for s in spans}


def test_tcp_dedup_span_points_at_primary_request(traced_gt):
    """Two identical poses coalescing into one wave: the second request's
    submit span reports outcome=dedup and names the primary request id —
    and only the primary carries the render span."""
    gw = traced_gt.gateway
    rec = gw.obs.trace
    rec.drain()
    cam = make_cam(H, W, dist=2.61)

    async def run():
        cl = AsyncFrontendClient("127.0.0.1", traced_gt.port)
        await cl.connect()
        traced_gt.call_soon(gw.pause)  # hold dispatch: both land in one wave
        await asyncio.sleep(0.05)
        futs = [await cl.submit_render("static", cam) for _ in range(2)]
        traced_gt.call_soon(gw.resume)
        frames = [await f for f in futs]
        await cl.close()
        return frames

    frames = asyncio.run(run())
    np.testing.assert_array_equal(frames[0], frames[1])
    spans = _wait_spans(
        rec, lambda ss: sum(1 for s in ss if s.name == "write") >= 2
    )
    trees = _trees(spans)
    assert len(trees) == 2
    rid_primary, rid_dup = sorted(trees)
    (sub,) = _named(trees[rid_dup], "submit")
    assert sub.meta["outcome"] == "dedup" and sub.meta["primary"] == rid_primary
    assert not _named(trees[rid_dup], "render")
    assert len(_named(trees[rid_primary], "render")) == 1
    assert _named(trees[rid_dup], "write") and _named(trees[rid_primary], "write")


def test_tcp_partial_tile_hit_span_tree(traced_gt):
    """Row-invalidation then a revisit: the submit span reports partial_hit
    with the missing-tile count, and the tree shows the strip render +
    assemble instead of a full-batch render."""
    gw = traced_gt.gateway
    rec = gw.obs.trace
    cam = make_cam(H, W, dist=2.77)
    with FrontendClient("127.0.0.1", traced_gt.port) as cl:
        cl.render("timeline", cam, timestep=1)  # fill the tile cache
        # drop ONLY tile row 0 of that timestep, on the engine thread
        n = gw.run_on_engine(
            lambda: gw.manager.invalidate("timeline", 1, rows=[0])
        ).result(timeout=60)
        assert n > 0
        rec.drain()
        cl.render("timeline", cam, timestep=1)
        spans = _wait_spans(
            rec, lambda ss: sum(1 for s in ss if s.name == "write") >= 1
        )
    (tree,) = _trees(spans).values()
    names = [s.name for s in tree]
    assert names == [
        "admit", "coalesce", "cache", "submit", "queue", "cache", "render", "cache", "assemble",
        "encode", "write",
    ]
    (sub,) = _named(tree, "submit")
    assert sub.meta["outcome"] == "partial_hit"
    assert sub.meta["missing_tiles"] == W // 16  # one 16px tile row
    (ren,) = _named(tree, "render")
    assert ren.meta["partial"] is True and ren.meta["rows"] == 1


def test_tcp_shed_request_emits_terminated_span():
    """A load-shed request's tree must END visibly: admit then a terminated
    shed span — and no render/write spans ever join that rid."""
    mgr = _obs_manager(timeline_steps=0)
    mgr.warmup()
    gw = Gateway(mgr, port=0, queue_limit=2)
    rec = mgr.obs.trace
    with GatewayThread(gw) as gt:

        async def run():
            cl = AsyncFrontendClient("127.0.0.1", gt.port)
            await cl.connect()
            gt.call_soon(gw.pause)
            await asyncio.sleep(0.05)
            futs = [
                await cl.submit_render("static", make_cam(H, W, dist=2.0 + 0.3 * i))
                for i in range(6)
            ]
            for fut in futs[:4]:
                with pytest.raises(ShedError):
                    await fut
            gt.call_soon(gw.resume)
            survivors = [await fut for fut in futs[4:]]
            await cl.close()
            return survivors

        survivors = asyncio.run(run())
        assert len(survivors) == 2
        spans = _wait_spans(
            rec, lambda ss: sum(1 for s in ss if s.name == "write") >= 2
        )
    trees = _trees(spans)
    shed_rids = {s.rid for s in spans if s.name == "shed"}
    assert len(shed_rids) == 4
    for rid in shed_rids:
        names = [s.name for s in trees[rid]]
        assert names == ["admit", "shed"]  # the tree ends here, visibly
        (sh,) = _named(trees[rid], "shed")
        assert sh.meta["terminated"] is True and sh.t1 >= sh.t0
    for rid in set(trees) - shed_rids:
        assert [s.name for s in trees[rid]] == [
            "admit", "coalesce", "cache", "submit", "queue", "render", "cache", "retire",
            "encode", "write",
        ]


# ===================================================== metrics on the wire
def test_metrics_message_round_trip_and_unified_reset_windows(traced_gt):
    """Protocol-v2 `metrics`: an atomic flat snapshot over TCP; ONE reset()
    zeroes every tier's counters (the benchmark-window contract) while the
    cache CONTENTS survive — the regression that motivated the unified
    reset: per-tier resets used to leave other tiers' windows dirty."""
    gw = traced_gt.gateway
    cam = make_cam(H, W, dist=2.93)
    with FrontendClient("127.0.0.1", traced_gt.port) as cl:
        cl.render("static", cam)
        out = cl.metrics()
        snap = out["metrics"]
        assert out["trace"]["enabled"] is True
        assert out["trace"]["recorded"] >= 1 and out["trace"]["dropped"] == 0
        assert snap["gateway.frames_sent"] >= 1
        assert snap["server.completed"] >= 1
        assert snap["sessions.admitted"] >= 1
        assert snap["cache.misses"] >= 1
        assert snap["server.latency_ms"]["count"] >= 1  # histograms ride along

        gw.run_on_engine(gw.manager.obs.metrics.reset).result(timeout=60)
        # NOT asserted zero: gateway.bytes_out — the deferred-drain write of
        # the previous reply may land (and count its bytes) after the reset
        snap2 = cl.metrics()["metrics"]
        for name in (
            "gateway.frames_sent", "gateway.shed",
            "server.completed", "server.deduped", "server.render_calls",
            "sessions.admitted", "cache.hits", "cache.misses",
        ):
            assert snap2[name] == 0, (name, snap2[name])
        assert snap2["server.latency_ms"]["count"] == 0

        # the new window starts clean AND warm: the same pose is still a
        # cache hit (reset clears counters, never cached content)
        cl.render("static", cam)
        snap3 = cl.metrics()["metrics"]
    assert snap3["gateway.frames_sent"] == 1
    assert snap3["server.completed"] == 1
    assert snap3["server.render_calls"] == 0  # no re-render happened
    assert snap3["server.full_hits"] == 1


def test_slo_state_is_visible_over_the_wire():
    """A gateway started with an SLO target must surface the tracker's state
    in BOTH wire surfaces: the protocol-v2 `metrics` message and the `stats`
    report — a real-TCP regression for the ops loop (dashboards watch the
    metrics message, humans read stats)."""
    mgr = _obs_manager(timeline_steps=0)
    mgr.warmup()
    gw = Gateway(mgr, port=0, queue_limit=8,
                 slo={"p99_ms": 2000.0, "window_s": 60.0})
    with GatewayThread(gw) as gt:
        with FrontendClient("127.0.0.1", gt.port) as cl:
            for i in range(3):
                cl.render("static", make_cam(H, W, dist=2.2 + 0.2 * i))
            slo = cl.metrics()["slo"]
            assert slo["state"] == "ok"  # 2s budget: smoke renders can't breach
            assert slo["target_p99_ms"] == 2000.0
            assert slo["window_count"] >= 1
            assert slo["window_p99_ms"] is not None
            stats = cl.stats()
    assert stats["gateway"]["slo"]["state"] == "ok"
    assert stats["gateway"]["slo"]["burn"] == 0.0
