"""Streaming subsystem tests: dead-slot reseeding, temporal checkpoint store,
warm-start-vs-cold step counts (with zero re-traces), and time-scrub serving.
"""
import jax
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import gaussians as G
from repro.core.config import GSConfig
from repro.core.sharding import make_mesh
from repro.core.train import init_state
from repro.insitu import (
    InsituTrainer,
    TemporalCheckpointStore,
    build_timeline_server,
    fixed_capacity_init,
    reseed_dead_slots,
    scrub,
)
from repro.serve_gs import RenderServer
from repro.volume.timevary import miranda_growth

from conftest import make_cam

H = W = 32


def _random_params(n, seed=0, shift=0.0):
    r = np.random.default_rng(seed)
    g = G.init_from_points(
        jnp.asarray(r.normal(0, 0.4, (n, 3)).astype(np.float32) + shift),
        jnp.asarray(r.uniform(0.2, 0.8, (n, 3)).astype(np.float32)),
        init_scale=0.06,
    )
    return g


# ------------------------------------------------------------------ reseed
def test_fixed_capacity_init_pads_with_dead_slots():
    pts = np.random.default_rng(0).normal(0, 0.4, (10, 3)).astype(np.float32)
    cols = np.full((10, 3), 0.5, np.float32)
    g = fixed_capacity_init(pts, cols, 16)
    assert g.n == 16
    opac = 1.0 / (1.0 + np.exp(-np.asarray(g.opacity_logit)))
    assert (opac[:10] > 0.05).all() and (opac[10:] < 1e-6).all()
    np.testing.assert_allclose(np.asarray(g.means)[:10], pts)


def test_reseed_dead_slots_fills_only_dead_capacity():
    rng = np.random.default_rng(1)
    pts0 = rng.normal(0, 0.4, (12, 3)).astype(np.float32)
    state = init_state(fixed_capacity_init(pts0, np.full((12, 3), 0.5, np.float32), 20))
    # make the adam moments nonzero so zeroing is observable
    ones = jax.tree_util.tree_map(jnp.ones_like, state.params)
    state = state._replace(adam=state.adam._replace(m=ones, v=ones))

    new_pts = rng.normal(0, 0.4, (30, 3)).astype(np.float32) + 5.0
    new_cols = np.full((30, 3), 0.7, np.float32)
    new_state, n_reseeded, slots = reseed_dead_slots(state, new_pts, new_cols, opacity_thresh=0.005)

    assert n_reseeded == 8  # all dead capacity refilled (points were plentiful)
    np.testing.assert_array_equal(slots, np.arange(12, 20))  # the refilled rows
    assert new_state.params.n == 20  # shapes untouched
    means = np.asarray(new_state.params.means)
    np.testing.assert_allclose(means[:12], pts0, atol=0)  # live rows untouched
    assert (np.abs(means[12:]).max(axis=1) > 3.0).all()  # dead rows now near +5
    opac = 1.0 / (1.0 + np.exp(-np.asarray(new_state.params.opacity_logit)))
    assert (opac[12:] > 0.05).all()  # reborn, not dead
    m = np.asarray(new_state.adam.m.means)
    assert (m[:12] == 1.0).all() and (m[12:] == 0.0).all()  # newborn moments zeroed


def test_reseed_with_no_dead_slots_is_identity():
    state = init_state(_random_params(16))
    new_state, n, slots = reseed_dead_slots(state, np.zeros((5, 3), np.float32), np.zeros((5, 3), np.float32))
    assert n == 0 and slots.size == 0
    np.testing.assert_array_equal(
        np.asarray(new_state.params.means), np.asarray(state.params.means)
    )


# ----------------------------------------------------------- temporal store
def test_temporal_store_keyframe_delta_roundtrip(tmp_path):
    rng = np.random.default_rng(2)
    frames = []
    g = _random_params(40, seed=3)
    for t in range(5):
        g = g._replace(means=g.means + jnp.asarray(rng.normal(0, 0.01, (40, 3)).astype(np.float32)))
        frames.append(jax.tree_util.tree_map(np.asarray, g))

    store = TemporalCheckpointStore(str(tmp_path / "seq"), keyframe_interval=2)
    for t, f in enumerate(frames):
        store.append(t, f)
    st = store.stats()
    assert store.timesteps() == [0, 1, 2, 3, 4]
    assert st["keyframes"] == 3 and st["delta_frames"] == 2  # every 2nd frame is a key

    for t, ref in enumerate(frames):
        got = store.load(t)
        for name in G.GaussianModel._fields:
            a, b = np.asarray(getattr(got, name)), np.asarray(getattr(ref, name))
            # keyframes restore exactly; delta frames are int16-quantized so
            # they land within one quantum of the true value (no drift:
            # deltas chain against the reconstructed previous frame)
            tol = 1e-7 if t % 2 == 0 else 2e-3
            np.testing.assert_allclose(a, b, atol=tol, err_msg=f"t={t} {name}")


def test_temporal_store_exact_rows_survive_reseed_jump(tmp_path):
    """A reseeded dead slot jumps its mean from the 1e6 sentinel into the
    scene — six orders of magnitude above the training deltas. Jump rows are
    stored exactly; the shared quantization scale must stay tight for the
    smooth rows instead of being poisoned by the jump."""
    g0 = _random_params(32, seed=7)
    g0 = g0._replace(means=g0.means.at[24:].set(1.0e6))  # dead padding
    rng = np.random.default_rng(8)
    drift = jnp.asarray(rng.normal(0, 0.01, (32, 3)).astype(np.float32))
    g1 = g0._replace(means=g0.means + drift)
    g1 = g1._replace(means=g1.means.at[24:].set(  # reseed: sentinel -> scene
        jnp.asarray(rng.normal(0, 0.4, (8, 3)).astype(np.float32))
    ))

    store = TemporalCheckpointStore(str(tmp_path / "seq"), keyframe_interval=10)
    store.append(0, g0)
    store.append(1, g1)  # delta frame containing the jump
    got = np.asarray(store.load(1).means)
    ref = np.asarray(g1.means)
    np.testing.assert_allclose(got[24:], ref[24:], atol=1e-6)  # jumps exact
    np.testing.assert_allclose(got[:24], ref[:24], atol=1e-4)  # smooth rows tight


def test_temporal_store_survives_reopen(tmp_path):
    g = _random_params(24, seed=4)
    d = str(tmp_path / "seq")
    store = TemporalCheckpointStore(d, keyframe_interval=3)
    store.append(0, g)
    store.append(1, g._replace(means=g.means + 0.01))
    store.close()  # async writer: make the sequence durable before reopening

    reopened = TemporalCheckpointStore(d, keyframe_interval=7)
    assert reopened.keyframe_interval == 3  # the on-disk sequence owns its cadence
    assert reopened.timesteps() == [0, 1]
    reopened.append(2, g._replace(means=g.means + 0.02))
    got = reopened.load(2)
    np.testing.assert_allclose(
        np.asarray(got.means), np.asarray(g.means) + 0.02, atol=2e-3
    )
    with pytest.raises(AssertionError):
        reopened.append(2, g)  # timesteps must be strictly increasing


def test_temporal_store_changed_slots_from_delta_encoding(tmp_path):
    """The delta encoding already knows which slots an update rewrote:
    ``changed_slots`` recovers exactly the perturbed rows from a delta frame
    and answers None (unknown) for keyframes."""
    import jax.numpy as jnp

    g = _random_params(32, seed=12)
    store = TemporalCheckpointStore(str(tmp_path / "seq"), keyframe_interval=10)
    store.append(0, g)
    means2 = np.asarray(g.means).copy()
    means2[[3, 7]] += 0.05
    store.append(1, g._replace(means=jnp.asarray(means2)))
    assert store.changed_slots(0) is None  # keyframe: no change set exists
    np.testing.assert_array_equal(store.changed_slots(1), [3, 7])


def test_replay_live_uses_changed_slots_for_partial_invalidation(tmp_path):
    """Post hoc live replay: stored deltas drive world-space invalidation of
    ONE serving slot — after the first pose registers, bounded updates drop
    tile rows, not whole frames, and served frames track the new model."""
    from repro.insitu import replay_live

    store = TemporalCheckpointStore(str(tmp_path / "seq"), keyframe_interval=10)
    g = _random_params(128, seed=5)
    store.append(0, g)
    means = np.asarray(g.means)
    for t in (1, 2):
        moved = means.copy()
        moved[:4] += np.float32(0.05 * t)  # a bounded 4-slot update
        store.append(t, g._replace(means=jnp.asarray(moved)))

    cfg = GSConfig(img_h=H, img_w=W, k_per_tile=64)
    server = build_timeline_server(
        store, cfg, timesteps=[0], n_levels=1, max_batch=2, cache_capacity=64
    )
    events = []
    server.add_invalidation_listener(lambda ts, rows: events.append(rows))
    cam = make_cam(H, W)
    frames = [server.submit(cam, timestep=0).result()]  # registers the pose
    replay_live(
        store, server, timesteps=[1, 2], serve_timestep=0,
        on_timestep=lambda t: frames.append(server.submit(cam, timestep=0).result()),
    )
    assert len(frames) == 3  # initial + one per replayed delta timestep
    assert np.abs(frames[2] - frames[0]).max() > 1e-4  # updates visible
    # the delta timesteps invalidated row sets, never the whole frame
    assert len(events) == 2 and all(rows is not None for rows in events)
    # ground truth: the final frame equals a fresh full render of t=2
    ref_server = build_timeline_server(store, cfg, timesteps=[2], n_levels=1, max_batch=2)
    np.testing.assert_array_equal(frames[2], ref_server.submit(cam, timestep=2).result())


# ------------------------------------------------------- time-scrub serving
def test_timeline_server_scrubs_distinct_cached_frames(tmp_path):
    # store -> timeline server: the post hoc time-scrubbing path end-to-end
    store = TemporalCheckpointStore(str(tmp_path / "seq"), keyframe_interval=2)
    for t in range(3):
        store.append(t, _random_params(128, seed=5, shift=0.15 * t))
    cfg = GSConfig(img_h=H, img_w=W, k_per_tile=64)
    server = build_timeline_server(store, cfg, n_levels=2, max_batch=2, cache_capacity=64)
    assert server.timesteps() == [0, 1, 2]

    cam = make_cam(H, W)
    frames = scrub(server, cam, [0, 1, 2])
    # same camera, three timesteps -> three distinct frames
    assert set(frames) == {0, 1, 2}
    for t in (0, 1):
        assert np.abs(frames[t] - frames[t + 1]).max() > 1e-4
    # replaying the scrub is pure cache hits: no new renders
    calls = server.report()["render"]["calls"]
    frames2 = scrub(server, cam, [0, 1, 2])
    rep = server.report()
    assert rep["render"]["calls"] == calls
    assert rep["cache"]["hits"] >= 3
    for t in (0, 1, 2):
        np.testing.assert_array_equal(frames[t], frames2[t])
    assert rep["timeline"]["requests_per_timestep"] == {0: 2, 1: 2, 2: 2}


def test_add_timestep_replacement_invalidates_cached_frames():
    cfg = GSConfig(img_h=H, img_w=W, k_per_tile=64)
    server = RenderServer(_random_params(128, seed=9), cfg, n_levels=1, max_batch=2, cache_capacity=64)
    cam = make_cam(H, W)
    old_frame = server.submit(cam).result()
    server.add_timestep(0, _random_params(128, seed=9, shift=0.5))  # replace the model
    fut2 = server.submit(cam)  # must MISS the cache and re-render
    assert np.abs(fut2.result() - old_frame).max() > 1e-4
    assert server.report()["render"]["calls"] == 2


def test_timeline_server_rejects_unknown_timestep():
    server = RenderServer(_random_params(64, seed=6), GSConfig(img_h=H, img_w=W, k_per_tile=64), n_levels=1)
    with pytest.raises(KeyError):
        server.submit(make_cam(H, W), timestep=7)


def test_batcher_groups_by_timestep():
    from repro.serve_gs import MicroBatcher, RenderRequest

    b = MicroBatcher(max_batch=4)
    cam = make_cam(H, W)
    r0 = RenderRequest(cam=cam, level=0, timestep=0)
    r1 = RenderRequest(cam=cam, level=0, timestep=1)
    b.submit(r0)
    b.submit(r1)
    mb0 = b.next_batch()
    mb1 = b.next_batch()
    assert mb0.timestep == 0 and mb0.requests == (r0,)
    assert mb1.timestep == 1 and mb1.requests == (r1,)


# --------------------------------------------------- epoch coverage (views)
def test_viewdataset_epoch_covers_every_view():
    from repro.data.views import ViewDataset

    vol = miranda_growth(0.0, res=12)
    data = ViewDataset(vol, n_views=5, img_h=12, img_w=12, cache_dir=None, n_steps_raymarch=8)
    counts = np.zeros(5, int)
    for cams, gt in data.batches(2, steps=5):  # 10 draws = 2 epochs over 5 views
        assert gt.shape == (2, 12, 12, 3)
        # recover view indices by matching view matrices
        all_vm = np.asarray(data.cams.viewmat).reshape(5, -1)
        for vm in np.asarray(cams.viewmat).reshape(2, -1):
            d = np.linalg.norm(all_vm - vm, axis=1)
            counts[int(np.argmin(d))] += 1
    # the old iterator dropped each epoch's leftover views; now every view is
    # sampled exactly once per epoch
    np.testing.assert_array_equal(counts, np.full(5, 2))


# --------------------------------------------- warm start beats cold start
@pytest.mark.slow
def test_warm_start_fewer_steps_and_zero_retraces():
    """After a small timestep perturbation, warm-start reaches the cold-start
    PSNR in strictly fewer optimization steps, with zero re-traces of the
    train step across timesteps."""
    mesh = make_mesh((1, 1))
    cfg = GSConfig(
        img_h=48, img_w=48, batch_size=2, k_per_tile=128, max_steps=200,
        densify_from=10**9, opacity_reset_interval=10**9,
    )
    kw = dict(
        cold_steps=80, warm_steps=80, n_views=6, max_points=800,
        n_steps_raymarch=48, init_scale=0.06, eval_every=10, seed=0,
    )
    vol0 = miranda_growth(0.0, res=32)
    vol1 = miranda_growth(0.075, res=32)  # small perturbation

    warm = InsituTrainer(cfg, mesh, **kw)
    warm.start(vol0)
    rep_warm = warm.advance(vol1)
    assert warm.n_traces == 1  # zero re-traces across the two timesteps

    cold = InsituTrainer(cfg, mesh, capacity=warm.capacity, **kw)
    rep_cold = cold.start(vol1)

    target = rep_cold.psnr_after - 0.1
    def steps_to(curve):
        return next((s for s, p in curve if p >= target), None)

    w_steps, c_steps = steps_to(rep_warm.psnr_curve), steps_to(rep_cold.psnr_curve)
    assert w_steps is not None, (target, rep_warm.psnr_curve)
    assert c_steps is not None
    assert w_steps < c_steps, (w_steps, c_steps, target)
