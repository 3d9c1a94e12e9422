"""Serving subsystem tests: LOD pyramid, micro-batcher, frame cache, and the
checkpoint -> server path."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.core import gaussians as G
from repro.core import render as R
from repro.core.config import GSConfig
from repro.core.losses import psnr
from repro.core.sharding import make_mesh
from repro.core.train import init_state, make_batched_eval_render, make_eval_render
from repro.launch.serve_gs import load_params_from_ckpt
from repro.serve_gs import (
    FrameCache,
    MicroBatcher,
    RenderRequest,
    RenderServer,
    build_lod_pyramid,
    frame_key,
    select_level,
    stack_cameras,
)
from repro.volume.cameras import camera_slice, orbit_cameras

from conftest import make_cam, make_scene

H = W = 32


def _render_model(g, cam):
    img, _ = R.render(g, cam, img_h=H, img_w=W, k_per_tile=128)
    return img


# --------------------------------------------------------------------- LOD
def test_lod_pyramid_monotone_and_close_to_full():
    g = make_scene(n=400, scale=0.08)
    pyr = build_lod_pyramid(g, n_levels=3, keep_ratio=0.5, pad_quantum=64)
    # each level has strictly fewer live Gaussians, padded to the quantum
    assert list(pyr.live_counts) == sorted(pyr.live_counts, reverse=True)
    for a, b in zip(pyr.live_counts, pyr.live_counts[1:]):
        assert b < a
    for lvl in pyr.levels[1:]:
        assert lvl.n % 64 == 0
    # level 0 is the model verbatim
    np.testing.assert_array_equal(np.asarray(pyr.levels[0].means), np.asarray(g.means))

    cam = make_cam(H, W)
    full = _render_model(g, cam)
    for k, lvl in enumerate(pyr.levels[1:], start=1):
        img = _render_model(G.GaussianModel(*[jnp.asarray(x) for x in lvl]), cam)
        p = float(psnr(img, full))
        assert np.isfinite(np.asarray(img)).all()
        # importance pruning keeps the dominant splats: each halving of the
        # Gaussian count may cost fidelity, but a 2x/4x-pruned toy scene must
        # stay recognizably the same image (bound loosens with depth)
        assert p > 20.0 - 3.0 * k, (k, p)


def test_lod_level_selection_by_distance():
    g = make_scene(n=300)
    pyr = build_lod_pyramid(g, n_levels=3, keep_ratio=0.5, pad_quantum=64)
    near = make_cam(H, W, dist=2.0)
    far = make_cam(H, W, dist=40.0)
    l_near = select_level(pyr, near, img_w=W)
    l_far = select_level(pyr, far, img_w=W)
    assert 0 <= l_near <= l_far <= pyr.n_levels - 1
    assert l_far > l_near


# ----------------------------------------------------------------- batcher
def _req(cam, level):
    return RenderRequest(cam=cam, level=level)


def test_batcher_coalesces_by_level_and_pads_to_bucket():
    cams = orbit_cameras(8, img_h=H, img_w=W)
    b = MicroBatcher(max_batch=4)
    ids0 = [b.submit(_req(camera_slice(cams, i), 0)) for i in range(3)]
    ids1 = [b.submit(_req(camera_slice(cams, i + 3), 1)) for i in range(2)]
    assert b.pending == 5

    mb = b.next_batch()  # level 0 submitted first -> drains first
    assert mb.level == 0
    assert [r.request_id for r in mb.requests] == ids0
    assert mb.bucket == 4  # 3 requests pad to the next bucket
    assert np.asarray(mb.cams.viewmat).shape == (4, 4, 4)
    # padding repeats the last real camera
    np.testing.assert_array_equal(
        np.asarray(mb.cams.viewmat)[3], np.asarray(mb.cams.viewmat)[2]
    )

    mb1 = b.next_batch()
    assert mb1.level == 1 and [r.request_id for r in mb1.requests] == ids1
    assert mb1.bucket == 2
    assert b.next_batch() is None and b.pending == 0


def test_batcher_respects_max_batch_and_fifo():
    cams = orbit_cameras(10, img_h=H, img_w=W)
    b = MicroBatcher(max_batch=4)
    for i in range(6):
        b.submit(_req(camera_slice(cams, i), 0))
    first = b.next_batch()
    assert len(first.requests) == 4 and first.bucket == 4
    second = b.next_batch()
    assert len(second.requests) == 2
    got = [r.request_id for r in first.requests + second.requests]
    assert got == sorted(got)  # FIFO order preserved


# ------------------------------------------------------------------- cache
def test_cache_key_quantization():
    cam = make_cam(H, W, dist=3.0)
    q = 1e-3
    k0 = frame_key(cam, 0, height=H, width=W, pose_quantum=q)
    # sub-quantum pose jitter shares the key
    jig = cam._replace(viewmat=cam.viewmat + 1e-5)
    assert frame_key(jig, 0, height=H, width=W, pose_quantum=q) == k0
    # super-quantum motion, another level, or other intrinsics do not
    moved = cam._replace(viewmat=cam.viewmat.at[2, 3].add(5 * q))
    assert frame_key(moved, 0, height=H, width=W, pose_quantum=q) != k0
    assert frame_key(cam, 1, height=H, width=W, pose_quantum=q) != k0
    zoomed = cam._replace(fx=cam.fx * 2)
    assert frame_key(zoomed, 0, height=H, width=W, pose_quantum=q) != k0
    # regression: the same quantized pose at another OUTPUT RESOLUTION must
    # not share a key — a hit would hand back a wrong-size frame
    assert frame_key(cam, 0, height=2 * H, width=2 * W, pose_quantum=q) != k0


def test_cache_lru_eviction_and_stats():
    c = FrameCache(capacity=2)
    f = np.zeros((2, 2, 3), np.float32)
    assert c.get(("a",)) is None  # miss
    c.put(("a",), f)
    c.put(("b",), f)
    assert c.get(("a",)) is not None  # hit; "a" becomes most-recent
    c.put(("c",), f)  # evicts "b" (least recent)
    assert c.get(("b",)) is None
    assert c.get(("c",)) is not None
    s = c.stats()
    assert s["hits"] == 2 and s["misses"] == 2 and s["evictions"] == 1
    assert s["hit_rate"] == 0.5 and len(c) == 2


# ------------------------------------------------- batched render + server
def test_batched_eval_render_matches_single():
    mesh = make_mesh((1, 1))
    cfg = GSConfig(img_h=H, img_w=W, k_per_tile=128)
    g = make_scene(n=256, scale=0.06)
    cams = orbit_cameras(3, img_h=H, img_w=W)
    single = make_eval_render(mesh, cfg)
    for mode in ("map", "vmap"):
        batched = make_batched_eval_render(mesh, cfg, batch_mode=mode)
        imgs = batched(g, stack_cameras([camera_slice(cams, i) for i in range(3)]))
        for i in range(3):
            ref, _ = single(g, camera_slice(cams, i))
            np.testing.assert_allclose(np.asarray(imgs[i]), np.asarray(ref), atol=1e-5)


def test_server_serves_and_caches(tmp_path):
    g = make_scene(n=256, scale=0.06)
    cfg = GSConfig(img_h=H, img_w=W, k_per_tile=64)
    server = RenderServer(g, cfg, n_levels=2, max_batch=4, cache_capacity=64)
    cams = orbit_cameras(4, img_h=H, img_w=W)
    futs = [server.submit(camera_slice(cams, i)) for i in range(4)]
    assert server.run() == 4
    assert all(f.done() for f in futs)
    # resubmitting the same poses is served from cache without new renders
    calls_before = server.report()["render"]["calls"]
    futs2 = [server.submit(camera_slice(cams, i)) for i in range(4)]
    assert all(f.done() for f in futs2)  # cache hits resolve at submit
    server.run()
    rep = server.report()
    assert rep["render"]["calls"] == calls_before
    assert rep["cache"]["hits"] == 4 and rep["completed"] == 8
    for fut in futs + futs2:
        frame = fut.result()
        assert frame.shape == (H, W, 3) and np.isfinite(frame).all()
        # the retirement buffer also holds recently served frames by id
        np.testing.assert_array_equal(server.frames[fut.request_id], frame)
    # identical pose -> identical cached frame
    np.testing.assert_array_equal(futs[0].result(), futs2[0].result())


def test_checkpoint_roundtrip_feeds_server(tmp_path):
    g = make_scene(n=200, scale=0.06)
    state = init_state(g)
    save_checkpoint(str(tmp_path), 3, state)
    params = load_params_from_ckpt(str(tmp_path))
    for a, b in zip(params, state.params):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    server = RenderServer(params, GSConfig(img_h=H, img_w=W, k_per_tile=64), n_levels=2, max_batch=2)
    fut = server.submit(make_cam(H, W))
    frame = fut.result()  # awaiting the future drives the pipeline itself
    assert frame.shape == (H, W, 3)
    assert np.isfinite(frame).all()
