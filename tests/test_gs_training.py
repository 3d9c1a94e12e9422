"""System tests: end-to-end GS training, densification, checkpointing."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import GSConfig
from repro.core.sharding import make_mesh
from repro.core.densify import densify_and_rebalance, reset_opacity, DEAD_LOGIT
from repro.core.train import init_state, make_train_step, make_eval_render, state_shardings
from repro.checkpoint import save_checkpoint, restore_checkpoint, latest_step
from repro.core import gaussians as G
from repro.core.losses import psnr
from repro.volume import kingsnake_like, miranda_like, extract_isosurface_points, orbit_cameras, render_isosurface
from repro.volume.cameras import camera_slice
from repro.data.views import ViewDataset


def _setup(n_points=600, H=32, views=4, res=32):
    mesh = make_mesh((1, 1))
    cfg = GSConfig(img_h=H, img_w=H, tile_h=16, tile_w=16, k_per_tile=128, batch_size=2,
                   densify_from=1, densify_interval=5, densify_until=100)
    vol = kingsnake_like(res=res)
    pts, _, cols = extract_isosurface_points(vol, max_points=n_points, seed=0)
    pad = (-pts.shape[0]) % 128
    pts = np.concatenate([pts, np.full((pad, 3), 1e6, np.float32)])
    cols = np.concatenate([cols, np.zeros((pad, 3), np.float32)])
    g = G.init_from_points(jnp.asarray(pts), jnp.asarray(cols), init_scale=0.06)
    data = ViewDataset(vol, n_views=views, img_h=H, img_w=H, cache_dir=None, n_steps_raymarch=48)
    return mesh, cfg, g, data


def test_training_reduces_loss_and_improves_psnr():
    mesh, cfg, g, data = _setup()
    state = jax.device_put(init_state(g), state_shardings(mesh))
    step = make_train_step(mesh, cfg)
    eval_fn = make_eval_render(mesh, cfg)
    cam0, gt0 = data.view(0)
    img0, _ = eval_fn(state.params, cam0)
    psnr_before = float(psnr(img0, gt0))
    losses = []
    for cams, gt in data.batches(cfg.batch_size, steps=15):
        state, m = step(state, cams, gt)
        losses.append(float(m["loss"]))
    img1, _ = eval_fn(state.params, cam0)
    psnr_after = float(psnr(img1, gt0))
    assert losses[-1] < losses[0]
    assert psnr_after > psnr_before
    assert np.isfinite(losses).all()


def test_densify_grows_and_prunes():
    mesh, cfg, g, data = _setup()
    state = jax.device_put(init_state(g), state_shardings(mesh))
    step = make_train_step(mesh, cfg)
    for cams, gt in data.batches(cfg.batch_size, steps=6):
        state, _ = step(state, cams, gt)
    n_before = state.params.n
    state2, report = densify_and_rebalance(state, cfg, n_shards=1)
    assert report.n_padded == state2.params.n
    assert report.n_padded % cfg.pad_quantum == 0
    assert report.n_after <= report.n_padded
    # training continues after re-jit with the new count
    step2 = make_train_step(mesh, cfg)
    cams, gt = next(iter(data.batches(cfg.batch_size, steps=1)))
    state3, m = step2(jax.device_put(state2, state_shardings(mesh)), cams, gt)
    assert np.isfinite(float(m["loss"]))


def test_opacity_reset_keeps_dead_dead():
    mesh, cfg, g, data = _setup()
    state = init_state(g)
    state = reset_opacity(state)
    logit = np.asarray(state.params.opacity_logit)
    live_max = 1.0 / (1.0 + np.exp(-logit[logit > DEAD_LOGIT + 1e-3]))
    assert np.all(live_max <= 0.0101)


def test_checkpoint_roundtrip(tmp_path):
    mesh, cfg, g, data = _setup(n_points=200)
    state = init_state(g)
    d = save_checkpoint(str(tmp_path), 7, state)
    assert latest_step(str(tmp_path)) == 7
    restored = restore_checkpoint(str(tmp_path), 7, jax.tree_util.tree_map(np.asarray, state))
    for a, b in zip(jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_miranda_volume_pipeline():
    vol = miranda_like(res=32)
    pts, nrm, cols = extract_isosurface_points(vol, max_points=500)
    assert pts.shape[0] > 0 and pts.shape == nrm.shape == cols.shape
    assert np.all(np.isfinite(pts)) and np.all(cols >= 0) and np.all(cols <= 1)
    cams = orbit_cameras(2, img_h=24, img_w=24)
    img = render_isosurface(jnp.asarray(vol.field), vol.isovalue, camera_slice(cams, 0),
                            img_h=24, img_w=24, n_steps=32)
    assert img.shape == (24, 24, 3) and bool(jnp.isfinite(img).all())
