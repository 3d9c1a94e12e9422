"""Distribution-correctness: the Grendel-style sharded step must produce the
same optimization trajectory as single-device (run in a subprocess with 8
forced host devices; conftest keeps the main process at 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SCRIPT = textwrap.dedent(
    """
    import os, sys, json
    if len(sys.argv) > 1 and sys.argv[1] != "1":
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={sys.argv[1]}"
    gather_mode = sys.argv[2] if len(sys.argv) > 2 else "projected"
    import jax, numpy as np, jax.numpy as jnp
    from repro.core import gaussians as G
    from repro.core.config import GSConfig
    from repro.core.sharding import make_mesh
    from repro.core.train import init_state, make_train_step, state_shardings
    from repro.volume import kingsnake_like, extract_isosurface_points, orbit_cameras, render_isosurface
    from repro.volume.cameras import camera_slice

    nd = len(jax.devices())
    shape = {1: (1, 1), 8: (4, 2)}[nd]
    mesh = make_mesh(shape)
    H = W = 32
    cfg = GSConfig(img_h=H, img_w=W, tile_h=16, tile_w=16, k_per_tile=128, batch_size=4,
                   backend="ref", gather_mode=gather_mode)
    vol = kingsnake_like(res=32)
    pts, nrm, cols = extract_isosurface_points(vol, max_points=800, seed=0)
    cams = orbit_cameras(4, img_h=H, img_w=W)
    gts = jnp.stack([
        render_isosurface(jnp.asarray(vol.field), vol.isovalue, camera_slice(cams, i), img_h=H, img_w=W, n_steps=48)
        for i in range(4)
    ])
    m = mesh.shape["model"]
    pad = (-pts.shape[0]) % (m * 128)
    pts = np.concatenate([pts, np.full((pad, 3), 1e6, np.float32)])
    cols = np.concatenate([cols, np.zeros((pad, 3), np.float32)])
    g = G.init_from_points(jnp.asarray(pts), jnp.asarray(cols), init_scale=0.06)
    g = g._replace(opacity_logit=g.opacity_logit.at[pts.shape[0]-pad:].set(-20.0))
    state = jax.device_put(init_state(g), state_shardings(mesh))
    step = make_train_step(mesh, cfg)
    losses = []
    for i in range(6):
        state, metrics = step(state, cams, gts)
        losses.append(float(metrics["loss"]))
    print(json.dumps(losses))
    """
)


def _run(n_devices: int, gather_mode: str = "projected"):
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(n_devices), gather_mode],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_equals_single_device():
    l1 = _run(1)
    l8 = _run(8)
    np.testing.assert_allclose(l8, l1, atol=5e-6)
    assert l1[-1] < l1[0]  # it actually optimizes


@pytest.mark.slow
def test_params3d_gather_equals_projected():
    """The beyond-paper 3D-state gather schedule is trajectory-identical to
    the paper-faithful projected-splat schedule under real sharding."""
    l_proj = _run(8, "projected")
    l_3d = _run(8, "params3d")
    np.testing.assert_allclose(l_3d, l_proj, atol=5e-6)


# ================================================= first-step gradients
GRAD_SCRIPT = textwrap.dedent(
    """
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np, jax.numpy as jnp
    from repro.core import gaussians as G
    from repro.core.config import GSConfig
    from repro.core.sharding import make_mesh
    from repro.core.train import init_state, make_train_step, state_shardings
    from repro.volume import kingsnake_like, extract_isosurface_points, orbit_cameras, render_isosurface
    from repro.volume.cameras import camera_slice

    H = W = 64  # four strips of one 16-px tile row on a (1, 4) mesh
    B1 = 0.9    # Adam's first-moment decay: m = (1 - b1) g after one step from m = 0
    vol = kingsnake_like(res=32)
    pts, nrm, cols = extract_isosurface_points(vol, max_points=800, seed=0)
    cams = orbit_cameras(4, img_h=H, img_w=W)
    gts = jnp.stack([
        render_isosurface(jnp.asarray(vol.field), vol.isovalue, camera_slice(cams, i), img_h=H, img_w=W, n_steps=48)
        for i in range(4)
    ])
    n0 = pts.shape[0]
    pad = (-n0) % 512
    pts = np.concatenate([pts, np.full((pad, 3), 1e6, np.float32)])
    cols = np.concatenate([cols, np.zeros((pad, 3), np.float32)])
    g = G.init_from_points(jnp.asarray(pts), jnp.asarray(cols), init_scale=0.06)
    g = g._replace(opacity_logit=g.opacity_logit.at[n0:].set(-20.0))
    out = {}
    for mode in ("projected", "params3d"):
        for shape in ((1, 1), (1, 4), (2, 2), (4, 1)):
            mesh = make_mesh(shape, devices=jax.devices()[: shape[0] * shape[1]])
            cfg = GSConfig(img_h=H, img_w=W, tile_h=16, tile_w=16, k_per_tile=64, batch_size=4,
                           backend="ref", gather_mode=mode)
            state = jax.device_put(init_state(g), state_shardings(mesh))
            new, _ = make_train_step(mesh, cfg)(state, cams, gts)
            got = {k: (np.asarray(v) / (1 - B1)).tolist() for k, v in new.adam.m._asdict().items()}
            got.update(grad2d_accum=np.asarray(new.grad2d_accum).tolist(),
                       vis_count=np.asarray(new.vis_count).tolist(),
                       max_radii=np.asarray(new.max_radii).tolist())
            out[f"{mode} {shape[0]}x{shape[1]}"] = got
    print(json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def first_step():
    """Each mesh's first-step gradient (Adam's first moment over 1 - b1) and
    densification statistics, from one state and one batch of four views."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", GRAD_SCRIPT],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


# The meshes sum the same float32 terms in another order (the loss's psum,
# the gather's reduce-scatter, the data-axis psum): about 2e-7 of a leaf's
# norm. A gradient scaled by the number of shards reads 3 or more.
GRAD_RTOL = 1e-5


@pytest.mark.parametrize("mode", ["projected", "params3d"])
@pytest.mark.parametrize("mesh", ["1x4", "2x2", "4x1"])
def test_first_step_gradient_is_the_one_chip_gradient(first_step, mode, mesh):
    """Every mesh hands Adam the gradient of the one global loss, and
    accumulates the same densification statistics, leaf by leaf."""
    want, got = first_step[f"{mode} 1x1"], first_step[f"{mode} {mesh}"]
    assert set(got) == set(want)
    for leaf in want:
        w, g = np.asarray(want[leaf]), np.asarray(got[leaf])
        gap = np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30)
        assert gap <= GRAD_RTOL, (leaf, gap)


# ====================================================== shard-balance gauges
BALANCE_SCRIPT = textwrap.dedent(
    """
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np, jax.numpy as jnp
    from repro.core.train import init_state, record_shard_balance, shard_balance, state_shardings
    from repro.core.sharding import make_mesh
    from repro.insitu import fixed_capacity_init
    from repro.obs import MetricsRegistry

    mesh = make_mesh((1, 4))
    n = 512
    rng = np.random.default_rng(0)
    pts = (rng.normal(size=(n, 3)) * 0.3).astype(np.float32)
    cols = rng.uniform(size=(n, 3)).astype(np.float32)
    g = fixed_capacity_init(pts, cols, n)  # n0 == capacity: every slot alive
    state = jax.device_put(init_state(g), state_shardings(mesh))
    b0 = shard_balance(state)
    m = MetricsRegistry()
    record_shard_balance(m, b0)
    # kill every slot of shard 0 (model-axis rows are contiguous blocks)
    dead = state.params.opacity_logit.at[: n // 4].set(-20.0)
    state = state._replace(params=state.params._replace(opacity_logit=dead))
    state = jax.device_put(state, state_shardings(mesh))
    b1 = shard_balance(state)
    print(json.dumps({"b0": b0, "b1": b1, "snap": m.snapshot()}))
    """
)


@pytest.mark.slow
def test_shard_balance_gauges_on_forced_mesh():
    """On a forced 4-device model mesh: per-shard alive gauges sum to the
    model size, a fresh exactly-at-capacity uniform init is perfectly
    balanced (imbalance == 1.0), and masking one shard's opacities skews it
    (> 1.0) — the signal a dynamic rebalancing pass will trigger on."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", BALANCE_SCRIPT],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    b0, b1, snap = out["b0"], out["b1"], out["snap"]

    assert b0["n_shards"] == 4
    assert sum(b0["capacity"]) == 512
    assert sum(b0["alive"]) == 512 == b0["alive_total"]
    assert b0["alive"] == [128] * 4  # uniform: every slot of every shard alive
    assert b0["imbalance"] == pytest.approx(1.0)

    # the registry mirrors the balance dict: per-shard gauges sum to the
    # model size and the imbalance gauge is what the dict computed
    gauges = [snap[f"train.shard_alive.s{i}"] for i in range(4)]
    assert sum(gauges) == 512 == snap["train.alive_total"]
    assert snap["train.shard_imbalance"] == pytest.approx(1.0)
    assert sum(snap[f"train.shard_capacity.s{i}"] for i in range(4)) == 512

    # one shard masked dead: total drops by that shard, max/mean rises
    assert b1["alive"][0] == 0 and sum(b1["alive"]) == 384
    assert b1["imbalance"] == pytest.approx(128 / (384 / 4))
    assert b1["imbalance"] > 1.0


# ==================================== traced-vs-untraced training guarantees
def _insitu_pair_vol():
    from repro.volume.timevary import synthetic_stream

    return next(iter(synthetic_stream("miranda", 1, res=24, t1=0.0)))


def _tiny_insitu(obs):
    from repro.core.config import GSConfig
    from repro.core.sharding import make_mesh
    from repro.insitu import InsituTrainer

    mesh = make_mesh((1, 1))
    cfg = GSConfig(
        img_h=24, img_w=24, tile_h=8, tile_w=8, k_per_tile=32, batch_size=2,
        max_steps=64, densify_from=10**9, opacity_reset_interval=10**9,
    )
    return InsituTrainer(
        cfg, mesh, cold_steps=4, warm_steps=2, n_views=4, max_points=200,
        n_steps_raymarch=16, seed=0, obs=obs,
    )


def test_training_trace_zero_alloc_and_bitwise_step():
    """The serving guarantees, restated for the train loop: with the
    NullRecorder, a full train step allocates NOTHING in the trace layer;
    and tracing a run (spans + block_until_ready fences) leaves the
    optimization bitwise identical to the untraced run."""
    import tracemalloc

    import jax

    from repro.obs import TRAIN_STAGES, Obs

    off = _tiny_insitu(Obs())
    on = _tiny_insitu(Obs(trace=True))
    vol = _insitu_pair_vol()
    rep_off = off.start(vol)
    rep_on = on.start(vol)
    assert rep_off.steps == rep_on.steps

    # bitwise: block_until_ready fences bound the device span but must not
    # perturb a single bit of the trajectory
    p_off = jax.tree_util.tree_map(np.asarray, off.state)
    p_on = jax.tree_util.tree_map(np.asarray, on.state)
    for a, b in zip(jax.tree_util.tree_leaves(p_off), jax.tree_util.tree_leaves(p_on)):
        np.testing.assert_array_equal(a, b)

    # the traced run produced training spans, all from the vocabulary
    spans = on.obs.trace.drain()
    names = {s.name for s in spans}
    assert {"extract", "batch", "dispatch", "device", "fit", "eval"} <= names
    assert names <= set(TRAIN_STAGES)

    # zero-alloc: more warm steps with tracing off touch the trace layer not
    # at all (registry observes are exempt — the guarantee is about spans)
    data = off._dataset(vol)
    off._fit(data, 1, psnr0=0.0)  # warm any lazy paths before measuring
    tracemalloc.start()
    s1 = tracemalloc.take_snapshot()
    off._fit(data, 2, psnr0=0.0)
    s2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    filt = [tracemalloc.Filter(True, "*obs/trace*")]
    diff = s2.filter_traces(filt).compare_to(s1.filter_traces(filt), "lineno")
    assert sum(abs(d.size_diff) for d in diff) == 0, diff
