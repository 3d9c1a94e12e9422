"""CPU checks of what the chip path relies on: where kernels run, where the
compile cache lives, what keys the ground-truth cache, and the f32 forms
of the projection math and depth sort."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gaussians as G
from repro.core import projection as P
from repro.kernels import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("backend,expect", [("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_follows_platform(monkeypatch, backend, expect):
    monkeypatch.setattr(platform.jax, "default_backend", lambda: backend)
    if expect is None:
        with pytest.raises(RuntimeError, match="gpu"):
            platform.interpret_mode()
    else:
        assert platform.interpret_mode() is expect


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_interpret_mode_for_operands_that_vary_over_mesh_axes(monkeypatch, backend):
    """Inside a shard_map that checks mesh-axis types, the CPU interprets a
    kernel with the TPU interpreter; the chip compiles it as ever."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(platform.jax, "default_backend", lambda: backend)
    got = platform.interpret_mode(varying=True)
    if backend == "tpu":
        assert got is False
    else:
        assert isinstance(got, pltpu.InterpretParams)


@pytest.mark.parametrize("env_dir", [None, "custom"])
def test_compile_cache_dir(tmp_path, env_dir):
    """The variable wins when set; otherwise a fixed directory in the
    checkout (run in a child: the cache directory is process-global)."""
    script = textwrap.dedent("""
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)
    """)
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                       env=env, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    returned, configured = r.stdout.split()
    want = str(tmp_path / env_dir) if env_dir else os.path.join(REPO, "experiments", "jax_cache")
    assert returned == configured == want


def test_gt_cache_keyed_on_render_settings(tmp_path):
    from repro.data.views import ViewDataset
    from repro.volume.datasets import miranda_like

    def data(vol, **kw):
        return ViewDataset(vol, n_views=2, img_h=8, img_w=8, cache_dir=str(tmp_path), **kw)

    vol = miranda_like(res=12)
    a = data(vol, n_steps_raymarch=8)
    data(vol, n_steps_raymarch=16)
    data(miranda_like(res=16), n_steps_raymarch=8)
    data(vol, n_steps_raymarch=8, radius=3.5)
    assert len(os.listdir(tmp_path)) == 4  # every setting gets its own file
    again = data(vol, n_steps_raymarch=8)  # same settings: read back
    np.testing.assert_array_equal(again.gt, a.gt)


def test_miranda_like_matches_full_grid_formulation():
    """The plane-summed modes equal the original per-voxel formula."""
    from repro.volume.datasets import _grid, miranda_like

    res, extent, modes, seed = 24, 1.0, 6, 1
    x, y, z = _grid(res, extent)
    rng = np.random.default_rng(seed)
    disp = np.zeros_like(x)
    for _ in range(modes):
        kx, ky = rng.uniform(2.0, 9.0, 2)
        ph1, ph2 = rng.uniform(0, 2 * np.pi, 2)
        amp = rng.uniform(0.04, 0.14)
        disp += amp * np.sin(kx * x + ph1) * np.cos(ky * y + ph2)
    disp += 0.08 * np.sin(4.0 * x) * np.sin(4.0 * y) * np.cos(3.0 * z)
    want = (z - disp).astype(np.float32)
    got = miranda_like(res=res, extent=extent, modes=modes, seed=seed).field
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want)


def test_depth_sort_matches_float_argsort():
    rng = np.random.default_rng(0)
    depth = rng.uniform(0.01, 10.0, 4096).astype(np.float32)
    depth[::7] = depth[3]          # ties keep their order (stable)
    depth[::11] = np.inf           # culled splats go last
    packed = np.zeros((depth.size, P.PACKED_DIM), np.float32)
    packed[:, P.DEPTH] = depth
    packed[:, 0] = np.arange(depth.size)
    _, order = P.sort_by_depth(jnp.asarray(packed))
    np.testing.assert_array_equal(np.asarray(order), np.argsort(depth, kind="stable"))


def test_small_matmul_matches_matmul():
    rng = np.random.default_rng(1)
    a = rng.normal(size=(64, 2, 3)).astype(np.float32)
    b = rng.normal(size=(64, 3, 3)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(G.small_matmul(a, b)), a @ b, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(G.small_matmul(a, b[0])), a @ b[0], rtol=1e-6, atol=1e-6)


def test_trainer_init_scale_ignores_padding():
    """GSTrainer pads with dead Gaussians far away; their positions must not
    inflate the initial scale of the real ones."""
    from repro.core.config import GSConfig
    from repro.core.sharding import make_mesh
    from repro.launch.train import GSTrainer

    rng = np.random.default_rng(2)
    pts = rng.uniform(-1, 1, (300, 3)).astype(np.float32)
    cols = rng.uniform(0, 1, (300, 3)).astype(np.float32)
    cfg = GSConfig(img_h=16, img_w=16, batch_size=1)
    tr = GSTrainer(cfg, make_mesh((1, 1)), pts, cols, verbose=False)
    assert tr.state.params.n == 512  # padded to the 256 quantum
    want = float(np.log(G.default_init_scale(pts)))
    np.testing.assert_allclose(np.asarray(tr.state.params.log_scales), want, rtol=1e-6)
    assert want < np.log(0.5)
