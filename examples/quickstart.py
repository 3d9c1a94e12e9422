"""Quickstart: fit 3D Gaussians to a synthetic isosurface in ~2 minutes on CPU.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gaussians as G
from repro.core.config import GSConfig
from repro.core.losses import psnr
from repro.core.sharding import make_mesh
from repro.core.train import init_state, make_eval_render, make_train_step, state_shardings
from repro.data.views import ViewDataset
from repro.volume import extract_isosurface_points, kingsnake_like

# 1. scientific volume -> isosurface point cloud (the ParaView step, in-repo)
vol = kingsnake_like(res=40)
points, normals, colors = extract_isosurface_points(vol, max_points=2500)
print(f"extracted {points.shape[0]} isosurface points from '{vol.name}'")

# 2. ground-truth views: ray-marched isosurface renders on a structured orbit
data = ViewDataset(vol, n_views=12, img_h=64, img_w=64, cache_dir=None, n_steps_raymarch=96)

# 3. Gaussians seeded from the point cloud
pad = (-points.shape[0]) % 256
points = np.concatenate([points, np.full((pad, 3), 1e6, np.float32)])
colors = np.concatenate([colors, np.zeros((pad, 3), np.float32)])
g = G.init_from_points(jnp.asarray(points), jnp.asarray(colors), init_scale=0.05)

# 4. distributed-ready train step (here on a trivial 1x1 mesh — the same code
#    runs Gaussian-sharded + pixel-sharded on a real TPU mesh)
mesh = make_mesh((1, 1))
cfg = GSConfig(img_h=64, img_w=64, batch_size=4, k_per_tile=192)
state = jax.device_put(init_state(g), state_shardings(mesh))
step = make_train_step(mesh, cfg)

for i, (cams, gt) in enumerate(data.batches(cfg.batch_size, steps=60)):
    state, metrics = step(state, cams, gt)
    if i % 10 == 0:
        print(f"step {i:3d}  loss {float(metrics['loss']):.5f}")

# 5. evaluate
eval_render = make_eval_render(mesh, cfg)
cam, gt = data.view(0)
img, _ = eval_render(state.params, cam)
print(f"PSNR vs ground truth: {float(psnr(img, gt)):.2f} dB")
