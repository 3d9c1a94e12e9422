"""Dev smoke: distributed GS train step on whatever devices exist.

Run plain (1 device) or with XLA_FLAGS=--xla_force_host_platform_device_count=8.
Prints loss trajectory; with DUMP=1 writes loss curve to /tmp/losses.txt for
cross-device-count equality checks.
"""
import os
import sys

if "--devices" in sys.argv:
    i = sys.argv.index("--devices")
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={sys.argv[i+1]}"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.core import gaussians as G
from repro.core import projection as P
from repro.core.config import GSConfig
from repro.core.sharding import make_mesh
from repro.core.train import init_state, make_train_step, state_shardings, make_eval_render
from repro.volume import kingsnake_like, extract_isosurface_points, orbit_cameras, render_isosurface
from repro.volume.cameras import camera_slice
from repro.core.losses import psnr

devs = jax.devices()
nd = len(devs)
dshape = {1: (1, 1), 2: (2, 1), 4: (2, 2), 8: (4, 2)}[nd]
mesh = make_mesh(dshape)
print("mesh", mesh.shape)

H = W = 64
cfg = GSConfig(img_h=H, img_w=W, tile_h=16, tile_w=16, k_per_tile=256, batch_size=4, backend="ref")

vol = kingsnake_like(res=48)
pts, nrm, cols = extract_isosurface_points(vol, max_points=2000, seed=0)
print("extracted", pts.shape[0], "points")
cams = orbit_cameras(8, img_h=H, img_w=W, radius=3.0)
gts = jnp.stack([
    render_isosurface(jnp.asarray(vol.field), vol.isovalue, camera_slice(cams, i), img_h=H, img_w=W, n_steps=96)
    for i in range(8)
])
print("gt range", float(gts.min()), float(gts.max()))

# pad N to multiple of model axis * quantum
m = mesh.shape["model"]
n0 = pts.shape[0]
pad = (-n0) % (m * 128)
pts = np.concatenate([pts, np.full((pad, 3), 1e6, np.float32)])
cols = np.concatenate([cols, np.zeros((pad, 3), np.float32)])
g = G.init_from_points(jnp.asarray(pts), jnp.asarray(cols), init_scale=0.04)
g = g._replace(opacity_logit=g.opacity_logit.at[n0:].set(-20.0))

state = init_state(g)
sh = state_shardings(mesh)
state = jax.device_put(state, sh)
step_fn = make_train_step(mesh, cfg)

rng = np.random.default_rng(0)
losses = []
for it in range(20):
    sel = rng.choice(8, cfg.batch_size, replace=False)
    cb = camera_slice(cams, jnp.asarray(sel))
    gb = gts[jnp.asarray(sel)]
    state, metrics = step_fn(state, cb, gb)
    losses.append(float(metrics["loss"]))
    if it % 5 == 0:
        print(f"step {it} loss {losses[-1]:.5f}")

eval_fn = make_eval_render(mesh, cfg)
img, _ = eval_fn(state.params, camera_slice(cams, 0))
print("final loss", losses[-1], "eval psnr vs gt0", float(psnr(img, gts[0])))
if os.environ.get("DUMP"):
    np.savetxt(f"/tmp/losses_{nd}.txt", np.asarray(losses))
