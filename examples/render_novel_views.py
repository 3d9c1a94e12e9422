"""Post-hoc visualization: restore a trained checkpoint and render a novel
orbit (the 'real-time post hoc visualization' use case from the paper).
Writes PPM images (no imaging deps needed).

  PYTHONPATH=src python examples/render_novel_views.py --ckpt experiments/ckpts/miranda_demo
"""
import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore_checkpoint
from repro.core import gaussians as G
from repro.core.config import GSConfig
from repro.core.sharding import make_mesh
from repro.core.train import init_state, make_eval_render, state_shardings
from repro.utils.image import write_ppm
from repro.volume.cameras import camera_slice, orbit_cameras


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", required=True)
    ap.add_argument("--res", type=int, default=64)
    ap.add_argument("--views", type=int, default=8)
    ap.add_argument("--out", default="experiments/renders")
    args = ap.parse_args()

    step = latest_step(args.ckpt)
    if step is None:
        raise SystemExit(f"no checkpoint under {args.ckpt} — run the training example first")
    # peek manifest for the Gaussian count
    import json
    man = json.load(open(os.path.join(args.ckpt, f"step_{step:08d}", "manifest.json")))
    n = man["leaves"]["params.means"]["shape"][0]
    like = init_state(G.init_from_points(jnp.zeros((n, 3)), jnp.zeros((n, 3))))
    state = restore_checkpoint(args.ckpt, step, jax.tree_util.tree_map(np.asarray, like))

    mesh = make_mesh((1, 1))
    cfg = GSConfig(img_h=args.res, img_w=args.res, k_per_tile=256)
    render = make_eval_render(mesh, cfg)
    params = G.GaussianModel(*[jnp.asarray(x) for x in state.params])
    cams = orbit_cameras(args.views, img_h=args.res, img_w=args.res, radius=2.5, elev_cycles=1.0)
    os.makedirs(args.out, exist_ok=True)
    for i in range(args.views):
        img, _ = render(params, camera_slice(cams, i))
        path = os.path.join(args.out, f"novel_{i:03d}.ppm")
        write_ppm(path, img)
        print("wrote", path)


if __name__ == "__main__":
    main()
