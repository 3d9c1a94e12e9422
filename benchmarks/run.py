"""Benchmark harness entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract, then
the paper-table analogs (Table I scaling, Tables II/III quality) and the
§Roofline summary when dry-run artifacts exist.

  PYTHONPATH=src python -m benchmarks.run            # quick sections only
  PYTHONPATH=src python -m benchmarks.run --full     # + heavy subprocess tables
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="also run subprocess-heavy tables")
    args = ap.parse_args()

    print("# --- kernel micro-benchmarks (name,us_per_call,derived) ---")
    from benchmarks import raster_kernel

    for name, us, derived in raster_kernel.rows() + raster_kernel.flash_rows():
        print(f"{name},{us:.1f},{derived}")

    print("\n# --- GS train step (single device, reduced scale) ---")
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.config import GSConfig
    from repro.core.sharding import make_mesh
    from repro.core.train import init_state, make_train_step, state_shardings
    from repro.core import gaussians as G
    from repro.volume import kingsnake_like, extract_isosurface_points, orbit_cameras, render_isosurface
    from repro.volume.cameras import camera_slice

    mesh = make_mesh((1, 1))
    cfg = GSConfig(img_h=64, img_w=64, k_per_tile=192, batch_size=2, backend="ref")
    vol = kingsnake_like(res=32)
    pts, _, cols = extract_isosurface_points(vol, max_points=1500, seed=0)
    pad = (-pts.shape[0]) % 256
    pts = np.concatenate([pts, np.full((pad, 3), 1e6, np.float32)])
    cols = np.concatenate([cols, np.zeros((pad, 3), np.float32)])
    g = G.init_from_points(jnp.asarray(pts), jnp.asarray(cols), init_scale=0.05)
    state = jax.device_put(init_state(g), state_shardings(mesh))
    step = make_train_step(mesh, cfg)
    cams = orbit_cameras(2, img_h=64, img_w=64)
    gt = jnp.stack([
        render_isosurface(jnp.asarray(vol.field), vol.isovalue, camera_slice(cams, i), img_h=64, img_w=64, n_steps=64)
        for i in range(2)
    ])
    state, m = step(state, cams, gt)  # compile
    t0 = time.perf_counter()
    for _ in range(3):
        state, m = step(state, cams, gt)
    jax.block_until_ready(state.params.means)
    us = (time.perf_counter() - t0) / 3 * 1e6
    print(f"gs_train_step_1536g_64px,{us:.0f},loss={float(m['loss']):.5f}")

    print("\n# --- Table I analog: scaling (modeled step time at paper scale) ---")
    from benchmarks import table1_scaling

    if args.full:
        table1_scaling.run_all()
    table1_scaling.table()

    print("\n# --- Tables II/III analog: quality vs workers ---")
    if args.full:
        from benchmarks import table23_quality

        table23_quality.table()
    else:
        import os, json
        rows = []
        for nd in (1, 4, 8):
            p = f"experiments/quality/quality_{nd}w.json"
            if os.path.exists(p):
                rows.append(json.load(open(p)))
        if rows:
            print("workers,psnr,ssim,lpips_proxy,final_loss")
            for d in rows:
                print(f"{d['workers']},{d['psnr']:.2f},{d['ssim']:.4f},{d['lpips_proxy']:.4f},{d['loss']:.5f}")
        else:
            print("(cached quality results not found; run with --full)")

    print("\n# --- Roofline summary (single-pod dry-run) ---")
    from benchmarks import roofline

    try:
        roofline.table()
    except Exception as e:  # dry-run artifacts may not exist yet
        print(f"(roofline artifacts missing: {e})")


if __name__ == "__main__":
    main()
