"""GS train-step dry-run at PAPER scale (Table I analog machinery).

Lowers the distributed Grendel-style GS train step with ShapeDtypeStructs at
the paper's true scales (Kingsnake 4M / Miranda 18.18M Gaussians; 512-2048px)
for 1/2/4 workers, and extracts per-worker FLOPs / HBM bytes / collective
bytes with the trip-aware HLO cost model. Wall-clock on this CPU container is
meaningless for a 4-A100 claim, so the Table I analog reports *modeled* step
time on the paper's hardware class and the derived speedups — method
documented in EXPERIMENTS.md §Paper-repro.

Run one point:  PYTHONPATH=src python benchmarks/gs_dryrun.py --points 4000000 --res 512 --workers 4
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--points", type=int, required=True)
    ap.add_argument("--res", type=int, required=True)
    ap.add_argument("--workers", type=int, required=True)       # model-axis workers
    ap.add_argument("--data-par", type=int, default=1)          # data-axis (views)
    ap.add_argument("--pods", type=int, default=1)              # pod axis (the paper's multi-node future work)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--k-per-tile", type=int, default=1024)
    ap.add_argument("--name", default="gs")
    ap.add_argument("--out", default="experiments/gs_dryrun")
    ap.add_argument("--gather-mode", default="projected", choices=["projected", "params3d"])
    args = ap.parse_args()

    n_dev = max(args.workers * args.data_par * args.pods, 1)
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import gaussians as G
    from repro.core import projection as P
    from repro.core.config import GSConfig
    from repro.core.sharding import make_mesh
    from repro.core.train import init_state, make_train_step
    from repro.launch import hlo_cost
    from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

    if args.pods > 1:
        mesh = make_mesh((args.pods, args.data_par, args.workers), ("pod", "data", "model"))
        data_axes = ("pod", "data")
    else:
        mesh = make_mesh((args.data_par, args.workers))
        data_axes = ("data",)
    quantum = args.workers * 256
    n = int(np.ceil(args.points / quantum) * quantum)
    cfg = GSConfig(
        img_h=args.res, img_w=args.res, batch_size=args.batch,
        k_per_tile=args.k_per_tile, backend="ref", gather_mode=args.gather_mode,
    )
    if args.gather_mode != "projected":
        args.name = f"{args.name}-{args.gather_mode}"

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt)

    params = G.GaussianModel(
        means=sds((n, 3)), log_scales=sds((n, 3)), quats=sds((n, 4)),
        opacity_logit=sds((n,)), sh=sds((n, 1, 3)),
    )
    state = jax.eval_shape(init_state, params)
    cams = P.Camera(
        viewmat=sds((args.batch, 4, 4)), fx=sds((args.batch,)), fy=sds((args.batch,)),
        cx=sds((args.batch,)), cy=sds((args.batch,)),
    )
    gt = sds((args.batch, args.res, args.res, 3))

    step = make_train_step(mesh, cfg, data_axes=data_axes)
    lowered = step.lower(state, cams, gt)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    txt = compiled.as_text()
    cost = hlo_cost.analyze(txt)

    # kernel-adjusted memory: the (K, tile_pixels) alpha-matrix intermediates
    # live in VMEM inside the Pallas rasterizer on TPU; the ref lowering
    # spills them to HBM. Subtract that class, add the kernel's true slab I/O.
    hc = hlo_cost.HloCost(txt)
    tile_px = cfg.tile_h * cfg.tile_w
    alpha_class = hlo_cost.sum_sig_suffix_bytes(hc, (args.k_per_tile, tile_px))
    tiles_local = (args.res // cfg.tile_h) * (args.res // cfg.tile_w) // max(args.workers, 1)
    slab_io = args.batch * tiles_local * args.k_per_tile * 11 * 4.0 * 3  # fwd read + bwd read/write
    kernel_mem_bytes = max(cost["bytes"] - alpha_class, 0.0) + slab_io

    result = {
        "name": args.name, "points": args.points, "res": args.res, "workers": args.workers,
        "pods": args.pods, "data_par": args.data_par,
        "batch": args.batch,
        "per_worker": {
            "flops": cost["flops"],
            "hbm_bytes": cost["bytes"],
            "collective_bytes": cost["coll_total_moved_bytes"],
            "collectives": cost["coll"],
            "arg_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes,
        },
        "roofline_s": {
            "compute": cost["flops"] / PEAK_FLOPS_BF16,
            "memory": cost["bytes"] / HBM_BW,
            "memory_kernel_adjusted": kernel_mem_bytes / HBM_BW,
            "collective": cost["coll_total_moved_bytes"] / ICI_BW,
        },
        "alpha_class_bytes": alpha_class,
        "top_bytes": cost.get("top_bytes", []),
        "top_collectives": cost.get("top_collectives", []),
    }
    os.makedirs(args.out, exist_ok=True)
    tag = f"{args.workers}w" + (f"_{args.pods}pod{args.data_par}dp" if args.pods > 1 or args.data_par > 1 else "")
    path = os.path.join(args.out, f"{args.name}_{args.points}_{args.res}_{tag}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result["roofline_s"]), "peak_gb=%.2f" % (result["per_worker"]["peak_bytes"] / 1e9))


if __name__ == "__main__":
    main()
