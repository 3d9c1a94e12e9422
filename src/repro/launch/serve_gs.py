"""Gaussian render-serving driver: trained model -> multi-client service.

Loads a trained checkpoint (or initializes a fresh model from a synthetic
isosurface when none is given), builds the LOD pyramid, and drives the
batched render server with a synthetic client fleet, printing a JSON report.

  PYTHONPATH=src python -m repro.launch.serve_gs --smoke
  PYTHONPATH=src python -m repro.launch.serve_gs --ckpt experiments/ckpts/run0 \
      --res 128 --clients 8 --requests 16 --levels 3
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore_checkpoint
from repro.configs.gs_datasets import DATASETS
from repro.core import gaussians as G
from repro.core.config import GSConfig
from repro.core.train import init_state
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import Obs, trace_meta, validate_trace_jsonl, write_trace
from repro.serve_gs import RenderServer, make_clients, run_load
from repro.volume import datasets as VD
from repro.volume.isosurface import extract_isosurface_points


def load_params_from_ckpt(ckpt_dir: str) -> G.GaussianModel:
    step = latest_step(ckpt_dir)
    if step is None:
        raise SystemExit(f"no checkpoint under {ckpt_dir}")
    man = json.load(open(os.path.join(ckpt_dir, f"step_{step:08d}", "manifest.json")))
    n = man["leaves"]["params.means"]["shape"][0]
    like = init_state(G.init_from_points(jnp.zeros((n, 3)), jnp.zeros((n, 3))))
    state = restore_checkpoint(ckpt_dir, step, jax.tree_util.tree_map(np.asarray, like))
    return G.GaussianModel(*[np.asarray(x) for x in state.params])


def init_params_from_volume(dataset: str, *, volume_res: int, max_points: int) -> G.GaussianModel:
    ds = DATASETS[dataset]
    vol = getattr(VD, ds.volume)(res=volume_res)
    pts, _, cols = extract_isosurface_points(vol, max_points=max_points)
    return G.init_from_points(jnp.asarray(pts), jnp.asarray(cols), init_scale=0.05)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="reduced CPU config (32px, 32 requests)")
    ap.add_argument("--ckpt", default=None, help="checkpoint dir from repro.launch.train")
    ap.add_argument("--dataset", choices=list(DATASETS), default="kingsnake")
    ap.add_argument("--volume-res", type=int, default=48)
    ap.add_argument("--max-points", type=int, default=4000)
    ap.add_argument("--res", type=int, default=64)
    ap.add_argument("--levels", type=int, default=3)
    ap.add_argument("--keep-ratio", type=float, default=0.5)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8, help="requests per client")
    ap.add_argument("--orbit-views", type=int, default=12)
    ap.add_argument("--radius-spread", type=float, default=1.0)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument(
        "--pipeline-depth", type=int, default=2,
        help="in-flight micro-batches kept on-device (1 = synchronous dispatch)",
    )
    ap.add_argument("--cache", type=int, default=512,
                    help="cache capacity in frame-equivalents (byte budget = "
                    "N x frame bytes; 0 disables)")
    ap.add_argument("--cache-bytes", type=int, default=None,
                    help="cache byte budget directly (overrides --cache)")
    ap.add_argument("--frame-cache", action="store_true",
                    help="whole-frame cache baseline (disables the "
                    "tile-granular cache + partial strip renders)")
    ap.add_argument("--rate", type=float, default=0.0, help="request rounds per second (0 = flat out)")
    ap.add_argument("--report", default=None, help="write the JSON report here too")
    ap.add_argument("--trace-out", default=None, metavar="PATH.jsonl",
                    help="record request span traces; on exit write JSONL "
                         "here plus a Perfetto-viewable .chrome.json next to it")
    ap.add_argument("--trace-capacity", type=int, default=65536,
                    help="span ring size (oldest spans drop beyond this)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        args.res = min(args.res, 32)
        args.volume_res = min(args.volume_res, 32)
        args.max_points = min(args.max_points, 800)

    if args.ckpt:
        params = load_params_from_ckpt(args.ckpt)
    else:
        params = init_params_from_volume(
            args.dataset, volume_res=args.volume_res, max_points=args.max_points
        )
    cfg = GSConfig(img_h=args.res, img_w=args.res, k_per_tile=128 if args.smoke else 256)

    obs = Obs(trace=args.trace_out is not None, trace_capacity=args.trace_capacity)
    with RenderServer(
        params,
        cfg,
        obs=obs,
        n_levels=args.levels,
        keep_ratio=args.keep_ratio,
        max_batch=args.max_batch,
        cache_capacity=args.cache,
        cache_bytes=args.cache_bytes,
        tile_cache=not args.frame_cache,
        store_frames=False,
        pipeline_depth=args.pipeline_depth,
    ) as server:
        print(
            f"serve_gs: {args.dataset} n={params.n} levels={server.pyramid.live_counts} "
            f"res={args.res} clients={args.clients}x{args.requests}"
        )
        clients = make_clients(
            args.clients,
            n_views=args.orbit_views,
            img_h=args.res,
            img_w=args.res,
            radius_spread=args.radius_spread,
        )
        report = run_load(server, clients, requests_per_client=args.requests, rate_hz=args.rate)
    report["config"] = {
        "res": args.res,
        "clients": args.clients,
        "requests_per_client": args.requests,
        "levels": args.levels,
        "keep_ratio": args.keep_ratio,
        "max_batch": args.max_batch,
        "pipeline_depth": args.pipeline_depth,
    }
    out = json.dumps(report, indent=1)
    print(out)
    if args.report:
        os.makedirs(os.path.dirname(args.report) or ".", exist_ok=True)
        with open(args.report, "w") as f:
            f.write(out)
    if args.trace_out:
        spans = obs.trace.drain()
        meta = trace_meta(obs.trace, knobs={
            "max_batch": args.max_batch,
            "pipeline_depth": args.pipeline_depth,
        })
        jsonl_path, chrome_path = write_trace(args.trace_out, spans, meta=meta)
        with open(jsonl_path) as f:
            n = validate_trace_jsonl(f.read())
        print(f"trace: {n} spans -> {jsonl_path} + {chrome_path}")
        if n.dropped:
            print(f"WARNING: span ring overflowed — {n.dropped} spans LOST "
                  f"(capacity {obs.trace.capacity}); raise --trace-capacity "
                  f"before trusting replay fits", file=sys.stderr)
    assert report["completed"] == args.clients * args.requests, (
        f"pipelined path dropped requests: completed {report['completed']} of "
        f"{args.clients * args.requests}"
    )
    print(f"served {report['completed']} requests "
          f"({report['frames_per_s']} frames/s, cache hit rate {report['cache']['hit_rate']}, "
          f"depth {report['pipeline']['depth']}, deduped {report['pipeline']['deduped']})")


if __name__ == "__main__":
    main()
