"""Find the highest rate a serving cell sustains: one sweep of offered rates.

    python3 bench/sweep.py --workload <cell> --rates 2,4,6,8 --seconds 20

One server, set up once, then one open-loop window per rate (the cell's own
poses and arrival rule). For each rate it prints the p95 latency from due
time, the frames completed per second of the window, the requests still
outstanding when the last one was due, and the mean latency of the last
quarter of requests over that of the first: a rate is sustained when the
backlog does not grow over the window (that ratio stays near 1). The cell's
mix file then states 4/5 of the highest sustained rate as a number.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=3_100_000_033)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(R.CACHE / "jax")
    sys.path.insert(0, str(R.ROOT / "src"))
    devices = R.tpu_devices(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ol = cell.kind
    ctx = R.RunContext(cell, seed=args.seed, seconds=args.seconds, trace=False, devices=devices)
    server, *_ = ol.start_server(ctx)
    res = cell.config["img_res"]
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        due = ol.arrivals(rate, args.seconds)
        cams = ol.poses(cell.traffic, len(due), args.seed + i, res)
        t0 = time.perf_counter()
        done, _, lag = ol.drive(server, cams, due, late_s=cell.traffic["late_s"], keep=set(), t0=t0)
        lat = np.where(np.isnan(done), np.inf, done - (t0 + due))
        q = max(len(due) // 4, 1)
        in_window = np.sum(done <= t0 + args.seconds)
        outstanding = int(np.sum(~(done <= t0 + due[-1])))
        print(json.dumps({
            "rate": rate, "requests": len(due), "p95_ms": ol.percentile_ms(lat),
            "completed_per_s_in_window": float(in_window / args.seconds),
            "outstanding_at_last_due": outstanding,
            "late_growth": float(np.mean(lat[-q:]) / np.mean(lat[:q])),
            "mean_batch": server.report()["render"]["mean_batch"],
            "submit_lag_max_ms": float(lag.max() * 1e3),
        }), flush=True)
        server.reset_metrics()
    server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
