"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control 3 --seconds 5

In one process on the cell's chips: runs of the cell (short windows, the
cell's own sizes and load) on ``--seeds`` seeds, which give the program's
readings of each number compared; then the control, the plain reference in
bfloat16 in the program's place, on the first ``--control`` of those seeds.
The benchmark's own runs never run the control. Prints one JSON line per
run and, last, each number's largest program reading (the lower end of its
limit) and smallest control reading (the upper end).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_017)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(R.CACHE / "jax")
    sys.path.insert(0, str(R.ROOT / "src"))
    devices = R.tpu_devices(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    program, control = {}, {}
    for seed in seeds:
        out = R.run_cell(cell, seed=seed, seconds=args.seconds, trace=False, devices=devices)
        print(json.dumps({"seed": seed, "side": "program", **out}), flush=True)
        for k, c in out["checks"].items():
            program.setdefault(k, []).append(c["value"])
    for seed in seeds[: args.control]:
        ctx = R.RunContext(cell, seed=seed, seconds=args.seconds, trace=False, devices=devices)
        checks = cell.kind.control(ctx)
        print(json.dumps({"seed": seed, "side": "control",
                          "checks": {c.name: c.value for c in checks}}), flush=True)
        for c in checks:
            control.setdefault(c.name, []).append(c.value)
    print(json.dumps({"lower": {k: max(v) for k, v in program.items()},
                      "upper": {k: min(v) for k, v in control.items()},
                      "program": program, "control": control}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
