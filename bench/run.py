"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. It names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix names the driver of its kind
(``bench/traffic/<kind>.py``), and the cell's correctness limits sit in
``bench/limits/<cell>.json``. With ``--trace 1`` each per-layer metric is
read by its own reader, ``bench/metrics/<metric>.py``. Nothing here lists
cells, mixes or metrics: a new cell is new files and a new entry.

The run needs the chips the cell asks for: without a TPU, or with fewer
chips, it exits nonzero and prints no result. Set-up (scene, weights,
compiles, warm-up steps) is timed as ``setup_s`` from process start; the
measured window follows; then the timed path's output is compared with the
plain reference, and the numbers compared are printed beside their limits
as the last lines of standard error and under ``checks`` in the result.
The last line of standard output is the result, one JSON object.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

from harness import BenchError, Outcome, load_module, read_json  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"  # scene caches, compile cache, traces (gitignored)


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with the files it resolves to."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    kind: object          # the traffic kind's driver module
    end_to_end: list      # the end-to-end metric entries this cell reports
    per_layer: list       # the per-layer metric entries this cell reports
    root: Path


def applies(metric: dict, workload: str, e2e_names: set[str] | None = None) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    if e2e_names is None:  # an end-to-end metric without a list: every cell
        return True
    return metric["moves"] in e2e_names


def load_cell(name: str, *, root: Path = ROOT, bench_file: Path | None = None) -> Cell:
    spec = read_json(bench_file or root / "BENCHMARK.json")
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json (have {sorted(work)})")
    w = work[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench = root / "bench"
    config = read_json(root / cfg_entry["file"])
    traffic = read_json(bench / "traffic" / f"{w['traffic']}.json")
    limits = read_json(bench / "limits" / f"{name}.json")
    kind = load_module(bench / "traffic" / f"{traffic['kind']}.py")
    e2e = [m for m in spec["end_to_end"] if applies(m, name)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if applies(m, name, names)]
    return Cell(name, int(w["chips"]), config, traffic, limits, kind, e2e, layer, root)


def tpu_devices(chips: int):
    """The chips of this run; raises BenchError without a TPU or enough chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise BenchError(f"needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


class RunContext:
    """What a traffic driver gets: the cell, the seed, the window length,
    the devices, and the set-up clock and trace window it must call."""

    def __init__(self, cell: Cell, *, seed: int, seconds: float, trace: bool, devices,
                 cache: Path = CACHE):
        self.cell = cell
        self.cache = cache  # scene caches and traces
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.t_setup_done: float | None = None
        self.window_t0: float | None = None
        self.window_t1: float | None = None
        self.trace_dir = cache / "traces" / cell.name
        self.memory_peak_bytes: int | None = None

    def setup_done(self) -> None:
        """Mark the end of set-up (the first timed step or request is next)."""
        self.t_setup_done = time.perf_counter()

    @property
    def setup_s(self) -> float:
        return self.t_setup_done - T_PROCESS

    @contextlib.contextmanager
    def window(self):
        """The measured window; traced by the profiler with ``--trace 1``.
        The body ends in ``block_until_ready`` on what it produced."""
        import jax

        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir.mkdir(parents=True)
            jax.profiler.start_trace(str(self.trace_dir))
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                self.window_t0 = time.perf_counter()
                yield self
                self.window_t1 = time.perf_counter()
        finally:
            if self.trace:
                jax.profiler.stop_trace()

    @property
    def window_s(self) -> float:
        return self.window_t1 - self.window_t0

    def read_memory(self) -> None:
        """Peak bytes on the fullest chip; read after the window, before the
        reference runs (a process's peak never falls again)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices]
        self.memory_peak_bytes = int(max(peaks))


def layer_metrics(cell: Cell, ctx: RunContext, outcome: Outcome, reduced) -> dict:
    """Each per-layer metric of the cell from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    import counts
    from peaks import peak_for

    run = {
        "workload": cell.name, "config": cell.config, "traffic": cell.traffic,
        "chips": cell.chips, "window_s": ctx.window_s, "trace": reduced,
        "peak": peak_for(ctx.devices[0].device_kind), "counts": counts, **outcome.layer,
    }
    out = {}
    for m in cell.per_layer:
        reader = load_module(cell.root / "bench" / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, devices,
             cache: Path = CACHE) -> dict:
    """Set up, measure, compare; returns the result object (not printed)."""
    ctx = RunContext(cell, seed=seed, seconds=seconds, trace=trace, devices=devices, cache=cache)
    outcome: Outcome = cell.kind.run(ctx)
    gc.collect()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": ctx.memory_peak_bytes,
    }
    result = {
        "correct": all(c.ok for c in outcome.checks) and outcome.failed == 0,
        "attempted": outcome.attempted,
        "failed": outcome.failed,
    }
    if trace:
        import trace_reduce

        reduced = trace_reduce.reduce_dir(ctx.trace_dir,
                                          host_spans=outcome.layer.get("host_spans", []),
                                          host_offset=ctx.window_t0)
        result["metrics"] = layer_metrics(cell, ctx, outcome, reduced)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["device"] = device
        result["breakdown"] = reduced.breakdown()
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                             for k, v in outcome.e2e.items() if k in units}
        result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in outcome.checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise BenchError("the program (src/repro) is not in this checkout")
        cell = load_cell(args.workload)
        # the compile cache lives inside the checkout at a fixed path, and the
        # program's own cache switch takes it from this variable
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
        sys.path.insert(0, str(ROOT / "src"))
        sys.path.insert(0, str(BENCH))
        devices = tpu_devices(cell.chips)
        import jax

        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        print(f"device: {devices[0].platform} {devices[0].device_kind} x{len(devices)}",
              file=sys.stderr)
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), devices=devices)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}", file=sys.stderr)
    print(json.dumps(finite(result), allow_nan=False), flush=True)
    return 0


def finite(obj):
    """The result with every non-finite number (a gap that could not be
    measured) as the largest float, which fails any limit."""
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [finite(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return -sys.float_info.max if obj < 0 else sys.float_info.max
    return obj


if __name__ == "__main__":
    sys.exit(main())
