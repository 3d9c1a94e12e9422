"""The benchmark's cells at a size a CPU test can hold.

A tiny cell keeps every file and setting of its cell in ``BENCHMARK.json``
(driver, limits, per-layer metrics) and shrinks only the scene: a 32^3
volume, 8 orbit views and 64 px frames, or 256 px where a test needs the
two-level binning. The Pallas kernels run in interpret mode on the CPU.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import run as R  # noqa: E402
from harness import load_module  # noqa: E402

SMALL = {"volume_res": 32, "n_views": 8, "gt_res": 32, "img_res": 64, "k_per_tile": 32,
         "pad_quantum": 256, "gt_raymarch_steps": 32}


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny_cell(name: str, *, config: dict | None = None, traffic: dict | None = None) -> R.Cell:
    """The cell ``name`` of BENCHMARK.json with its scene shrunk."""
    s = spec()
    w = {x["name"]: x for x in s["workloads"]}[name]
    file = {c["name"]: c["file"] for c in s["configs"]}[w["config"]]
    cfg = json.loads((ROOT / file).read_text())
    cfg.update(SMALL, name=f"tiny-{w['config']}")
    cfg.update(config or {})
    mix = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    mix.update(traffic or {})
    limits = json.loads((ROOT / "bench" / "limits" / f"{name}.json").read_text())
    kind = load_module(ROOT / "bench" / "traffic" / f"{mix['kind']}.py")
    e2e = [m for m in s["end_to_end"] if R.applies(m, name)]
    layer = [m for m in s["per_layer"] if R.applies(m, name, {m["name"] for m in e2e})]
    return R.Cell(name, w["chips"], cfg, mix, limits, kind, e2e, layer, ROOT)


def run_tiny(cell: R.Cell, tmp_path: Path, *, seed: int = 2**31 + 11, seconds: float = 2.0,
             trace: bool = False) -> dict:
    """One run of ``cell`` on the CPU devices, skipping the look for a chip."""
    import jax

    return R.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                      devices=jax.devices()[: cell.chips], cache=tmp_path)
