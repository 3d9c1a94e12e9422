"""Every cell of BENCHMARK.json resolves to its files, and the file keeps
the benchmark's contract of names, units, bounds and layers."""
from __future__ import annotations

import re
import subprocess
import sys

import pytest
from tiny import ROOT, spec

import run as R

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in spec()["workloads"]]


def test_top_level_keys_and_size():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
                      "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= s["run_seconds"] <= 51 and isinstance(s["run_seconds"], int)
    assert s["command"] == ["python3", "bench/run.py"]
    assert all((ROOT / p).is_dir() for p in s["paths"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = R.load_cell(name)
    assert cell.chips in (1, 4)
    assert hasattr(cell.kind, "run")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in names
    ref = ROOT / "bench" / "configs" / f"{cell.config['reference']}.py"
    assert ref.is_file()
    assert set(cell.limits) == {c for c in cell.limits}  # limits are plain numbers
    assert all(isinstance(v, (int, float)) and v > 0 for v in cell.limits.values())


def test_names_units_and_bounds():
    s = spec()
    metrics = s["end_to_end"] + s["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in s["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in s["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert set(m["workloads"]) <= set(CELLS)
        layers.setdefault(m["layer"], []).append(m["name"])
    for w in s["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in s["workloads"])
    assert four <= max(1, len(s["workloads"]) // 2)


def test_each_config_file_is_one_configuration():
    s = spec()
    files = [c["file"] for c in s["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in s["workloads"]}
    for c in s["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])


def test_run_exits_nonzero_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(ROOT / "bench" / ".cache")},
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    import shutil

    for p in ["BENCHMARK.json", *spec()["paths"]]:
        src = ROOT / p
        if src.is_dir():
            shutil.copytree(src, tmp_path / p, ignore=shutil.ignore_patterns(".cache", "__pycache__"))
        else:
            shutil.copy(src, tmp_path / p)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path)},
    )
    assert out.returncode != 0 and out.stdout.strip() == ""
