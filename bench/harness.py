"""Pieces the benchmark's runner and its traffic drivers share."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path


class BenchError(RuntimeError):
    """A run that cannot produce a result: it exits nonzero, printing none."""


def load_module(path: Path):
    if not path.is_file():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit (value <= limit)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit  # NaN fails


@dataclasses.dataclass
class Outcome:
    """What a traffic driver hands back: end-to-end values, counts, the
    comparison, and the raw material the per-layer readers use."""

    e2e: dict
    attempted: int
    failed: int
    checks: list
    layer: dict  # reader inputs: spans, counters, step or frame counts


def memory_analysis(jitted, *args) -> dict:
    """Bytes the compiled program ``jitted(*args)`` holds on each device
    (arguments, outputs, temporaries), as its compiler reports them."""
    m = jitted.lower(*args).compile().memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
            "alias_size_in_bytes", "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}
