"""The program's own spans of a run, as the per-layer readers take them.

A run hands its spans over as ``run["host_spans"]``: (name, t0, t1) on the
host's ``perf_counter`` clock. The train driver clips them to the window
already. The serve driver hands over every span drained after the window,
which holds the traced run's post-window compiles too; its first request is
due at the window's start, so the window is [the earliest ``submit`` span's
start, that + ``run["window_s"]``].
"""
from __future__ import annotations


def in_window(run) -> list:
    """The run's spans that start inside its window."""
    spans = run["host_spans"]
    if "frames" not in run:  # a train run: clipped by its driver
        return list(spans)
    starts = [t0 for name, t0, _ in spans if name == "submit"]
    if not starts:
        return []
    lo = min(starts)
    hi = lo + run["window_s"]
    return [s for s in spans if lo <= s[1] <= hi]


def durations_s(run, name: str) -> list:
    return [t1 - t0 for n, t0, t1 in in_window(run) if n == name]
