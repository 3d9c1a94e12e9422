"""Reduce a profiler trace (``.xplane.pb``) to device busy time, time per
operation and idle gaps.

Busy time of a chip is the union of the intervals in which one of its XLA
operations ran; the idle share is 1 - busy / window. Each device operation
is named by its HLO instruction text, which the per-layer readers match
(``ops.py``). An idle gap (a stretch of
the window with nothing on the chip) is labelled by what the host was doing
then: the innermost host span that covers the gap's midpoint, from the
profiler's own host events and from the program's spans mapped onto the
profiler's clock.

    python3 bench/trace_reduce.py <trace dir>   # print a summary of a trace
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MIN_GAP_NS = 10_000  # idle stretches shorter than this are launch jitter, not gaps


@dataclasses.dataclass
class Op:
    chip: int
    name: str  # the HLO instruction text
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Reduced:
    ops: list            # device operations inside the window, all chips
    chips: int
    window_ns: tuple     # (start, end) on the trace clock
    busy_ns: list        # per chip
    gaps: list           # (label, ns) idle gaps of chip 0, longest first

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        return sum(self.busy_ns) / len(self.busy_ns) * 1e-9

    def op_seconds(self, match) -> float:
        """Device seconds of the operations ``match`` selects, mean over chips."""
        return sum(o.dur_ns for o in self.ops if match(o)) / self.chips * 1e-9

    def breakdown(self, n: int = 10) -> dict:
        per = collections.Counter()
        for o in self.ops:
            per[label(o)] += o.dur_ns / self.chips
        gaps = collections.Counter()
        for name, ns in self.gaps:
            gaps[name] += ns
        return {"device_ops": [[k, v * 1e-9] for k, v in per.most_common(n)],
                "idle_gaps": [[k, v * 1e-9] for k, v in gaps.most_common(n)]}


def label(op: Op) -> str:
    """An operation's HLO name, result shape and opcode (its text, cut short)."""
    return op.name[:120]


def find_xplane(trace_dir) -> str:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_xplane(path: str):
    """(device ops, host spans) of a trace file. Device ops come from each
    device plane's "XLA Ops" line; host spans from every host thread."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, host = [], []
    for plane in pd.planes:
        chip = DEVICE_PLANE.match(plane.name)
        if chip:
            chip = int(chip.group(1))
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    ops.append(Op(chip, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    # a span shorter than a gap labels none: the (millions of)
                    # tiny ones go, and the span around them labels the gap
                    if ev.duration_ns >= MIN_GAP_NS:
                        host.append(Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return ops, host


def union_ns(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def idle_gaps(intervals, window) -> list[tuple[float, float]]:
    gaps, cursor = [], window[0]
    for s, e in sorted(intervals):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if window[1] > cursor:
        gaps.append((cursor, window[1]))
    return [(s, e) for s, e in gaps if e - s >= MIN_GAP_NS]


def gap_labels(gaps, host: list) -> list[str]:
    """For each gap, the shortest host span covering its midpoint."""
    import numpy as np

    start = np.array([s.start_ns for s in host], np.float64)
    end = np.array([s.end_ns for s in host], np.float64)
    out = []
    for g in gaps:
        mid = (g[0] + g[1]) / 2
        cover = np.nonzero((start <= mid) & (end >= mid))[0]
        out.append(host[cover[np.argmin(end[cover] - start[cover])]].name if cover.size
                   else "(no host span)")
    return out


def reduce(ops: list, host: list, window=None, window_name: str = "bench.window") -> Reduced:
    """Clip to the window (the host span ``window_name`` unless given) and reduce."""
    if window is None:
        spans = [s for s in host if s.name == window_name]
        if spans:
            window = (spans[0].start_ns, spans[0].end_ns)
        elif ops:
            window = (min(o.start_ns for o in ops), max(o.end_ns for o in ops))
        else:
            window = (0.0, 0.0)
    inside = []
    for o in ops:
        s, e = max(o.start_ns, window[0]), min(o.end_ns, window[1])
        if e > s:
            inside.append(dataclasses.replace(o, start_ns=s, end_ns=e))
    chips = sorted({o.chip for o in ops}) or [0]
    busy = [union_ns([(o.start_ns, o.end_ns) for o in inside if o.chip == c]) for c in chips]
    first = [(o.start_ns, o.end_ns) for o in inside if o.chip == chips[0]]
    labelled = [s for s in host if s.name != window_name]
    spans = idle_gaps(first, window)
    gaps = [(name, g[1] - g[0]) for name, g in zip(gap_labels(spans, labelled), spans)]
    gaps.sort(key=lambda g: -g[1])
    return Reduced(inside, len(chips), window, busy, gaps)


def reduce_dir(trace_dir, *, host_spans=(), host_offset=None) -> Reduced:
    """Reduce the newest trace under ``trace_dir``. ``host_spans`` are the
    program's own (name, t0, t1) spans on the host's perf_counter clock, and
    ``host_offset`` the perf_counter time at which the window's host span
    ("bench.window") began: together they place the spans on the trace clock."""
    ops, host = read_xplane(find_xplane(trace_dir))
    win = [s for s in host if s.name == "bench.window"]
    if win and host_offset is not None:
        shift = win[0].start_ns - host_offset * 1e9
        host += [Span(n, t0 * 1e9 + shift, t1 * 1e9 + shift) for n, t0, t1 in host_spans]
    return reduce(ops, host)


def main(argv=None) -> int:
    path = find_xplane((argv or sys.argv[1:])[0])
    ops, host = read_xplane(path)
    r = reduce(ops, host)
    print(json.dumps({"file": path, "chips": r.chips, "window_s": r.window_s,
                      "busy_s": r.busy_s, "breakdown": r.breakdown(25)}, indent=1))
    seen = collections.Counter()
    for o in sorted(r.ops, key=lambda o: -o.dur_ns):
        key = label(o)
        if seen[key] < 1 and len(seen) < 40:
            print(json.dumps({"name": o.name, "dur_us": o.dur_ns / 1e3})[:1500])
        seen[key] += 1
    names = collections.Counter(s.name for s in host)
    print("host spans:", json.dumps(names.most_common(40)))
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name, [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines][:12])
    return 0


if __name__ == "__main__":
    sys.exit(main())
