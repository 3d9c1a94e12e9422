"""Device time per named scope of the program, from a profiler trace.

The program puts one ``jax.named_scope`` on each stage of its train step and
render programs (``project``, ``depth_sort``, ``binning``, ``tile_gather``,
``raster``, ``loss``, ``grad_reduce``, ``adam``). The scope lands in each HLO
operation's ``op_name`` metadata; a backward operation reads
``transpose(jvp(<scope>))``. An operation belongs to the innermost known
scope of its path, or to none.

Where the scope is read: a TPU v5e trace carries an operation's ``op_name``
as the ``tf_op`` stat of its event metadata (``SCOPE_STAT``), but not for
every operation. The compiler builds the scatter-adds of the gathers'
backward passes, the sorts it inserts before them, and the ``while`` loops
as operations whose own metadata is empty. The trace also holds each
program's optimized HLO (the ``/host:metadata`` plane), so an operation
without ``tf_op`` is resolved there (``hlo_scopes``): by its own ``op_name``;
else, for a fusion or a loop, by the root of the computation it calls,
followed through the roots of what that calls (a scatter-add fusion's root
is the scatter, whose reducer the stage's transpose rule made in its
scope); else by the one scope of all the instructions it calls; else by the
one scope of its users, then of its operands (a sort the compiler put in
front of a scatter is used by that scatter alone).

Per scope the device time is the union of its operations' intervals, so an
operation nested in a ``while`` (the binning scan's sorts) is not counted
twice; it is clipped to the run's window and averaged over the chips.

``jax.profiler.ProfileData`` gives an event's own stats only, so the
``.xplane.pb`` is read here from the protobuf wire format (the schemas are
``tsl/profiler/protobuf/xplane.proto`` and ``xla/service/hlo.proto``); host
planes are skipped.

    python3 bench/scopes.py <trace dir>   # per-scope split of a trace
"""
from __future__ import annotations

import collections
import dataclasses
import json
import re
import struct
import sys
from pathlib import Path

import trace_reduce as T

SCOPES = ("project", "depth_sort", "binning", "tile_gather", "raster", "loss", "grad_reduce",
          "adam")
SCOPE_STAT = "tf_op"  # the stat that holds an operation's op_name on a TPU v5e
TRACES = Path(__file__).resolve().parent / ".cache" / "traces"  # the runner's trace_dir root
_WRAPPER = re.compile(r"^(?:[A-Za-z_]+\()+|\)+$")


@dataclasses.dataclass
class ScopedOp:
    chip: int
    name: str         # the HLO instruction text
    path: str         # its op_name: the scope path
    start_ns: float
    end_ns: float


def scope_of(path: str) -> str | None:
    """The innermost known scope of an op_name path, or None.

    ``jit(step)/transpose(jvp(vmap(tile_gather)))/scatter-add`` -> tile_gather."""
    found = None
    for part in path.split("/"):
        name = _WRAPPER.sub("", part)
        if name in SCOPES:
            found = name
    return found


# ------------------------------------------------------------ wire format
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf, start: int = 0, end: int | None = None):
    """(field number, value) of one message's fields. A length-delimited
    value is its (start, end) in ``buf``; a fixed64 value is its 8 bytes."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wire == 1:
            v = bytes(buf[i:i + 8])
            i += 8
        elif wire == 5:
            v = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield num, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, span, stat_names: dict) -> tuple[str, object]:
    """One XStat: (its name, its value)."""
    mid, value = 0, None
    for num, v in fields(buf, *span):
        if num == 1:
            mid = v
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num in (3, 7):
            value = v
        elif num == 4:
            value = _signed(v)
        elif num in (5, 6):
            value = _text(buf, v)
        if num == 7:  # a reference: the value is another stat metadata's name
            value = stat_names.get(v, v)
    return stat_names.get(mid, str(mid)), value


def read_device_planes(path: str):
    """(chip, HLO text, {stat: value}, start_ns, end_ns) for each event of
    the "XLA Ops" line of each device plane. Stats are the event metadata's
    and the event's own."""
    buf = memoryview(Path(path).read_bytes())
    out = []
    for num, pspan in fields(buf):
        if num != 1:
            continue
        name, lines, ev_meta, st_meta = None, [], {}, {}
        for pn, v in fields(buf, *pspan):
            if pn == 2:
                name = _text(buf, v)
                if not T.DEVICE_PLANE.match(name):
                    break
            elif pn == 3:
                lines.append(v)
            elif pn in (4, 5):
                key = val = None
                for en, ev in fields(buf, *v):
                    if en == 1:
                        key = ev
                    elif en == 2:
                        val = ev
                (ev_meta if pn == 4 else st_meta)[key] = val
        if name is None or not T.DEVICE_PLANE.match(name):
            continue
        chip = int(T.DEVICE_PLANE.match(name).group(1))
        stat_names = {}
        for k, span in st_meta.items():
            for sn, sv in fields(buf, *span):
                if sn == 2:
                    stat_names[k] = _text(buf, sv)
        meta_cache = {}

        def metadata(mid):
            got = meta_cache.get(mid)
            if got is None:
                text, stats = "", {}
                span = ev_meta.get(mid)
                if span is not None:
                    for mn, mv in fields(buf, *span):
                        if mn == 2:
                            text = _text(buf, mv)
                        elif mn == 5:
                            k, val = _stat(buf, mv, stat_names)
                            stats[k] = val
                got = meta_cache[mid] = (text, stats)
            return got

        for lspan in lines:
            lname, ts_ns, events = None, 0, []
            for ln, lv in fields(buf, *lspan):
                if ln == 2:
                    lname = _text(buf, lv)
                elif ln == 3:
                    ts_ns = _signed(lv)
                elif ln == 4:
                    events.append(lv)
            if lname != "XLA Ops":
                continue
            for espan in events:
                mid = off_ps = dur_ps = 0
                own = {}
                for en, ev in fields(buf, *espan):
                    if en == 1:
                        mid = ev
                    elif en == 2:
                        off_ps = ev
                    elif en == 3:
                        dur_ps = ev
                    elif en == 4:
                        k, val = _stat(buf, ev, stat_names)
                        own[k] = val
                text, stats = metadata(mid)
                start = ts_ns + off_ps / 1e3
                out.append((chip, text, {**stats, **own}, start, start + dur_ps / 1e3))
    return out


# ------------------------------------------------------------ HLO modules
_PLANE_NAME, _PLANE_EVENT_META = 2, 4
_INST_NAME, _INST_OPCODE, _INST_METADATA, _INST_ID = 1, 2, 7, 35
_INST_OPERANDS, _INST_CALLED = 36, 38
_METADATA_OP_NAME = 2
_HLO_NAME = re.compile(r"^%(\S+) = ")


def _ints(buf, v) -> list:
    """A repeated int64 field's values (packed or not)."""
    if not isinstance(v, tuple):
        return [v]
    out, i = [], v[0]
    while i < v[1]:
        x, i = _varint(buf, i)
        out.append(x)
    return out


def hlo_modules(buf) -> dict:
    """{program id: (start, end) of its HloProto} from the metadata plane."""
    out = {}
    for num, pspan in fields(buf):
        if num != 1:
            continue
        plane = list(fields(buf, *pspan))
        if not any(n == _PLANE_NAME and _text(buf, v) == "/host:metadata" for n, v in plane):
            continue
        for n, v in plane:
            if n != _PLANE_EVENT_META:
                continue
            entry = dict(fields(buf, *v))
            meta = list(fields(buf, *entry[2]))
            pid = next((mv for mn, mv in meta if mn == 1), None)
            for mn, mv in meta:
                if mn == 5:  # the stat holding the serialized HloProto
                    stat = dict(fields(buf, *mv))
                    if 6 in stat:
                        out[pid] = stat[6]
    return out


def hlo_scopes(buf, span) -> dict:
    """{instruction name: scope} of one HloProto; see the module docstring."""
    (module,) = [v for n, v in fields(buf, *span) if n == 1]
    comps, roots, insts = {}, {}, {}
    for n, cv in fields(buf, *module):
        if n != 3:
            continue
        cid, root, members = None, None, []
        for cn, v in fields(buf, *cv):
            if cn == 5:
                cid = v
            elif cn == 6:
                root = v
            elif cn == 2:
                d = {"ops": [], "called": [], "op_name": ""}
                for f, fv in fields(buf, *v):
                    if f == _INST_NAME:
                        d["name"] = _text(buf, fv)
                    elif f == _INST_OPCODE:
                        d["opcode"] = _text(buf, fv)
                    elif f == _INST_METADATA:
                        md = dict(fields(buf, *fv))
                        if _METADATA_OP_NAME in md:
                            d["op_name"] = _text(buf, md[_METADATA_OP_NAME])
                    elif f == _INST_ID:
                        d["id"] = fv
                    elif f == _INST_OPERANDS:
                        d["ops"] += _ints(buf, fv)
                    elif f == _INST_CALLED:
                        d["called"] += _ints(buf, fv)
                members.append(d)
        comps[cid], roots[cid] = members, root
        for d in members:
            insts[d["id"]] = d

    def rooted(cid, depth=0):
        """The scope of a computation's root, through the roots of what it
        calls (a fusion's scatter, the scatter's reducer)."""
        root = insts.get(roots.get(cid))
        if root is None or depth > 8:
            return None
        s = scope_of(root["op_name"])
        for c in root["called"]:
            s = s or rooted(c, depth + 1)
        return s

    deep_memo = {}

    def deep(cid) -> set:
        """The scopes of every instruction a computation holds or calls."""
        if cid not in deep_memo:
            deep_memo[cid] = set()
            got = set()
            for d in comps.get(cid, ()):
                s = scope_of(d["op_name"])
                if s:
                    got.add(s)
                for c in d["called"]:
                    got |= deep(c)
            deep_memo[cid] = got
        return deep_memo[cid]

    scope = {}
    for i, d in insts.items():
        s = scope_of(d["op_name"])
        for c in d["called"]:
            s = s or rooted(c)
        if s is None and d["called"]:
            called = set().union(*(deep(c) for c in d["called"]))
            s = called.pop() if len(called) == 1 else None
        scope[i] = s
    users = collections.defaultdict(list)
    for i, d in insts.items():
        for o in d["ops"]:
            users[o].append(i)
    for neighbours in (lambda i: users[i], lambda i: insts[i]["ops"]):
        changed = True
        while changed:
            changed = False
            for i in insts:
                if scope[i] is not None:
                    continue
                near = {scope[j] for j in neighbours(i) if j in scope} - {None}
                if len(near) == 1:
                    scope[i] = near.pop()
                    changed = True
    return {d["name"]: scope[i] for i, d in insts.items() if "name" in d}


def scoped_ops(path: str) -> list[ScopedOp]:
    """The device operations with their scope paths: the ``tf_op`` stat, or
    for an operation without one, its scope resolved in its program's HLO."""
    buf = memoryview(Path(path).read_bytes())
    modules = hlo_modules(buf)
    resolved = {}
    out = []
    for chip, text, stats, s, e in read_device_planes(path):
        op_path = str(stats.get(SCOPE_STAT, ""))
        if scope_of(op_path) is None:
            pid = _program_id(stats.get("program_id"))
            m = _HLO_NAME.match(text)
            if pid in modules and m:
                if pid not in resolved:
                    resolved[pid] = hlo_scopes(buf, modules[pid])
                op_path = resolved[pid].get(m.group(1)) or op_path
        out.append(ScopedOp(chip, text, op_path, s, e))
    return out


def _program_id(v):
    """A program id as the metadata plane keys it (unsigned 64 bits)."""
    try:
        return int(v) % (1 << 64)
    except (TypeError, ValueError):
        return None


# --------------------------------------------------------------- reduction
@dataclasses.dataclass
class Split:
    seconds: dict       # scope -> device seconds, mean over chips
    unscoped_s: float   # busy seconds in no scope
    busy_s: float
    chips: int

    @property
    def unscoped_share(self) -> float:
        return self.unscoped_s / self.busy_s if self.busy_s > 0 else 0.0


def split(ops: list[ScopedOp], window) -> Split:
    """Per-scope union of the ops' intervals inside ``window`` (ns)."""
    chips = sorted({o.chip for o in ops}) or [0]
    per = collections.defaultdict(lambda: collections.defaultdict(list))
    for o in ops:
        s, e = max(o.start_ns, window[0]), min(o.end_ns, window[1])
        if e > s:
            per[o.chip][scope_of(o.path)].append((s, e))
    seconds = {k: 0.0 for k in SCOPES}
    busy = unscoped = 0.0
    for c in chips:
        by = per[c]
        everything = [iv for ivs in by.values() for iv in ivs]
        busy += T.union_ns(everything)
        scoped = [iv for k, ivs in by.items() if k is not None for iv in ivs]
        unscoped += T.union_ns(everything) - T.union_ns(scoped)
        for k in SCOPES:
            seconds[k] += T.union_ns(by.get(k, ()))
    n = len(chips)
    return Split({k: v / n * 1e-9 for k, v in seconds.items()}, unscoped / n * 1e-9,
                 busy / n * 1e-9, n)


_cache: dict = {}


def for_run(run) -> Split | None:
    """The split of the run's own trace (its cell's newest), clipped to the
    run's window; None without a trace or a scoped operation in it."""
    try:
        path = T.find_xplane(TRACES / run["workload"])
    except FileNotFoundError:
        return None
    window = tuple(run["trace"].window_ns)
    key = (path, Path(path).stat().st_mtime_ns, window)
    if key not in _cache:
        ops = scoped_ops(path)
        _cache.clear()
        _cache[key] = split(ops, window) if any(scope_of(o.path) for o in ops) else None
    return _cache[key]


def scope_ms(run, scope: str, per: str) -> float | None:
    """Device ms of ``scope`` per ``run[per]`` (steps or frames), or None."""
    got = for_run(run)
    if got is None or got.seconds[scope] <= 0 or not run[per]:
        return None
    return got.seconds[scope] / run[per] * 1e3


def main(argv=None) -> int:
    trace_dir = (argv or sys.argv[1:])[0]
    path = T.find_xplane(trace_dir)
    ops, host = T.read_xplane(path)
    window = T.reduce(ops, host).window_ns
    sp = split(scoped_ops(path), window)
    print(json.dumps({
        "file": path, "chips": sp.chips, "window_s": (window[1] - window[0]) * 1e-9,
        "busy_s": sp.busy_s, "scope_s": sp.seconds, "unscoped_s": sp.unscoped_s,
        "scoped_share_of_busy": 1.0 - sp.unscoped_share,
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
