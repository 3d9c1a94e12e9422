"""Write small profiler traces (``.xplane.pb``) for tests: one device plane
whose "XLA Ops" line holds the given operations, each with its ``tf_op``
stat, in the protobuf wire format ``bench/scopes.py`` reads."""
from __future__ import annotations


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(data)) + data


def xspace(ops, *, chip: int = 0) -> bytes:
    """``ops``: (HLO text, op_name or None, start_ns, end_ns) per operation."""
    stat_meta = _field(5, _field(1, 1) + _field(2, _field(1, 1) + _field(2, "tf_op")))
    ev_meta, events = b"", b""
    for i, (text, op_name, s, e) in enumerate(ops, start=1):
        stats = _field(5, _field(1, 1) + _field(5, op_name)) if op_name else b""
        ev_meta += _field(4, _field(1, i) + _field(2, _field(1, i) + _field(2, text) + stats))
        events += _field(4, _field(1, i) + _field(2, int(s * 1000)) + _field(3, int((e - s) * 1000)))
    line = _field(3, _field(2, "XLA Ops") + _field(3, 0) + events)
    plane = _field(2, f"/device:TPU:{chip}") + line + ev_meta + stat_meta
    return _field(1, plane)
