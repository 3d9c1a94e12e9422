"""repro.obs — observability for the serving stack.

One ``Obs`` bundle travels down the stack (gateway → sessions → engine →
cache → encoders): it owns the shared :class:`MetricsRegistry` (atomic
snapshot, one ``reset()`` for every tier) and the span recorder —
:data:`NULL_RECORDER` (falsy; tracing disabled, zero hot-path cost) unless
tracing was requested. Components that are constructed standalone (a bare
``RenderServer`` in a test) default to their own private ``Obs`` so the
instrumentation never needs a None check.
"""
from __future__ import annotations

from repro.obs.clock import now, since
from repro.obs.export import (
    TraceCheck,
    spans_to_chrome,
    spans_to_jsonl,
    trace_meta,
    validate_trace_jsonl,
    write_trace,
)
from repro.obs.metrics import (
    DEFAULT_MS_BUCKETS,
    DEFAULT_SIZE_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.slo import SLOTracker, parse_slo_spec
from repro.obs.trace import (
    NO_SPAN,
    NULL_RECORDER,
    STAGES,
    TRAIN_STAGES,
    NullRecorder,
    Span,
    TraceRecorder,
    new_request_id,
    unwatch_compiles,
)
from repro.obs import devmem

__all__ = [
    "Obs",
    "now",
    "since",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "DEFAULT_MS_BUCKETS",
    "DEFAULT_SIZE_BUCKETS",
    "TraceRecorder",
    "NullRecorder",
    "NULL_RECORDER",
    "NO_SPAN",
    "Span",
    "STAGES",
    "TRAIN_STAGES",
    "devmem",
    "new_request_id",
    "spans_to_jsonl",
    "spans_to_chrome",
    "write_trace",
    "validate_trace_jsonl",
    "trace_meta",
    "TraceCheck",
    "SLOTracker",
    "parse_slo_spec",
]


class Obs:
    """The observability bundle one serving stack shares.

    ``obs.metrics`` — the registry every tier registers its counters on.
    ``obs.trace`` — a :class:`TraceRecorder` when tracing is on, else the
    falsy :data:`NULL_RECORDER`; hot paths gate on its truthiness.
    """

    __slots__ = ("metrics", "trace")

    def __init__(self, *, trace: bool = False, trace_capacity: int = 65536,
                 metrics: MetricsRegistry | None = None):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.trace = TraceRecorder(trace_capacity) if trace else NULL_RECORDER

    @property
    def tracing(self) -> bool:
        return bool(self.trace)

    def enable_trace(self, capacity: int = 65536) -> TraceRecorder:
        """Switch tracing on (idempotent); returns the live recorder."""
        if not self.trace:
            self.trace = TraceRecorder(capacity)
        return self.trace

    def disable_trace(self) -> None:
        unwatch_compiles(self.trace)
        self.trace = NULL_RECORDER
