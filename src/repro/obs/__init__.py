"""Unified telemetry: metrics registry, lifecycle tracing, step log.

See ``obs/README.md`` for the metric catalog, trace event schema, the
step log's spans and scopes, and the launcher knobs (``--metrics-dir``,
``--trace``, ``--profile``)."""
from repro.obs.metrics import (
    LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.profile import (
    SCOPES,
    STEP_LOG,
    StepLog,
    scope_map,
    span,
    trace_ctx,
)
from repro.obs.trace import EVENTS, TraceRecorder

__all__ = [
    "LATENCY_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "SCOPES",
    "STEP_LOG",
    "StepLog",
    "scope_map",
    "span",
    "trace_ctx",
    "EVENTS",
    "TraceRecorder",
]
