"""Observability on two clocks that never mix.

greentrace, virtual-time structured tracing with per-joule attribution:
see :mod:`repro.obs.tracer` for the event model and the reconciliation
invariant, :mod:`repro.obs.export` for canonical JSON + Perfetto export,
:mod:`repro.obs.report` for the "where did the joules go" analyzer, and
:mod:`repro.obs.reduce` for the shared telemetry merge helper.

:mod:`repro.obs.wall` records host-clock spans of the measured training
path, on the profiler's clock; nothing of it enters greentrace's events.
"""
from repro.obs.export import (
    build_payload,
    dumps_canonical,
    load_trace,
    run_meta,
    to_chrome,
    trace_digest,
    write_chrome,
    write_trace,
)
from repro.obs.reduce import merge_counters
from repro.obs.tracer import (
    NULL_TRACER,
    NullTracer,
    ReconciliationError,
    Tracer,
    component_totals,
    ledger_totals,
    reconcile,
)
from repro.obs.wall import NULL_SPANS, SpanRecord, SpanRecorder

__all__ = [
    "NULL_SPANS",
    "NULL_TRACER",
    "NullTracer",
    "ReconciliationError",
    "SpanRecord",
    "SpanRecorder",
    "Tracer",
    "build_payload",
    "component_totals",
    "dumps_canonical",
    "ledger_totals",
    "load_trace",
    "merge_counters",
    "reconcile",
    "run_meta",
    "to_chrome",
    "trace_digest",
    "write_chrome",
    "write_trace",
]
