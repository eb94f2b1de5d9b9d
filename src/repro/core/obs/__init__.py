"""Observability layer: flight recorder, metrics registry, decision audit.

Public surface of ``repro.core.obs`` — the single source of timing
truth for the exchange/adapt pipeline (see ``docs/observability.md``):

* :class:`TraceRecorder` / :func:`span` / :func:`activate` — bounded
  span ring with ``block_until_ready``-fenced timing, and a
  ``jax.profiler`` annotation per span while a capture runs
  (:mod:`~repro.core.obs.recorder`);
* :class:`MetricsRegistry` — counters/gauges/histograms
  (:mod:`~repro.core.obs.metrics`);
* :class:`DecisionAudit` / :func:`record_decision` — every
  selector/gating choice with rejected-alternative costs and evidence
  grades (:mod:`~repro.core.obs.audit`);
* :func:`write_recording` / :func:`provenance_meta` — Perfetto-loadable
  export and the shared bench provenance block
  (:mod:`~repro.core.obs.export`).

Everything is off by default: with no active recorder and no profiler
capture each instrumentation point costs two checks.
"""
from repro.core.obs.audit import (
    EVIDENCE_GRADES,
    GLOBAL_AUDIT,
    DecisionAudit,
    DecisionRecord,
    record_decision,
)
from repro.core.obs.export import (
    PROVENANCE_KEYS,
    SCHEMA_VERSION,
    provenance_meta,
    recording_dict,
    trace_events,
    write_recording,
)
from repro.core.obs.metrics import (
    MetricsRegistry,
    metric_key,
)
from repro.core.obs.recorder import (
    Span,
    SpanHandle,
    TraceRecorder,
    activate,
    block_on,
    current_metrics,
    current_recorder,
    span,
    trace_span,
)

__all__ = [
    "DecisionAudit",
    "DecisionRecord",
    "EVIDENCE_GRADES",
    "GLOBAL_AUDIT",
    "MetricsRegistry",
    "PROVENANCE_KEYS",
    "SCHEMA_VERSION",
    "Span",
    "SpanHandle",
    "TraceRecorder",
    "activate",
    "block_on",
    "current_metrics",
    "current_recorder",
    "metric_key",
    "provenance_meta",
    "record_decision",
    "recording_dict",
    "span",
    "trace_span",
    "trace_events",
    "write_recording",
]
