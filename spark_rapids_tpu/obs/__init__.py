"""obs: the production observability plane (docs/observability.md).

Connected layers over dispatch, sync, memory, shuffle, retry and
chaos:

* **Concurrent per-query tracing** (:mod:`.tracer`, near-zero-cost when
  ``spark.rapids.tpu.trace.enabled`` is off): each query gets its own
  ring-buffered, thread-aware span/event tracer routed by thread-local
  scopes — N sessions trace N queries simultaneously — with three exports
  from the same record (:mod:`.export`): Chrome trace-event JSON
  (perfetto / ``chrome://tracing``), ``session.explain("metrics")``
  (:mod:`.explain`; works with tracing off, from the session snapshots),
  and the diagnostics bundle (``session.last_query_profile()``) whose
  per-operator dispatch+sync counts reconcile against its OWN query's
  ``calls_by_kind``/SyncLedger deltas.
* **Phases** (:func:`phase` / :func:`phase_add`, :mod:`.tracer`): the
  boundary spans of the served path (session → scheduler → plan → scan →
  compiled stage → result). One call site feeds the always-on per-query
  phase table (``session.last_query_phases()``,
  ``metrics.recent_queries()``), the ring span of a traced query, and a
  ``TraceAnnotation("srt.<name>")`` on the profiler's own clock.
* **Always-on metrics registry** (:mod:`.metrics`): process-wide
  counters, gauges and log2-bucket histograms (query latency p50/p95/p99,
  rows/s, HBM high-water, spill bytes, cache hit rates, retry/chaos
  counts) — ``session.metrics_snapshot()`` / ``python -m
  tools.obs_report``.
* **Crash flight recorder** (:mod:`.flight`): a small always-on ring of
  notable events that dumps a postmortem bundle (last-K events, registry
  snapshot, HBM/semaphore/spill state, active queries) under
  ``spark.rapids.tpu.obs.postmortemDir`` on a fatal device error, an
  exhausted retry, or an HBM OOM.
* **Mesh efficiency profiler** (:mod:`.mesh_profile`): per-collective-
  exchange wall attribution (staging/launch/wait/compact), per-chip skew
  and straggler reporting, "why not collective" fallback reasons, and
  the collective watchdog — the distributed layer over the three above
  (``last_query_profile()['mesh']``, ``parallel/sharded.py``'s
  ``efficiency_attribution``, ``mesh.watchdog_fired``).

Instrumentation sites in execs//shuffle//memory//parallel/ must emit
through this package's :func:`span` / :func:`event` / metric helpers
(tracelint rule TL012) and must never put a blocking device→host sync in
an emission argument.
"""

from .explain import render_explain_metrics
from .export import build_bundle, chrome_trace, span_tree, write_artifacts
from .tracer import (PhaseLaps, QueryTracer, SpanRef, begin_query,
                     current_span, end_query, event, inherit, is_active,
                     phase, phase_add, span, thread_traced)
from . import flight, mesh_profile, metrics

__all__ = [
    "PhaseLaps", "QueryTracer", "SpanRef", "begin_query", "build_bundle",
    "chrome_trace", "current_span", "end_query", "event", "flight",
    "inherit", "is_active", "mesh_profile", "metrics", "phase", "phase_add",
    "render_explain_metrics", "span", "span_tree", "thread_traced",
    "write_artifacts",
]
