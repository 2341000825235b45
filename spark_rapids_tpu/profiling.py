"""Profiling, tracing, and task-level metrics.

Reference (SURVEY.md §5 tracing/profiling):
  (a) NVTX ranges around every operator (NvtxWithMetrics.scala) → here
      `trace_scope` emits jax.profiler TraceAnnotations, visible in
      xprof/TensorBoard timelines;
  (b) the built-in sampled profiler (profiler.scala:37, JNI CUPTI Profiler,
      `spark.rapids.profile.*` configs) → `TpuProfiler` drives
      jax.profiler.start_trace/stop_trace writing to
      `spark.rapids.profile.pathPrefix`;
  (c) per-task accumulators GpuTaskMetrics (semaphore-wait, retry count/time,
      spill-to-host/disk bytes, GpuTaskMetrics.scala:82-101) →
      `TaskMetricsRegistry`;
  (d) per-operator SQLMetrics at ESSENTIAL/MODERATE/DEBUG (GpuExec.scala:41)
      → TpuMetric on every exec, surfaced via `collect_plan_metrics`;
  (e) DumpUtils.scala (dump problem batches to parquet for offline repro) →
      `dump_batch`.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Dict, Optional

# ---------------------------------------------------------------------------
# (c) task metrics


class TaskMetricsRegistry:
    """Process-wide accumulators mirroring GpuTaskMetrics: semaphore wait,
    retry counts/time, spill bytes, read-spill time."""

    _instance: Optional["TaskMetricsRegistry"] = None
    _lock = threading.Lock()

    KNOWN = ("semaphoreWaitNs", "retryCount", "splitAndRetryCount",
             "retryBlockTimeNs", "spillToHostBytes", "spillToDiskBytes",
             "readSpillTimeNs", "deviceRetryCount", "deviceRetryBlockTimeNs")

    def __init__(self):
        self._vals: Dict[str, int] = {k: 0 for k in self.KNOWN}
        self._mu = threading.Lock()

    @classmethod
    def get(cls) -> "TaskMetricsRegistry":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def reset_for_tests(cls) -> "TaskMetricsRegistry":
        with cls._lock:
            cls._instance = cls()
            return cls._instance

    def add(self, name: str, value: int) -> None:
        with self._mu:
            self._vals[name] = self._vals.get(name, 0) + int(value)

    def snapshot(self) -> Dict[str, int]:
        with self._mu:
            return dict(self._vals)


# ---------------------------------------------------------------------------
# (c2) the sync ledger: every BLOCKING device→host transfer, attributed to
# the operator that caused it. Each blocking sync stalls the host until the
# device has drained its queue, so the *count* of syncs per partition — not
# their payload size — is what the general path pays for. All engine syncs route
# through columnar/vector.py's audited_sync helpers (tracelint TL011 flags
# strays), which record here; execs/base.py maintains the active-operator
# scope around every batch pull.


class SyncLedger:
    """Process-wide {operator: {kind: count}} of blocking D→H transfers."""

    _instance: Optional["SyncLedger"] = None
    _lock = threading.Lock()

    def __init__(self):
        self._mu = threading.Lock()
        self._by_op: Dict[str, Dict[str, int]] = {}
        self._total = 0

    @classmethod
    def get(cls) -> "SyncLedger":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def reset_for_tests(cls) -> "SyncLedger":
        with cls._lock:
            cls._instance = cls()
            return cls._instance

    def record(self, kind: str, op: Optional[str] = None) -> None:
        if op is None:
            op = current_sync_scope()
        with self._mu:
            ops = self._by_op.setdefault(op, {})
            ops[kind] = ops.get(kind, 0) + 1
            self._total += 1
        # piggyback the query tracer (obs): one instant event per blocking
        # sync PLUS the bound tracer's per-query sync counter, attributed
        # with the SAME operator scope the ledger used — the diagnostics
        # bundle reconciles against its own query's deltas even when other
        # queries run concurrently (the process-wide ledger cross-bleeds)
        from .obs import tracer as _obs
        if _obs._ACTIVE:
            _obs.sync_event(op, kind)

    def snapshot(self) -> Dict[str, Dict[str, int]]:
        with self._mu:
            return {op: dict(kinds) for op, kinds in self._by_op.items()}

    def total(self) -> int:
        with self._mu:
            return self._total

    def totals_by_op(self) -> Dict[str, int]:
        with self._mu:
            return {op: sum(kinds.values())
                    for op, kinds in self._by_op.items()}


class _SyncScope(threading.local):
    """Stack of operator names; the top attributes recorded syncs. Thread-
    local: pipelined map tasks and prefetch workers each carry their own."""
    stack = ()


_sync_scope_tls = _SyncScope()


def current_sync_scope() -> str:
    st = _sync_scope_tls.stack
    return st[-1] if st else "<unattributed>"


@contextlib.contextmanager
def sync_scope(name: str):
    """Attribute blocking syncs inside the scope to `name` (set by
    TpuExec.execute_partition around each batch pull, so nested pulls
    re-attribute to the producing operator)."""
    _sync_scope_tls.stack = _sync_scope_tls.stack + (name,)
    try:
        yield
    finally:
        _sync_scope_tls.stack = _sync_scope_tls.stack[:-1]


def record_sync(kind: str, op: Optional[str] = None) -> None:
    """Record one blocking device→host transfer (called by the audited sync
    helpers in columnar/vector.py)."""
    SyncLedger.get().record(kind, op)


# ---------------------------------------------------------------------------
# (a) operator trace scopes (NVTX analogue)

_PROFILING_ACTIVE = False


def set_trace_annotations(on: bool) -> None:
    """The public switch for the engine's `TraceAnnotation`s — the
    per-operator `trace_scope`s and the `srt.<phase>` spans of `obs.phase`
    — for a caller that runs its own `jax.profiler` session
    (`TpuProfiler.start/stop` call it themselves). It stores to
    `_PROFILING_ACTIVE`, the one flag every site reads."""
    global _PROFILING_ACTIVE
    _PROFILING_ACTIVE = bool(on)


#: the event `TpuProfiler.start` writes first: its `unix_ns` stat is
#: time.time_ns() at its own start
ANCHOR_SPAN = "srt.anchor"


@contextlib.contextmanager
def trace_scope(name: str):
    """NVTX-range analogue: a named scope in the xprof timeline. Free when no
    trace is being captured."""
    if not _PROFILING_ACTIVE:
        yield
        return
    import jax.profiler
    with jax.profiler.TraceAnnotation(name):
        yield


# ---------------------------------------------------------------------------
# (b) the profiler driver


class TpuProfiler:
    """Capture an xprof trace of a query region (reference ProfilerOnExecutor:
    scoped by configs, written under spark.rapids.profile.pathPrefix)."""

    def __init__(self, path_prefix: str):
        self.path = os.path.join(path_prefix,
                                 f"rapids-tpu-profile-{int(time.time())}")
        self._active = False
        #: time.time_ns() as start_trace was called. The trace's event
        #: times count from the profiler session's start, which lies inside
        #: start_trace (docs/observability.md "Laying a query over a device
        #: trace"); the ANCHOR_SPAN event gives the shift exactly
        self.t0_unix_ns: Optional[int] = None

    def start(self) -> None:
        import jax.profiler
        os.makedirs(self.path, exist_ok=True)
        self.t0_unix_ns = time.time_ns()
        jax.profiler.start_trace(self.path)
        self._active = True
        set_trace_annotations(True)
        # one event that carries the realtime instant of its own start:
        # unix_ns - start_ns is the realtime instant of the trace's zero
        with jax.profiler.TraceAnnotation(ANCHOR_SPAN,
                                          unix_ns=time.time_ns()):
            pass

    def stop(self) -> None:
        if not self._active:
            return
        import jax.profiler
        set_trace_annotations(False)
        jax.profiler.stop_trace()
        self._active = False

    def __enter__(self) -> "TpuProfiler":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


# ---------------------------------------------------------------------------
# (d) plan metric collection


_LEVEL_ORDER = {"ESSENTIAL": 0, "MODERATE": 1, "DEBUG": 2}


def collect_plan_metrics(plan, level: str = "MODERATE") -> Dict[str, Dict[str, int]]:
    """Per-operator metric values at or above the requested level
    (ESSENTIAL ⊂ MODERATE ⊂ DEBUG, reference GpuMetric levels)."""
    want = _LEVEL_ORDER.get(str(level).upper(), 1)
    out: Dict[str, Dict[str, int]] = {}
    for i, node in enumerate(plan.collect_nodes()):
        vals = {m.name: m.value for m in node.metrics.values()
                if _LEVEL_ORDER.get(m.level, 1) <= want and m.value}
        if vals:
            out[f"{i}:{node.node_name()}"] = vals
    return out


def snapshot_plan_metrics(plan) -> Dict[str, Dict[str, tuple]]:
    """All non-zero metrics with their levels, as plain data — lets the
    session drop the plan reference after the query (no device buffers
    pinned) while still supporting level filtering later."""
    out: Dict[str, Dict[str, tuple]] = {}
    for i, node in enumerate(plan.collect_nodes()):
        vals = {m.name: (m.value, m.level) for m in node.metrics.values()
                if m.value}
        if vals:
            out[f"{i}:{node.node_name()}"] = vals
    return out


def plan_query_counters(plan) -> Dict[str, int]:
    """The plan's nodes' `query_counters`, added up; a metric counts once
    under a name however many nodes name it. Read after
    :func:`snapshot_plan_metrics`, which has fetched the parked counts."""
    out: Dict[str, int] = {}
    seen = set()
    for node in plan.collect_nodes():
        for name, metric in node.query_counters() + node.mesh_counters():
            if (name, id(metric)) not in seen:
                seen.add((name, id(metric)))
                out[name] = out.get(name, 0) + metric.value
    return out


def metric_level_filter(snapshot: Dict[str, Dict[str, tuple]],
                        level: str) -> Dict[str, Dict[str, int]]:
    want = _LEVEL_ORDER.get(str(level).upper(), 1)
    out: Dict[str, Dict[str, int]] = {}
    for op, vals in snapshot.items():
        kept = {n: v for n, (v, lvl) in vals.items()
                if _LEVEL_ORDER.get(lvl, 1) <= want}
        if kept:
            out[op] = kept
    return out


# ---------------------------------------------------------------------------
# (e) batch dump for offline repro


def dump_batch(batch, path_prefix: str, op_name: str) -> str:
    """Write a problem batch to parquet for offline repro (reference
    DumpUtils.scala). Returns the written path."""
    import pyarrow.parquet as pq
    os.makedirs(path_prefix, exist_ok=True)
    p = os.path.join(path_prefix,
                     f"dump-{op_name}-{int(time.time() * 1000)}.parquet")
    table = batch if hasattr(batch, "num_columns") and not hasattr(
        batch, "to_arrow") else batch.to_arrow()
    pq.write_table(table, p)
    return p
