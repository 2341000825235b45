"""Always-on process-wide metrics registry: counters, gauges, and bounded
log2-bucket histograms with per-session/per-query labels.

Reference: the plugin accumulates per-operator ``GpuMetric``s into Spark's
executor-wide metrics system and history server (SURVEY L2 /
``GpuExec.scala``) — an aggregate, always-on layer that exists whether or
not anyone is profiling, so serving dashboards (rows/s, p95 latency, HBM
pressure, spill volume) read from running totals instead of per-query
artifacts. This module is that layer for the TPU engine; the per-query
tracer (obs/tracer.py) remains the deep-dive tool.

Design:

* **Always on, near-zero cost when idle**: nothing increments when no
  query runs. The hot path is one dict lookup plus one in-place add on a
  pre-resolved cell — no lock is taken on the increment path (CPython's
  GIL keeps cell reads untorn; a rare lost update under extreme thread
  contention is the standard monitoring-counter tradeoff and is
  documented here rather than hidden). Locks guard only registry/label
  STRUCTURE (first sight of a metric or label set) and snapshots.
* **Emission discipline** (tracelint TL012, analysis/obslint.py): engine
  code emits through the module-level helpers (:func:`counter_inc`,
  :func:`gauge_set`, :func:`gauge_max`, :func:`histogram_observe`) and a
  label/value argument must never embed a blocking device→host sync —
  metric values are numbers the caller already holds on host.
* **Histograms** use log2 buckets: bucket ``i`` counts observations in
  ``[2^(i-1), 2^i)`` (bucket 0: values < 1), 64 buckets total — bounded
  memory per label set, and p50/p95/p99 read out as the upper edge of the
  bucket where the cumulative count crosses the rank (factor-of-two
  resolution, which is what a serving dashboard needs).
* **Query lifecycle** (:func:`query_begin` / :func:`query_end`) feeds the
  ``queries.active`` gauge, the ``query.latency_ms`` / ``query.rows_per_s``
  histograms and the process-wide query epoch the tracer uses to decide
  whether process-wide counter deltas are attributable to one query
  (``exclusive``) — it runs for EVERY query, traced or not.
* :func:`full_snapshot` is the one readout
  (``session.metrics_snapshot()``, ``python -m tools.obs_report``): the
  registry's own metrics plus the pre-existing process-wide counters
  folded in at snapshot time (opjit ``cache_stats``, mesh
  ``collective_stats``, the SyncLedger, ``TaskMetricsRegistry``, chaos
  injection counts, shuffle/HBM/spill state) — folding at read time keeps
  their hot paths untouched.

Schema: docs/observability.md "Metrics registry".
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from .. import profiling as _profiling

#: global off-switch (spark.rapids.tpu.obs.metrics.enabled; session init
#: applies it) — read unlocked on every emission
_ENABLED = True

_N_BUCKETS = 64

_REG_LOCK = threading.Lock()


def _label_key(labels: Dict[str, Any]) -> Tuple[Tuple[str, str], ...]:
    if not labels:
        return ()
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


class _Hist:
    """One label set's log2 histogram cell."""

    __slots__ = ("buckets", "count", "total")

    def __init__(self):
        self.buckets = [0] * _N_BUCKETS
        self.count = 0
        self.total = 0.0

    def observe(self, value) -> None:
        v = int(value)
        idx = v.bit_length() if v > 0 else 0
        if idx >= _N_BUCKETS:
            idx = _N_BUCKETS - 1
        self.buckets[idx] += 1
        self.count += 1
        self.total += float(value)

    def quantile(self, q: float) -> float:
        """Upper bucket edge where the cumulative count crosses rank
        ``q * count`` (factor-of-two resolution)."""
        if self.count == 0:
            return 0.0
        rank = q * self.count
        cum = 0
        for i, n in enumerate(self.buckets):
            cum += n
            if cum >= rank:
                return float(1 << i)
        return float(1 << (_N_BUCKETS - 1))

    def snapshot(self) -> Dict[str, Any]:
        return {
            "count": self.count,
            "sum": self.total,
            "p50": self.quantile(0.50),
            "p95": self.quantile(0.95),
            "p99": self.quantile(0.99),
            "buckets": {f"<{1 << i}": n
                        for i, n in enumerate(self.buckets) if n},
        }


class MetricsRegistry:
    """Process-wide metric store. Engine code uses the module helpers;
    this class is the storage + snapshot."""

    _instance: Optional["MetricsRegistry"] = None

    def __init__(self):
        # name -> {label_key: cell}; counter/gauge cells are one-element
        # lists (in-place adds stay lock-free), histogram cells are _Hist
        self._counters: Dict[str, Dict[Tuple, list]] = {}
        self._gauges: Dict[str, Dict[Tuple, list]] = {}
        self._hists: Dict[str, Dict[Tuple, _Hist]] = {}

    @classmethod
    def get(cls) -> "MetricsRegistry":
        reg = cls._instance
        if reg is None:
            with _REG_LOCK:
                reg = cls._instance
                if reg is None:
                    reg = cls._instance = cls()
        return reg

    @classmethod
    def reset_for_tests(cls) -> "MetricsRegistry":
        global _ENABLED
        with _REG_LOCK:
            cls._instance = cls()
            _ENABLED = True
            return cls._instance

    def _cell(self, table: Dict[str, Dict], name: str, labels, ctor):
        cells = table.get(name)
        key = _label_key(labels)
        if cells is not None:
            cell = cells.get(key)
            if cell is not None:
                return cell
        with _REG_LOCK:
            cells = table.setdefault(name, {})
            cell = cells.get(key)
            if cell is None:
                cell = cells[key] = ctor()
            return cell

    # --- snapshot ----------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        with _REG_LOCK:
            counters = {n: {self._fmt(k): c[0] for k, c in cells.items()}
                        for n, cells in self._counters.items()}
            gauges = {n: {self._fmt(k): c[0] for k, c in cells.items()}
                      for n, cells in self._gauges.items()}
            hists = {n: {self._fmt(k): h.snapshot()
                         for k, h in cells.items()}
                     for n, cells in self._hists.items()}
        return {"counters": counters, "gauges": gauges,
                "histograms": hists}

    @staticmethod
    def _fmt(key: Tuple) -> str:
        return ",".join(f"{k}={v}" for k, v in key) if key else ""


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def counter_inc(name: str, value: int = 1, **labels) -> None:
    """Add ``value`` to a monotonic counter (one cell per label set)."""
    if not _ENABLED:
        return
    cell = MetricsRegistry.get()._cell(
        MetricsRegistry.get()._counters, name, labels, lambda: [0])
    cell[0] += value


def gauge_set(name: str, value, **labels) -> None:
    """Set a gauge to the latest value."""
    if not _ENABLED:
        return
    cell = MetricsRegistry.get()._cell(
        MetricsRegistry.get()._gauges, name, labels, lambda: [0])
    cell[0] = value


def gauge_max(name: str, value, **labels) -> None:
    """Raise a high-water gauge to ``value`` if it exceeds the current."""
    if not _ENABLED:
        return
    cell = MetricsRegistry.get()._cell(
        MetricsRegistry.get()._gauges, name, labels, lambda: [0])
    if value > cell[0]:
        cell[0] = value


def histogram_observe(name: str, value, **labels) -> None:
    """Record one observation into a log2-bucket histogram."""
    if not _ENABLED:
        return
    MetricsRegistry.get()._cell(
        MetricsRegistry.get()._hists, name, labels, _Hist).observe(value)


# ---------------------------------------------------------------------------
# query lifecycle: every query (traced or not) registers here — the active-
# query gauge/list, the latency and rows/s histograms, and the epoch the
# tracer's exclusivity check reads all come from this one place.

_QL_LOCK = threading.Lock()
# token -> (name, t0_ns, priority class or None, t0 unix ns, profiler
# annotations on at begin)
_ACTIVE_QUERIES: Dict[int, Tuple] = {}
_EPOCH = 0
_NEXT_TOKEN = 1
#: per-query summaries, newest last (recent_queries): what the phase table
#: of each finished query folded into
_RECENT: deque = deque(maxlen=4096)
_COMPILE_LISTENERS = False


def _set_active_gauges_locked() -> None:
    """queries.active total plus one labelled cell per SLO class with an
    active query (docs/serving.md): a dashboard watching
    queries.active{cls=interactive} sees exactly the class the shed
    policy protects. Committed under the lifecycle lock — an interleaved
    begin/end pair must not overwrite a gauge with a stale count."""
    gauge_set("queries.active", len(_ACTIVE_QUERIES))
    by_cls: Dict[str, int] = {}
    for entry in _ACTIVE_QUERIES.values():
        cls = entry[2]
        if cls is not None:
            by_cls[cls] = by_cls.get(cls, 0) + 1
    from ..serving.query_context import PRIORITIES
    for cls in PRIORITIES:
        gauge_set("queries.active", by_cls.get(cls, 0), cls=cls)


def query_begin(name: str, session: str = "default",
                cls: Optional[str] = None) -> int:
    """Register a query start; returns the token for :func:`query_end`.
    `cls` is the SLO priority class (None for lifecycle paths that
    predate classes — counted in the total, not any per-class cell)."""
    global _EPOCH, _NEXT_TOKEN
    _install_compile_listeners()
    with _QL_LOCK:
        _EPOCH += 1
        token = _NEXT_TOKEN
        _NEXT_TOKEN += 1
        _ACTIVE_QUERIES[token] = (
            name, time.perf_counter_ns(), cls, time.time_ns(),
            _profiling._PROFILING_ACTIVE)
        _set_active_gauges_locked()
    from . import flight as _flight
    _flight.note("query.begin", query=name, session=session)
    return token


def query_end(token: int, rows: Optional[int] = None,
              failed: bool = False, session: str = "default",
              qctx=None) -> Optional[Dict[str, Any]]:
    """Close a query: latency/rows-per-s histograms + completion counters,
    and the per-query summary — the phase table of `qctx` (the query's
    QueryContext; obs.phase) folded beside the query's own clocks —
    appended to the recent-queries ring and returned. ``cpu_ns`` is the
    root phase's (``query``): the query thread's CPU time. Idempotent on an
    unknown token (returns None)."""
    with _QL_LOCK:
        entry = _ACTIVE_QUERIES.pop(token, None)
        _set_active_gauges_locked()
    if entry is None:
        return None
    name, t0, cls, t0_unix, annotated = entry
    t1 = time.perf_counter_ns()
    latency_ms = (t1 - t0) / 1e6
    phases = {} if qctx is None else qctx.phase_table()
    compiled = phases.get("xla.compile", {})
    wait_ms = None if qctx is None else qctx.admit_wait_ms
    summary = {
        "name": name,
        "session": session if qctx is None else qctx.session_id,
        "cls": cls, "failed": bool(failed),
        "t_begin_ns": t0, "t_end_ns": t1, "t_begin_unix_ns": t0_unix,
        "admit_wait_ns": int((wait_ms or 0.0) * 1e6),
        "wall_ns": t1 - t0,
        "cpu_ns": phases.get("query", {}).get("cpu_ns"),
        "annotated": bool(annotated or _profiling._PROFILING_ACTIVE),
        "phases": phases,
        "counters": {} if qctx is None else qctx.counter_table(),
        "compiles": compiled.get("count", 0),
        "compile_ns": compiled.get("wall_ns", 0)}
    with _QL_LOCK:
        _RECENT.append(summary)
    counter_inc("queries.failed" if failed else "queries.completed",
                session=session)
    histogram_observe("query.latency_ms", latency_ms, session=session)
    if rows is not None and not failed and latency_ms > 0:
        histogram_observe("query.rows_per_s", rows / (latency_ms / 1e3),
                          session=session)
    from . import flight as _flight
    _flight.note("query.end", query=name, session=session,
                 latency_ms=round(latency_ms, 3), rows=rows, failed=failed)
    return summary


def recent_queries(n: Optional[int] = None) -> List[Dict[str, Any]]:
    """The last `n` (default: all kept, at most 4096) per-query summaries,
    oldest first: ``{name, session, cls, failed, t_begin_ns, t_end_ns``
    (``time.perf_counter_ns()``, absolute)``, t_begin_unix_ns``
    (``time.time_ns()``)``, admit_wait_ns, wall_ns, cpu_ns, annotated,
    phases: {name: {count, wall_ns, cpu_ns (None: not sampled),
    child_wall_ns, cat}}, counters: {name: n}, compiles, compile_ns}`` —
    docs/observability.md "Span model"."""
    with _QL_LOCK:
        out = list(_RECENT)
    return out if n is None else out[-n:] if n > 0 else []


def phase_totals() -> Dict[str, Dict[str, Any]]:
    """Per-phase totals over the kept summaries (the last 4096 queries at
    most), folded at read time like the other external counters."""
    totals: Dict[str, Dict[str, Any]] = {}
    for summary in recent_queries():
        for n, ph in summary["phases"].items():
            tot = totals.get(n)
            if tot is None:
                tot = totals[n] = {
                    "queries": 0, "count": 0, "wall_ns": 0, "cpu_ns": 0,
                    "child_wall_ns": 0, "cat": ph["cat"]}
            tot["queries"] += 1
            for k in ("count", "wall_ns", "child_wall_ns"):
                tot[k] += ph[k]
            tot["cpu_ns"] = None if None in (tot["cpu_ns"], ph["cpu_ns"]) \
                else tot["cpu_ns"] + ph["cpu_ns"]
    return totals


def _install_compile_listeners() -> None:
    """Once per process: attribute every XLA backend compile (a persistent-
    cache load fires the same event, with its load time) to the query bound
    to the compiling thread as phase ``xla.compile``, and count compiles,
    their ms and the persistent cache's hits in the registry."""
    global _COMPILE_LISTENERS
    if _COMPILE_LISTENERS:
        return
    with _QL_LOCK:
        if _COMPILE_LISTENERS:
            return
        _COMPILE_LISTENERS = True
    import jax.monitoring

    from .tracer import phase_add

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            counter_inc("xla.compiles")
            counter_inc("xla.compile_ms", secs * 1e3)
            phase_add("xla.compile", 1, int(secs * 1e9), None)

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            counter_inc("xla.cache_hits")

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def active_queries() -> List[str]:
    with _QL_LOCK:
        return [entry[0] for entry in _ACTIVE_QUERIES.values()]


def active_query_count() -> int:
    with _QL_LOCK:
        return len(_ACTIVE_QUERIES)


def query_epoch() -> int:
    """Monotone count of query begins (any session, traced or not) — the
    tracer compares begin/end epochs to decide exclusivity."""
    with _QL_LOCK:
        return _EPOCH


def reset_query_state_for_tests() -> None:
    global _EPOCH, _NEXT_TOKEN
    with _QL_LOCK:
        _ACTIVE_QUERIES.clear()
        _RECENT.clear()
        _EPOCH = 0
        _NEXT_TOKEN = 1


# ---------------------------------------------------------------------------
# the one readout: registry + pre-existing process-wide counters folded in
# at snapshot time (their hot paths stay untouched)


def hbm_state() -> Dict[str, Any]:
    """HBM budget state without side-effect instantiation (shared by the
    metrics snapshot and the flight recorder's postmortem bundle)."""
    from ..memory.hbm import HbmBudget
    b = HbmBudget._instance
    if b is None:
        return {}
    return {"budget": b.budget, "used": b.used,
            "peak_used": b.peak_used, "alloc_count": b.alloc_count}


def full_snapshot() -> Dict[str, Any]:
    """The registry snapshot plus the engine's other process-wide counters
    (opjit cache stats incl. hit rate, mesh collective_stats, SyncLedger
    totals, task metrics, chaos injections, shuffle bytes, HBM state) —
    ``session.metrics_snapshot()`` and ``tools/obs_report.py`` both serve
    this. Folding never raises: a source that cannot be read reports an
    error string instead."""
    out = MetricsRegistry.get().snapshot()
    out["schema"] = "spark-rapids-tpu/metrics/1"
    out["queries"] = {"active": active_queries(), "epoch": query_epoch()}
    out["phases"] = phase_totals()
    ext: Dict[str, Any] = {}

    def fold(key, fn):
        try:
            ext[key] = fn()
        except Exception as e:  # noqa: BLE001 — a readout must never fail
            ext[key] = {"error": f"{type(e).__name__}: {e}"[:120]}

    def _opjit():
        from ..execs import opjit
        st = opjit.cache_stats()
        calls = st.get("hits", 0) + st.get("misses", 0)
        st["hit_rate"] = round(st.get("hits", 0) / calls, 4) if calls \
            else None
        st["entries"] = opjit.cache_len()
        return st

    def _collective():
        from ..parallel.mesh import collective_stats
        return collective_stats()

    def _mesh_profiles():
        # the mesh efficiency profiler's recent per-exchange records
        # (phase walls + skew tables) and the per-map fallback reasons —
        # the metrics_snapshot() "mesh" readout next to the registry's
        # mesh.* histograms
        from . import mesh_profile
        return {"recent_exchanges": mesh_profile.recent(16),
                "per_map_reasons": mesh_profile.fallback_counts()}

    def _syncs():
        from ..profiling import SyncLedger
        led = SyncLedger.get()
        return {"total": led.total(), "by_op": led.totals_by_op()}

    def _task_metrics():
        from ..profiling import TaskMetricsRegistry
        return TaskMetricsRegistry.get().snapshot()

    def _chaos():
        from ..chaos import FaultInjector
        inj = FaultInjector.get()
        return {"injections": inj.injection_count(),
                "enabled": inj.enabled}

    def _shuffle():
        from ..shuffle.manager import TpuShuffleManager
        mgr = TpuShuffleManager._instance  # no side-effect instantiation
        if mgr is None:
            return {}
        return {"bytes_written": mgr.bytes_written,
                "bytes_read": mgr.bytes_read}

    def _scheduler():
        # the query scheduler's admission state (queued/running names,
        # limits) — docs/robustness.md "Query lifecycle"
        from ..serving.scheduler import QueryScheduler
        s = QueryScheduler._instance  # no side-effect instantiation
        if s is None:
            return {}
        return s.snapshot()

    fold("opjit", _opjit)
    fold("collective", _collective)
    fold("mesh_profiles", _mesh_profiles)
    fold("sync_ledger", _syncs)
    fold("task_metrics", _task_metrics)
    fold("chaos", _chaos)
    fold("shuffle", _shuffle)
    fold("scheduler", _scheduler)
    fold("hbm", hbm_state)
    out["external"] = ext
    return out
