"""Mesh efficiency profiler: per-exchange wall attribution, skew and
straggler reporting, and the collective watchdog.

A mesh query's collectives can be a small part of its wall, and the rest
must not stay unattributed (host staging? launch overhead? compact?
partition skew? idle chips?). The reference stack treats shuffle-transport visibility as
a first-class subsystem (per-peer/per-block accounting around
``RapidsShuffleHeartbeatManager``, SURVEY §2.7); this module is that
layer for the collective data plane:

* **Per-exchange profiles** — every collective exchange records a
  :data:`MeshExchangeProfile`-shaped dict (exchange id, per-chip send /
  recv rows and bytes from the already-synced sizing counters — ZERO new
  device syncs — plus the phase walls: host staging, program launch,
  collective wait, per-shard compact) into a bounded process-wide ring.
  The session folds the profiles recorded during one query into the
  diagnostics bundle's ``mesh`` section (``last_query_profile()``), the
  always-on registry folds the recent ring into
  ``session.metrics_snapshot()``, and ``parallel/sharded.py`` turns them
  into a query's ``efficiency_attribution`` breakdown.
* **Skew metrics** — per profile: max / median per-chip received rows,
  the imbalance factor (max/median), and the straggler chip id when one
  chip's share exceeds ``spark.rapids.tpu.obs.meshStragglerFactor`` × the
  median (per-chip rows are the exact host-known proxy for that chip's
  downstream work — the wait of everyone else). Registry histograms
  ``mesh.skew_imbalance`` (imbalance × 100, log2 buckets) and
  ``mesh.straggler_wait_ms`` (the collective wait of exchanges where a
  straggler was detected) feed serving dashboards.
* **"Why not collective" reasons** — when the planner or the exchange
  routes a mesh-session exchange per-map (string payload, misaligned
  partitions, conf off, staging OOM), :func:`record_fallback` counts the
  reason (``mesh.per_map_exchange{reason=…}``) and keeps it for the
  multichip summary and ``explain("metrics")``.
* **Collective watchdog** — on real hardware a hung chip manifests
  exactly as an unbounded collective wait, indistinguishable from a slow
  one. :func:`collective_watchdog` arms a timer around the launch+wait
  window: past ``spark.rapids.tpu.obs.collectiveWatchdogMs`` it emits a
  flight-recorder event + the ``mesh.watchdog_fired`` counter WHILE the
  wait is still blocked; past ``…collectiveWatchdogFatalMs`` (when set)
  it dumps a postmortem bundle so the incident artifact exists even if
  the process never returns from the wait.

Emission discipline is the same TL012 contract as the rest of the plane:
every value recorded here is a host scalar the collective already holds
(the sizing counters and ``perf_counter`` walls) — the profiler adds no
device round trip to the hot path, asserted by
``tests/test_mesh_profile.py``.

Schema: docs/observability.md "Mesh profiling".
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

_RING_SIZE = 256

_LOCK = threading.Lock()
#: recording switch (tests toggle it to prove zero hot-path impact); the
#: watchdog is configured independently via the conf thresholds
_ENABLED = True
_SEQ = 0
_PROFILES: deque = deque(maxlen=_RING_SIZE)
_FALLBACKS: deque = deque(maxlen=_RING_SIZE)

#: watchdog / skew thresholds — armed once at session init
#: (maybe_configure, the flight-recorder pattern: the exchange hot path
#: has no session handle)
_WATCHDOG_MS = 30000.0
_WATCHDOG_FATAL_MS = 0.0
_STRAGGLER_FACTOR = 2.0


def maybe_configure(conf) -> None:
    """Apply the collective-watchdog thresholds and the straggler factor
    from a session's conf — called at session init (same arm-once pattern
    as ``flight.maybe_configure``: only EXPLICITLY SET keys overwrite the
    process state, so constructing a default-conf session never silently
    resets another live session's thresholds)."""
    global _WATCHDOG_MS, _WATCHDOG_FATAL_MS, _STRAGGLER_FACTOR
    from ..config import (OBS_COLLECTIVE_WATCHDOG_FATAL_MS,
                          OBS_COLLECTIVE_WATCHDOG_MS,
                          OBS_MESH_STRAGGLER_FACTOR)
    with _LOCK:
        if conf.get_raw(OBS_COLLECTIVE_WATCHDOG_MS.key) is not None:
            _WATCHDOG_MS = float(conf.get(OBS_COLLECTIVE_WATCHDOG_MS))
        if conf.get_raw(OBS_COLLECTIVE_WATCHDOG_FATAL_MS.key) is not None:
            _WATCHDOG_FATAL_MS = float(
                conf.get(OBS_COLLECTIVE_WATCHDOG_FATAL_MS))
        if conf.get_raw(OBS_MESH_STRAGGLER_FACTOR.key) is not None:
            _STRAGGLER_FACTOR = max(1.0, float(
                conf.get(OBS_MESH_STRAGGLER_FACTOR)))


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def reset_for_tests() -> None:
    global _ENABLED, _SEQ, _WATCHDOG_MS, _WATCHDOG_FATAL_MS, \
        _STRAGGLER_FACTOR
    with _LOCK:
        _ENABLED = True
        _SEQ = 0
        _PROFILES.clear()
        _FALLBACKS.clear()
        _WATCHDOG_MS = 30000.0
        _WATCHDOG_FATAL_MS = 0.0
        _STRAGGLER_FACTOR = 2.0


def current_seq() -> int:
    """Monotone count of recorded exchange profiles — snapshot before a
    query, pass to :func:`profiles_since` after (the same windowing idiom
    as the session's counter deltas)."""
    with _LOCK:
        return _SEQ


def alloc_seq() -> int:
    """Pre-allocate the next profile's sequence id so the ``mesh.exchange``
    span and the consumer-read flow events can reference it before the
    profile itself is recorded (the Chrome-trace pairing key)."""
    global _SEQ
    with _LOCK:
        _SEQ += 1
        return _SEQ


def profiles_since(seq: int, query: Optional[str] = None
                   ) -> List[Dict[str, Any]]:
    """Profiles recorded after sequence ``seq``; when ``query`` is given,
    keep ONLY profiles tagged with that traced query name. The filter is
    strict: a traced query's exchanges always materialize on a tracer-
    bound thread so its own profiles are tagged, and accepting untagged
    (query=None) records would absorb a concurrent UNTRACED query's
    exchanges into this query's bundle (cross-query bleed — the exact
    failure the PR 12 routing exists to prevent)."""
    with _LOCK:
        out = [p for p in _PROFILES if p["seq"] > seq]
    if query is not None:
        out = [p for p in out if p.get("query") == query]
    return out


def fallbacks_since(seq: int, query: Optional[str] = None
                    ) -> List[Dict[str, Any]]:
    with _LOCK:
        out = [f for f in _FALLBACKS if f["seq"] > seq]
    if query is not None:
        out = [f for f in out if f.get("query") == query]
    return out


def window_dropped(seq: int) -> int:
    """How many records (profiles + fallbacks) sequenced after ``seq``
    have already been evicted from the bounded rings — callers report the
    count instead of presenting a silently truncated window as complete.
    (Sequence ids are allocated across both rings, so the count is exact
    while recording is enabled.)"""
    with _LOCK:
        have = sum(1 for p in _PROFILES if p["seq"] > seq) \
            + sum(1 for f in _FALLBACKS if f["seq"] > seq)
        return max(0, _SEQ - seq - have)


def recent(last_k: int = 16) -> List[Dict[str, Any]]:
    """The most recent profiles (``metrics_snapshot()`` /
    ``tools/obs_report.py --mesh`` readout)."""
    with _LOCK:
        recs = list(_PROFILES)
    return recs[-last_k:]


def fallback_counts() -> Dict[str, int]:
    """{reason: count} over the fallback ring."""
    with _LOCK:
        recs = list(_FALLBACKS)
    out: Dict[str, int] = {}
    for f in recs:
        out[f["reason"]] = out.get(f["reason"], 0) + 1
    return out


def skew_stats(recv_rows: List[int], factor: Optional[float] = None
               ) -> Dict[str, Any]:
    """Skew metrics over one exchange's per-chip received-row counts (all
    host-known from the sizing sync): max, median, the imbalance factor
    (max/median — 1.0 is perfectly balanced) and the straggler chip id
    when the heaviest chip exceeds ``factor`` × the median."""
    if factor is None:
        factor = _STRAGGLER_FACTOR
    n = len(recv_rows)
    if n == 0 or not any(recv_rows):
        return {"max_rows": 0, "median_rows": 0, "imbalance": 1.0,
                "straggler_chip": None}
    ordered = sorted(recv_rows)
    mid = n // 2
    median = (ordered[mid] if n % 2
              else (ordered[mid - 1] + ordered[mid]) / 2.0)
    mx = max(recv_rows)
    # a zero median with a non-zero max is the worst skew there is: the
    # imbalance reports max vs the next-best denominator (1 row)
    imbalance = mx / max(float(median), 1.0)
    straggler = recv_rows.index(mx) \
        if mx > factor * max(float(median), 1.0) else None
    return {"max_rows": int(mx), "median_rows": float(median),
            "imbalance": round(float(imbalance), 3),
            "straggler_chip": straggler}


def record_exchange(seq: int, shuffle_id: int, partitioning: str,
                    n_dev: int, send_rows: List[int], recv_rows: List[int],
                    recv_bytes: List[int], stage_ns: int, launch_ns: int,
                    wait_ns: int, compact_ns: int,
                    watchdog_fired: bool = False,
                    compact_fused: bool = False,
                    staging_reuse_hits: int = 0,
                    overlap_segments: int = 0,
                    input_devices: int = 0
                    ) -> Optional[Dict[str, Any]]:
    """Record one collective exchange's profile. Every argument is a host
    value the collective already computed (the sizing counters and the
    ``perf_counter`` walls) — recording adds zero device syncs. Returns
    the profile dict (also appended to the ring), or None when recording
    is disabled."""
    if not _ENABLED:
        return None
    from . import metrics as _metrics
    from .tracer import current_query_name
    wait_ms = wait_ns / 1e6
    skew = skew_stats(list(recv_rows))
    profile: Dict[str, Any] = {
        "seq": seq,
        "exchange": shuffle_id,
        "partitioning": partitioning,
        "n_dev": n_dev,
        "query": current_query_name(),
        "ts": time.time(),
        "send_rows": [int(x) for x in send_rows],
        "recv_rows": [int(x) for x in recv_rows],
        "recv_bytes": [int(x) for x in recv_bytes],
        "phases_ms": {
            "staging": round(stage_ns / 1e6, 3),
            "launch": round(launch_ns / 1e6, 3),
            "collective_wait": round(wait_ms, 3),
            "compact": round(compact_ns / 1e6, 3),
        },
        "skew": skew,
        "watchdog_fired": bool(watchdog_fired),
        # r07 fused dataplane keys (docs/distributed.md "Fused compact &
        # overlap"): whether the post-collective compact ran inside the
        # collective dispatch, how many staged pad pieces came from the
        # staging pool, and the segment count when the exchange rode the
        # overlapped path (0 = unsegmented)
        "compact_fused": bool(compact_fused),
        "staging_reuse_hits": int(staging_reuse_hits),
        "overlap_segments": int(overlap_segments),
        # distinct devices holding the staged inputs' shards (sharding
        # metadata, no sync): n_dev when the exchange really spans the mesh
        "input_devices": int(input_devices),
    }
    # registry histograms (docs/observability.md "Mesh profiling"):
    # imbalance ×100 so the log2 buckets resolve 1.28x from 2.56x from
    # 5.12x; straggler_wait_ms only for exchanges where a straggler was
    # actually detected — its p95 is the "how much wall does skew cost"
    # dashboard number
    _metrics.histogram_observe("mesh.skew_imbalance",
                               skew["imbalance"] * 100.0)
    if skew["straggler_chip"] is not None:
        _metrics.histogram_observe("mesh.straggler_wait_ms", wait_ms)
    with _LOCK:
        _PROFILES.append(profile)
    return profile


def record_fallback(shuffle_id: int, reason: str) -> None:
    """One mesh-session exchange routed per-map instead of riding the
    collective: count the reason (``mesh.per_map_exchange{reason=…}``)
    and keep it for the multichip summary / diagnostics bundle."""
    global _SEQ
    if not _ENABLED:
        return
    from . import metrics as _metrics
    from .tracer import current_query_name
    _metrics.counter_inc("mesh.per_map_exchange", reason=reason)
    with _LOCK:
        _SEQ += 1
        _FALLBACKS.append({"seq": _SEQ, "exchange": shuffle_id,
                           "reason": str(reason),
                           "query": current_query_name(),
                           "ts": time.time()})


class collective_watchdog:
    """Context manager arming the collective watchdog around one
    launch+wait window. Timers fire on daemon threads WHILE the wait is
    still blocked — the only vantage point that can tell a hung chip
    (unbounded wait) from a slow one:

    * at ``collectiveWatchdogMs``: flight-recorder event
      (``mesh.watchdog``) + ``mesh.watchdog_fired`` registry counter, and
      the profile records ``watchdog_fired`` when the exchange eventually
      completes;
    * at ``collectiveWatchdogFatalMs`` (when > 0): a postmortem bundle
      under ``spark.rapids.tpu.obs.postmortemDir`` — the incident
      artifact exists even if the process never returns from the wait.

    Both timers cancel on a timely exit; a watchdog with threshold 0 is
    disabled and arms nothing."""

    __slots__ = ("_shuffle", "_n_dev", "_query", "_t0", "_timer",
                 "_fatal_timer", "fired", "fatal_fired")

    def __init__(self, shuffle_id: int, n_dev: int):
        self._shuffle = shuffle_id
        self._n_dev = n_dev
        self._query = None
        self._t0 = 0.0
        self._timer: Optional[threading.Timer] = None
        self._fatal_timer: Optional[threading.Timer] = None
        self.fired = False
        self.fatal_fired = False

    def __enter__(self) -> "collective_watchdog":
        from .tracer import current_query_name
        # captured on the exchange thread: the timer threads have no
        # tracer binding, so the flight note tags the query explicitly
        self._query = current_query_name()
        self._t0 = time.perf_counter()
        if _WATCHDOG_MS > 0:
            self._timer = threading.Timer(_WATCHDOG_MS / 1e3, self._trip)
            self._timer.daemon = True
            self._timer.start()
        if _WATCHDOG_FATAL_MS > 0:
            self._fatal_timer = threading.Timer(_WATCHDOG_FATAL_MS / 1e3,
                                                self._fatal)
            self._fatal_timer.daemon = True
            self._fatal_timer.start()
        return self

    def _waited_ms(self) -> float:
        return round((time.perf_counter() - self._t0) * 1e3, 1)

    def _trip(self) -> None:
        from . import flight as _flight
        from . import metrics as _metrics
        self.fired = True
        _metrics.counter_inc("mesh.watchdog_fired")
        _flight.note("mesh.watchdog", shuffle=self._shuffle,
                     n_dev=self._n_dev, waited_ms=self._waited_ms(),
                     threshold_ms=_WATCHDOG_MS,
                     query=self._query or "<untraced>")

    def _fatal(self) -> None:
        from . import flight as _flight
        from . import metrics as _metrics
        self.fatal_fired = True
        _metrics.counter_inc("mesh.watchdog_fatal")
        _flight.note("mesh.watchdog_fatal", shuffle=self._shuffle,
                     waited_ms=self._waited_ms(),
                     threshold_ms=_WATCHDOG_FATAL_MS,
                     query=self._query or "<untraced>")
        _flight.postmortem("collective_watchdog")

    def __exit__(self, *exc) -> bool:
        if self._timer is not None:
            self._timer.cancel()
        if self._fatal_timer is not None:
            self._fatal_timer.cancel()
        return False
