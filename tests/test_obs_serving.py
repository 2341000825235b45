"""Serving-era observability (ISSUE 12, docs/observability.md): concurrent
per-query tracing, the always-on metrics registry, and the crash flight
recorder + postmortem bundles.

* N=4 threads each run a TRACED query concurrently: every session gets its
  own ``last_query_profile()`` bundle, each reconciles against its own
  query's dispatch/sync deltas (no cross-query bleed — the SUM of all
  bundles' dispatch counts equals the process-wide ``calls_by_kind`` delta
  for the whole run), and zero queries are silently untraced;
* trace-capacity drops are COUNTED in the ``trace.dropped_queries``
  registry counter, never silent (the old one-query singleton's None);
* the always-on registry: query latency / rows-per-s histograms populated
  by a multi-query run with p50/p95 readouts, and an overhead gate showing
  registry emission costs < 2% of a jitted microbench batch;
* flight recorder + postmortem: a chaos-injected FATAL device error dumps
  a postmortem bundle carrying the failing query's last-K flight events
  and a registry snapshot; exhausted transient retries and a genuine HBM
  budget OOM dump their own bundles.
"""

import glob
import json
import threading
import time

import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.obs import flight as obs_flight
from spark_rapids_tpu.obs import metrics as obs_metrics
from spark_rapids_tpu.obs import tracer as obs_tracer
from spark_rapids_tpu.session import TpuSession


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs_tracer.QueryTracer.reset_for_tests()
    obs_metrics.MetricsRegistry.reset_for_tests()
    obs_metrics.reset_query_state_for_tests()
    obs_flight.reset_for_tests()
    yield
    obs_tracer.QueryTracer.reset_for_tests()
    obs_metrics.MetricsRegistry.reset_for_tests()
    obs_metrics.reset_query_state_for_tests()
    obs_flight.reset_for_tests()


_GENERAL = {"spark.rapids.tpu.agg.compiledStage.enabled": "false",
            "spark.rapids.tpu.join.compiledStage.enabled": "false",
            "spark.sql.autoBroadcastJoinThreshold": "-1"}


def _traced_session(parts=4, tag=None, **extra):
    conf = {"spark.rapids.tpu.trace.enabled": "true",
            "spark.sql.shuffle.partitions": str(parts)}
    if tag:
        conf["spark.rapids.tpu.trace.tag"] = tag
    conf.update(extra)
    return TpuSession(conf)


def _shuffled_query(s, n=2000, seed=0):
    fact = pa.table({
        "k": pa.array([(i * 7 + seed) % 20 for i in range(n)],
                      type=pa.int64()),
        "v": pa.array([float(i % 97) for i in range(n)])})
    f = s.createDataFrame(fact, num_partitions=2)
    return (f.filter(F.col("v") > 3.0)
            .groupBy("k").agg(F.sum(F.col("v")).alias("sv"))
            .sort("sv"))


def _drop_total(snap):
    return sum(snap["counters"].get("trace.dropped_queries", {}).values())


# ---------------------------------------------------------------------------
# concurrent per-query tracing
# ---------------------------------------------------------------------------


def test_four_concurrent_traced_queries_reconcile_independently():
    """The acceptance bar: 4 threads × 4 sessions, each query traced, each
    bundle reconciles against ITS OWN query's dispatch/sync deltas, zero
    silent drops, and the union of the bundles accounts for every
    process-wide dispatch of the run (no bleed, no loss)."""
    from spark_rapids_tpu.execs import opjit
    N = 4
    # distinct shuffle-partition counts desymmetrize the queries so
    # cross-query bleed could not hide behind identical counts
    sessions = [_traced_session(parts=2 + i, tag=f"conc{i}", **_GENERAL)
                for i in range(N)]
    queries = [_shuffled_query(s, seed=i)
               for i, s in enumerate(sessions)]
    # warm plans/caches untraced so the traced run is steady-state
    for s, q in zip(sessions, queries):
        s.conf.set("spark.rapids.tpu.trace.enabled", "false")
        q.collect()
        s.conf.set("spark.rapids.tpu.trace.enabled", "true")

    disp_before = opjit.cache_stats()["calls_by_kind"]
    barrier = threading.Barrier(N)
    results, errors = {}, {}

    def run(i):
        try:
            barrier.wait(timeout=30)
            results[i] = queries[i].collect()
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors[i] = e

    threads = [threading.Thread(target=run, args=(i,)) for i in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    disp_after = opjit.cache_stats()["calls_by_kind"]

    bundles = []
    total_bundle_disp = {}
    for i, s in enumerate(sessions):
        p = s.last_query_profile()
        assert p is not None, f"query {i} ran silently untraced"
        bundles.append(p)
        rec = p["reconcile"]
        assert not rec["overflow"]
        assert rec["dispatch_ok"], (i, p["dispatches_by_kind"],
                                    rec["dispatch_expected"])
        assert rec["sync_ok"], (i, p["by_operator"])
        assert p["dispatches_by_kind"], f"query {i} recorded no dispatches"
        # the bundle's sync attribution IS this session's per-query ledger
        ledger = s.last_sync_ledger()
        got = {op: slot["syncs"] for op, slot in p["by_operator"].items()
               if slot.get("syncs")}
        assert got == ledger, (i, got, ledger)
        for k, v in p["dispatches_by_kind"].items():
            total_bundle_disp[k] = total_bundle_disp.get(k, 0) + v

    # no bleed AND no loss: the four bundles partition the process-wide
    # dispatch delta exactly
    delta = {k: disp_after.get(k, 0) - disp_before.get(k, 0)
             for k in set(disp_after) | set(disp_before)}
    delta = {k: v for k, v in delta.items() if v}
    assert total_bundle_disp == delta, (total_bundle_disp, delta)

    # every query traced: zero capacity/nested drops
    assert _drop_total(sessions[0].metrics_snapshot()) == 0

    # span trees are independent: each bundle's root is its own query
    names = {p["query"] for p in bundles}
    assert len(names) == N, names


def test_concurrent_begin_query_no_longer_silently_drops():
    """The PR 7 singleton returned None for a second concurrent
    begin_query (obs/tracer.py:35-36 then) — that behavior is GONE: a
    second query on another thread traces with its own tracer."""
    first = obs_tracer.begin_query("owner")
    assert first is not None
    second = {}

    def begin_on_other_thread():
        second["tr"] = obs_tracer.begin_query("peer")
        if second["tr"] is not None:
            with obs_tracer.span("op", cat="op"):
                obs_tracer.sync_event("X", "rows")
            second["profile"] = obs_tracer.end_query(second["tr"])

    t = threading.Thread(target=begin_on_other_thread)
    t.start()
    t.join()
    assert second["tr"] is not None, \
        "second concurrent begin_query must trace, not silently drop"
    assert second["profile"]["name"] == "peer"
    assert second["profile"]["sync_counts"] == {"X": {"rows": 1}}
    # the owner's record is untouched by the peer's events
    profile = obs_tracer.end_query(first)
    assert profile["name"] == "owner"
    assert profile["sync_counts"] == {}
    assert _drop_total(obs_metrics.full_snapshot()) == 0


def test_trace_capacity_drop_is_counted_not_silent():
    owner = obs_tracer.begin_query("owner", max_concurrent=1)
    assert owner is not None
    res = {}

    def over_capacity():
        res["tr"] = obs_tracer.begin_query("over", max_concurrent=1)

    t = threading.Thread(target=over_capacity)
    t.start()
    t.join()
    assert res["tr"] is None
    snap = obs_metrics.full_snapshot()
    drops = snap["counters"].get("trace.dropped_queries", {})
    assert drops.get("reason=capacity") == 1, drops
    obs_tracer.end_query(owner)
    # a nested begin on the SAME (already tracing) thread is also counted
    owner2 = obs_tracer.begin_query("owner2")
    assert obs_tracer.begin_query("nested") is None
    snap = obs_metrics.full_snapshot()
    assert snap["counters"]["trace.dropped_queries"].get(
        "reason=nested_thread") == 1
    obs_tracer.end_query(owner2)


# ---------------------------------------------------------------------------
# always-on metrics registry
# ---------------------------------------------------------------------------


def test_metrics_snapshot_populated_by_multi_query_run():
    s = TpuSession({"spark.sql.shuffle.partitions": "2"})
    q = _shuffled_query(s)
    for _ in range(3):
        assert q.collect()
    snap = s.metrics_snapshot()
    assert snap["schema"] == "spark-rapids-tpu/metrics/1"
    lat = snap["histograms"]["query.latency_ms"]
    cell = next(iter(lat.values()))
    assert cell["count"] >= 3
    assert cell["p50"] > 0 and cell["p95"] >= cell["p50"] \
        and cell["p99"] >= cell["p95"]
    rps = snap["histograms"]["query.rows_per_s"]
    assert next(iter(rps.values()))["count"] >= 3
    done = snap["counters"]["queries.completed"]
    assert sum(done.values()) >= 3
    assert snap["gauges"]["queries.active"][""] == 0
    # folded process-wide counters ride along
    assert snap["external"]["opjit"]["hits"] >= 0
    assert "sync_ledger" in snap["external"]
    assert "collective" in snap["external"]


def test_registry_overhead_gate():
    """The always-on registry must stay invisible next to device work: a
    generous 50-emissions-per-batch budget costs < 2% of one jitted
    microbench batch (same harness as the tracer's off-gate in
    test_obs.py)."""
    N = 100_000
    t0 = time.perf_counter()
    for i in range(N):
        obs_metrics.counter_inc("gate.counter")
    inc_cost = (time.perf_counter() - t0) / N
    t0 = time.perf_counter()
    for i in range(N):
        obs_metrics.histogram_observe("gate.hist", 1234)
    obs_cost = (time.perf_counter() - t0) / N
    s = TpuSession({})
    t = pa.table({"k": pa.array([i % 4 for i in range(20_000)],
                                type=pa.int64()),
                  "v": [float(i) for i in range(20_000)]})
    q = s.createDataFrame(t).groupBy("k").agg(F.sum(F.col("v")).alias("sv"))
    q.collect()  # warm
    batch_wall = min(
        (lambda t0=time.perf_counter(): (q.collect(),
                                         time.perf_counter() - t0)[1])()
        for _ in range(3))
    budget = 0.02 * batch_wall
    assert 50 * max(inc_cost, obs_cost) < budget, (
        f"counter={inc_cost * 1e9:.0f}ns hist={obs_cost * 1e9:.0f}ns "
        f"batch={batch_wall * 1e3:.1f}ms budget={budget * 1e6:.0f}us")


def test_metrics_disabled_is_a_noop():
    obs_metrics.set_enabled(False)
    try:
        obs_metrics.counter_inc("off.counter")
        obs_metrics.histogram_observe("off.hist", 5)
        snap = obs_metrics.MetricsRegistry.get().snapshot()
        assert "off.counter" not in snap["counters"]
        assert "off.hist" not in snap["histograms"]
    finally:
        obs_metrics.set_enabled(True)


# ---------------------------------------------------------------------------
# flight recorder + postmortem bundles
# ---------------------------------------------------------------------------


def _postmortems(tmp_path, reason):
    return sorted(glob.glob(str(tmp_path / f"postmortem-{reason}-*.json")))


def test_chaos_fatal_device_error_dumps_postmortem(tmp_path):
    """The acceptance bar: a chaos-injected fatal device error produces a
    postmortem bundle containing the failing query's last-K events and a
    registry snapshot."""
    from spark_rapids_tpu.chaos import FaultInjector
    FaultInjector.reset_for_tests()
    FaultInjector.get().force("device.dispatch", "fatal", 1)
    try:
        s = _traced_session(
            **_GENERAL,
            **{"spark.rapids.tpu.obs.postmortemDir": str(tmp_path)})
        with pytest.raises(RuntimeError, match="INTERNAL"):
            _shuffled_query(s).collect()
    finally:
        FaultInjector.reset_for_tests()
    paths = _postmortems(tmp_path, "fatal_device_error")
    assert paths, "fatal device error produced no postmortem bundle"
    pm = json.load(open(paths[0]))
    assert pm["schema"] == "spark-rapids-tpu/postmortem/1"
    assert pm["error_type"] == "RuntimeError"
    assert "INTERNAL" in pm["error"]
    events = {r["event"] for r in pm["flight_events"]}
    assert "chaos.inject" in events and "query.begin" in events, events
    # the chaos note self-tagged with the failing traced query's name
    chaos_notes = [r for r in pm["flight_events"]
                   if r["event"] == "chaos.inject"]
    assert any(r.get("query", "").startswith("query-")
               for r in chaos_notes), chaos_notes
    # the failing query was still active at dump time
    assert any(q.startswith("query-") for q in pm["active_queries"])
    assert pm["metrics"]["schema"] == "spark-rapids-tpu/metrics/1"
    assert "hbm" in pm["engine_state"]


def test_exhausted_transient_retry_dumps_postmortem(tmp_path):
    from spark_rapids_tpu.chaos import FaultInjector
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.failure import with_device_retry
    obs_flight.maybe_configure(RapidsConf(
        {"spark.rapids.tpu.obs.postmortemDir": str(tmp_path)}))
    FaultInjector.reset_for_tests()
    inj = FaultInjector.get()
    inj.force("device.dispatch", "transient", 5)
    from spark_rapids_tpu.chaos import inject
    try:
        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            with_device_retry(lambda: inject("device.dispatch"), None,
                              max_attempts=2, base_ms=1, max_ms=2)
    finally:
        FaultInjector.reset_for_tests()
    paths = _postmortems(tmp_path, "retry_exhausted")
    assert paths, "exhausted retry produced no postmortem bundle"
    pm = json.load(open(paths[0]))
    assert pm["reason"] == "retry_exhausted"
    events = [r for r in pm["flight_events"]
              if r["event"] == "device.retry"]
    assert len(events) == 2, "both healing attempts flight-noted"
    snap = obs_metrics.full_snapshot()
    assert sum(snap["counters"]["device.retries"].values()) == 2


def test_hbm_budget_oom_dumps_postmortem_only_when_it_kills(tmp_path):
    """A genuine budget exhaustion dumps its bundle at the QUERY-DEATH
    point (failure.handle_task_failure) — not at the raise site, where the
    retry framework may still heal it by spilling/splitting."""
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.failure import handle_task_failure
    from spark_rapids_tpu.memory.hbm import HbmBudget, TpuRetryOOM
    conf = RapidsConf(
        {"spark.rapids.tpu.obs.postmortemDir": str(tmp_path)})
    b = HbmBudget.reset_for_tests(budget_bytes=128)
    try:
        with pytest.raises(TpuRetryOOM, match="HBM budget exhausted") as ei:
            b.allocate(1 << 20)
    finally:
        HbmBudget.reset_for_tests()
    # the raise alone dumps nothing (a retry scope could still heal it) ...
    assert not _postmortems(tmp_path, "hbm_oom")
    # ... only the unhealed OOM reaching the task-failure hook dumps
    handle_task_failure(ei.value, conf, exit_on_fatal=False)
    paths = _postmortems(tmp_path, "hbm_oom")
    assert paths, "unhealed HBM budget OOM produced no postmortem bundle"
    pm = json.load(open(paths[0]))
    assert pm["reason"] == "hbm_oom"
    assert any(r["event"] == "hbm.oom" for r in pm["flight_events"])
    assert any(r["event"] == "hbm.oom_unhealed"
               for r in pm["flight_events"])
    snap = obs_metrics.full_snapshot()
    assert sum(snap["counters"]["hbm.oom_events"].values()) == 1


def test_chaos_injected_retry_oom_does_not_spam_postmortems(tmp_path):
    """A chaos/test-hook TpuRetryOOM at hbm.alloc is HEALABLE by design
    (the retry framework splits) — it never dumps a bundle, even if it
    reaches the task-failure hook (no budget_exhausted marker)."""
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.failure import handle_task_failure
    from spark_rapids_tpu.memory.hbm import HbmBudget, TpuRetryOOM
    conf = RapidsConf(
        {"spark.rapids.tpu.obs.postmortemDir": str(tmp_path)})
    b = HbmBudget.reset_for_tests(budget_bytes=1 << 30)
    try:
        b.force_retry_oom(1)
        with pytest.raises(TpuRetryOOM) as ei:
            b.allocate(64)
    finally:
        HbmBudget.reset_for_tests()
    handle_task_failure(ei.value, conf, exit_on_fatal=False)
    assert not _postmortems(tmp_path, "hbm_oom")


def test_flight_ring_is_bounded_and_ordered():
    for i in range(2000):
        obs_flight.note("flood", i=i)
    recs = obs_flight.snapshot()
    assert len(recs) == 512  # default ring bound
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs) and seqs[-1] == 2000
    assert obs_flight.snapshot(last_k=16)[0]["i"] == 2000 - 16


def test_postmortem_without_dir_is_a_noop(tmp_path):
    assert obs_flight.postmortem("fatal_device_error",
                                 RuntimeError("x")) is None
    assert not list(tmp_path.iterdir())
