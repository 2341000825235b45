"""Phase spans of the served path (ISSUE 24, docs/observability.md "Span
model"): `obs.phase` / `obs.phase_add`, the always-on per-query summary
(`session.last_query_phases()`, `obs.metrics.recent_queries()`), the
profiler annotations (`srt.<phase>`), the scan's `uploadTime`, XLA compiles
attributed to the query that paid them, and the benchmark's two readers of
the summary ring.
"""

import glob
import json
import os
import threading
import time
from types import SimpleNamespace

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu import obs, profiling
from spark_rapids_tpu.execs import opjit
from spark_rapids_tpu.obs import tracer as obs_tracer
from spark_rapids_tpu.serving.query_context import QueryContext, bind
from spark_rapids_tpu.session import TpuSession

#: clock granularity allowance for cpu <= wall (thread_time_ns and
#: perf_counter_ns are read one after the other)
EPS_NS = 200_000
#: phases of these tests that open with no parent phase
ROOTS = ("query", "outer")


@pytest.fixture(autouse=True)
def _fresh():
    obs_tracer.QueryTracer.reset_for_tests()
    yield
    profiling.set_trace_annotations(False)
    obs_tracer.QueryTracer.reset_for_tests()


def _cached(s, n=3000, parts=3):
    t = pa.table({"k": pa.array([i % 5 for i in range(n)], type=pa.int64()),
                  "v": pa.array([float(i) for i in range(n)])})
    return s.createDataFrame(t, num_partitions=parts).device_cache()


def _grouped(s, parts=3, lo=10.0, df=None):
    df = df if df is not None else _cached(s, parts=parts)
    return (df.filter(F.col("v") > lo).groupBy("k")
            .agg(F.sum("v").alias("sv"), F.count("*").alias("c")).sort("k"))


def _check_cells(phases, all_sampled=False):
    """Always-on the CPU clock is read for a parentless phase (the root)
    and for a wait; for every phase only on the traced / annotated path.
    What is added after the fact (an XLA compile, the admission wait)
    carries none."""
    for name, c in phases.items():
        assert 0 <= c["child_wall_ns"] <= c["wall_ns"], (name, c)
        assert c["count"] >= 1
        if name not in ("xla.compile", "sched.admit_wait") and (
                all_sampled or c["cat"] == "wait" or name in ROOTS):
            assert 0 <= c["cpu_ns"] <= c["wall_ns"] + EPS_NS * c["count"], \
                (name, c)
        else:
            assert c["cpu_ns"] is None, (name, c)


# ---------------------------------------------------------------------------
# (a) arithmetic: nesting, child wall, cpu, phase_add, laps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("inner", ["phase", "phase_add", "lap"])
def test_phase_arithmetic(inner):
    q = QueryContext("t", session_id="s")
    with bind(q):
        with obs.phase("outer"):
            if inner == "phase":
                for _ in range(2):
                    with obs.phase("in"):
                        time.sleep(0.005)
                with obs.phase("blocked", cat="wait"):
                    time.sleep(0.005)
            elif inner == "phase_add":
                obs.phase_add("in", 2, 3_000_000, 1_000_000)
                time.sleep(0.005)
            else:
                laps = obs.PhaseLaps()
                for _ in range(2):
                    with laps.lap("in"):
                        # what a launch reports from inside (an XLA
                        # compile) is the lap's child, not the outer's
                        obs.phase_add("deep", 1, 1_000_000, None)
                        time.sleep(0.003)
                assert "in" not in q.phase_table()      # nothing per batch
                laps.flush()
    t = q.phase_table()
    if inner == "phase_add":            # the caller's own CPU reading stands
        assert t["in"] == {"count": 2, "wall_ns": 3_000_000,
                           "cpu_ns": 1_000_000, "child_wall_ns": 0,
                           "cat": "phase"}
    _check_cells({n: c for n, c in t.items()
                  if (inner, n) != ("phase_add", "in")})
    assert t["outer"]["count"] == 1 and t["in"]["count"] == 2
    assert t["outer"]["cat"] == "phase"
    if inner == "phase":
        assert t["blocked"]["cat"] == "wait"
        assert t["outer"]["child_wall_ns"] == (
            t["in"]["wall_ns"] + t["blocked"]["wall_ns"])
        # a sleeping thread is off the CPU: wall - cpu is most of the wall
        assert t["blocked"]["wall_ns"] - t["blocked"]["cpu_ns"] > 4_000_000
        assert t["outer"]["wall_ns"] - t["outer"]["cpu_ns"] > 12_000_000
    elif inner == "phase_add":
        assert t["outer"]["child_wall_ns"] == 3_000_000
    else:
        assert t["deep"]["count"] == 2
        assert t["in"]["child_wall_ns"] == 2_000_000
        assert t["outer"]["child_wall_ns"] == t["in"]["wall_ns"]
    assert t["outer"]["wall_ns"] - t["outer"]["child_wall_ns"] >= 0


# ---------------------------------------------------------------------------
# (e) off path: no bound context, annotations off
# ---------------------------------------------------------------------------


def test_off_path_is_the_shared_null_span():
    assert obs.phase("anything") is obs_tracer._NULL_SPAN
    assert obs.phase("other", cat="wait", k=1) is obs_tracer._NULL_SPAN
    obs.phase_add("anything", 1, 10, 10)        # nothing bound: dropped
    with obs.phase("anything") as ph:
        assert ph is None


# ---------------------------------------------------------------------------
# (b) the compiled stage's phases and its launches in calls_by_kind
# ---------------------------------------------------------------------------


def test_compiled_stage_phases_and_dispatch_counter():
    s = TpuSession({})
    q = _grouped(s)
    before = opjit.cache_stats()["calls_by_kind"].get("compiledagg", 0)
    rows = q.collect()
    assert [r["k"] for r in rows] == [0, 1, 2, 3, 4]
    after = opjit.cache_stats()["calls_by_kind"].get("compiledagg", 0)
    assert after - before == 3
    summ = s.last_query_phases()
    ph = summ["phases"]
    _check_cells(ph)
    assert {n: ph[n]["count"] for n in ("stage.collect", "stage.launch",
                                        "stage.fetch", "stage.assemble")} \
        == {"stage.collect": 1, "stage.launch": 3, "stage.fetch": 1,
            "stage.assemble": 1}
    assert ph["stage.fetch"]["cat"] == "wait"
    assert ph["sched.admit_wait"]["cat"] == "wait"
    assert ph["query"]["count"] == ph["plan.build"]["count"] \
        == ph["result.drain"]["count"] == 1
    # the root covers what the query did; the phases cover the root
    assert ph["query"]["wall_ns"] <= summ["wall_ns"]
    assert ph["query"]["child_wall_ns"] == (
        ph["plan.build"]["wall_ns"] + ph["result.drain"]["wall_ns"])
    assert summ["t_end_ns"] - summ["t_begin_ns"] == summ["wall_ns"]
    assert summ["cpu_ns"] == ph["query"]["cpu_ns"]
    assert abs(summ["t_begin_unix_ns"] - time.time_ns()) < 600e9
    assert summ["failed"] is False and summ["annotated"] is False
    # the same interval as qctx.admit_wait_ms, to the float's rounding
    assert abs(summ["admit_wait_ns"]
               - ph["sched.admit_wait"]["wall_ns"]) <= 1
    assert summ["admit_wait_ns"] > 0
    assert obs.metrics.recent_queries(1) == [summ]
    totals = obs.metrics.full_snapshot()["phases"]
    assert totals["stage.launch"]["count"] >= 3
    assert totals["stage.launch"]["queries"] >= 1


# ---------------------------------------------------------------------------
# (g) compiles land in the summary of the query that paid them
# ---------------------------------------------------------------------------


def test_new_literal_compiles_its_repeat_does_not():
    s = TpuSession({})
    df = _cached(s)
    _grouped(s, lo=10.0, df=df).collect()
    # same plan shape over the same table, another literal: one more
    # program behind a plan-cache hit (PERF.md Open question 1)
    q = _grouped(s, lo=11.5, df=df)
    c0 = obs.metrics.full_snapshot()["counters"].get("xla.compiles", {})
    q.collect()
    first = s.last_query_phases()
    assert first["compiles"] >= 1 and first["compile_ns"] > 0
    assert first["phases"]["xla.compile"]["count"] == first["compiles"]
    c1 = obs.metrics.full_snapshot()["counters"]["xla.compiles"]
    assert sum(c1.values()) - sum(c0.values()) >= first["compiles"]
    q.collect()
    repeat = s.last_query_phases()
    assert repeat["compiles"] == 0 and "xla.compile" not in repeat["phases"]


# ---------------------------------------------------------------------------
# (c) two sessions on two threads: each summary holds its own phases only
# ---------------------------------------------------------------------------


def test_two_sessions_two_threads_do_not_mix():
    sessions = [TpuSession({}), TpuSession({})]
    parts = [2, 4]
    queries = [_grouped(s, parts=p) for s, p in zip(sessions, parts)]
    for q in queries:
        q.collect()                       # compile outside the race
    start = threading.Barrier(2)
    errors = []

    def run(q):
        try:
            start.wait(timeout=30)
            for _ in range(3):
                q.collect()
        except Exception as e:  # noqa: BLE001 — reported by the assert below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(q,)) for q in queries]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors and not any(t.is_alive() for t in threads)
    for s, p in zip(sessions, parts):
        summ = s.last_query_phases()
        assert summ["session"] == s._session_id
        assert summ["phases"]["stage.launch"]["count"] == p
        assert summ["phases"]["query"]["count"] == 1
        _check_cells(summ["phases"])
    mine = {s._session_id: p for s, p in zip(sessions, parts)}
    for summ in obs.metrics.recent_queries(6):
        assert summ["phases"]["stage.launch"]["count"] == mine[summ["session"]]


# ---------------------------------------------------------------------------
# (d) the profiler's own clock: srt.* annotations, and the ring's anchor
# ---------------------------------------------------------------------------


def test_annotations_in_xplane_and_unix_anchor_in_exports(tmp_path):
    from jax.profiler import ProfileData
    s = TpuSession({"spark.rapids.tpu.trace.enabled": "true",
                    "spark.rapids.tpu.trace.dir": str(tmp_path / "obs")})
    q = _grouped(s)
    q.collect()                                     # compile before tracing
    prof = profiling.TpuProfiler(str(tmp_path / "xprof"))
    with prof:
        assert profiling._PROFILING_ACTIVE
        q.collect()
    assert not profiling._PROFILING_ACTIVE
    summ = s.last_query_phases()
    assert summ["annotated"] is True
    assert summ["phases"]["stage.launch"]["count"] == 3
    _check_cells(summ["phases"], all_sampled=True)
    assert abs(prof.t0_unix_ns - summ["t_begin_unix_ns"]) < 60e9

    files = glob.glob(os.path.join(prof.path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files
    spans, zero_unix_ns = {}, None
    for plane in ProfileData.from_file(files[-1]).planes:
        for ln in plane.lines:
            for e in ln.events:
                if e.name == profiling.ANCHOR_SPAN:
                    zero_unix_ns = dict(e.stats)["unix_ns"] - e.start_ns
                elif e.name.startswith("srt."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    # the trace's zero lies inside start_trace, after t0_unix_ns; shifted by
    # it, the query's root annotation starts at the summary's begin
    assert 0 <= zero_unix_ns - prof.t0_unix_ns < 30e9
    (q0, q1), = spans["srt.query"]
    assert abs(zero_unix_ns + q0 - summ["t_begin_unix_ns"]) < 50e6
    launches = spans["srt.stage.launch"]
    assert len(launches) == 3
    assert all(q0 <= a and b <= q1 for a, b in launches)
    assert {"srt.plan.build", "srt.result.drain", "srt.stage.collect",
            "srt.stage.fetch", "srt.stage.assemble"} <= set(spans)

    # the ring's exports carry the realtime anchor of their ts 0
    bundle = s.last_query_profile()
    assert abs(bundle["t0_unix_ns"] - summ["t_begin_unix_ns"]) < 60e9
    chrome = json.load(open(bundle["artifacts"]["chrome_trace"]))
    assert chrome["otherData"]["t0_unix_ns"] == bundle["t0_unix_ns"]
    # traced: one ring span per batch, nested under the query's spans
    names = [e["name"] for e in chrome["traceEvents"] if e.get("ph") == "B"]
    assert names.count("stage.launch") == 3 and "result.drain" in names


# ---------------------------------------------------------------------------
# (f) the scan's upload timer nests inside decodeTime
# ---------------------------------------------------------------------------


def test_scan_upload_time_nests_in_decode_time(tmp_path):
    n = 5000
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({
        "k": pa.array([i % 3 for i in range(n)], type=pa.int32()),
        "x": pa.array([float(i) for i in range(n)]),
        "y": pa.array([None if i % 7 == 0 else float(i) for i in range(n)]),
    }), path, row_group_size=2048)
    s = TpuSession({})
    rows = (s.read.parquet(path).filter(F.col("x") >= 0.0).groupBy("k")
            .agg(F.sum("x").alias("sx"), F.count("y").alias("cy"))
            .sort("k").collect())
    assert sum(r["cy"] for r in rows) == n - len(range(0, n, 7))
    scan = {}
    for vals in s.last_query_metrics("MODERATE").values():
        for k in ("decodeTime", "uploadTime"):
            scan[k] = scan.get(k, 0) + vals.get(k, 0)
    assert 0 < scan["uploadTime"] <= scan["decodeTime"]
    ph = s.last_query_phases()["phases"]
    _check_cells(ph)
    groups = {n_: ph[n_]["count"] for n_ in (
        "scan.page_walk", "scan.admit", "scan.upload", "scan.launch")}
    assert groups == dict.fromkeys(groups, 3)
    assert ph["scan.admit"]["cat"] == "wait"
    # the scan's phases are children of the stage's pass 1 here
    assert ph["stage.collect"]["child_wall_ns"] >= sum(
        ph[n_]["wall_ns"] for n_ in groups)


# ---------------------------------------------------------------------------
# (h) the benchmark's readers of the summary ring
# ---------------------------------------------------------------------------


def _summary(annotated, wall, cpu, fetch, launch, batches, self_ns):
    def cell(count, wall_ns, cpu_ns, child=0, cat="phase"):
        return {"count": count, "wall_ns": wall_ns, "cpu_ns": cpu_ns,
                "child_wall_ns": child, "cat": cat}

    return {"annotated": annotated, "phases": {
        "query": cell(1, wall, cpu, wall - self_ns),
        "sched.admit_wait": cell(1, 7_000_000, 0, cat="wait"),
        "stage.launch": cell(batches, launch, launch),
        "stage.fetch": cell(1, fetch, 0, cat="wait")}}


@pytest.mark.parametrize("case", ["plain_preferred", "all_annotated",
                                  "ring_too_short", "no_ring"])
def test_benchmark_phase_readers(monkeypatch, case):
    from chipbench.readers import phase_ms, phase_share
    ms = 1_000_000
    ring = [
        _summary(False, 999 * ms, 0, 0, 0, 1, 0),            # before the window
        _summary(True, 200 * ms, 60 * ms, 100 * ms, 40 * ms, 4, 20 * ms),
        _summary(False, 100 * ms, 45 * ms, 50 * ms, 30 * ms, 2, 5 * ms),
        _summary(False, 100 * ms, 35 * ms, 50 * ms, 30 * ms, 4, 15 * ms)]
    n_window = 3
    if case == "all_annotated":
        for q in ring:
            q["annotated"] = True
    if case == "ring_too_short":
        ring = ring[2:]
    if case == "no_ring":
        monkeypatch.delattr(obs.metrics, "recent_queries")
    else:
        monkeypatch.setattr(obs.metrics, "recent_queries",
                            lambda n=None: ring[-n:] if n else list(ring))
    ctx = SimpleNamespace(records=[object()] * n_window)
    launch = phase_ms.read(ctx, phase="stage.launch", per="count")
    fetch = phase_ms.read(ctx, phase="stage.fetch", per="query")
    blocked = phase_share.read(ctx, what="blocked")
    unattributed = phase_share.read(ctx, what="unattributed")
    if case in ("ring_too_short", "no_ring"):
        assert (launch, fetch, blocked, unattributed) == (None,) * 4
        return
    assert phase_ms.read(ctx, phase="stage.collect", per="query") is None
    if case == "plain_preferred":
        # the two unannotated queries of the window
        assert launch == pytest.approx(60 / 6)
        assert fetch == pytest.approx(50.0)
        # running wall 2 x (100 - 50), of which off the CPU (50-45)+(50-35)
        assert blocked == pytest.approx(100.0 * 20 / 100)
        assert unattributed == pytest.approx(100.0 * 20 / 200)
    else:
        assert launch == pytest.approx(100 / 10)
        assert fetch == pytest.approx(200 / 3)
        assert blocked == pytest.approx(100.0 * (40 + 5 + 15) / 200)
        assert unattributed == pytest.approx(100.0 * 40 / 400)
    with pytest.raises(ValueError):
        phase_ms.read(ctx, phase="stage.launch", per="batch")
    with pytest.raises(ValueError):
        phase_share.read(ctx, what="idle")
