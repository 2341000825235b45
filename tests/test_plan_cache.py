"""Scheduler-owned plan cache (ISSUE 20): fingerprint hit/miss semantics,
parameter-slot literal re-binding (bit-identity vs cold-planned), FileScan
caching with file-set identity, LRU bounds, conf-change / cached-relation /
file-set invalidation, cross-session sharing through the one scheduler
instance, an N=4 concurrent-session race soak with a resource-baseline
leak check, and the failed-planning no-half-insert guarantee."""

import threading

import pytest

import benchmarks.tpch as tpch
import spark_rapids_tpu.functions as F
from spark_rapids_tpu.memory.cleaner import MemoryCleaner
from spark_rapids_tpu.memory.hbm import HbmBudget
from spark_rapids_tpu.serving.plan_cache import (fingerprint,
                                                 plan_relevant_conf)
from spark_rapids_tpu.serving.scheduler import QueryScheduler
from spark_rapids_tpu.session import TpuSession


@pytest.fixture(autouse=True)
def _fresh_scheduler():
    QueryScheduler.reset_for_tests()
    yield
    QueryScheduler.reset_for_tests()


def _cache():
    return QueryScheduler.get().plan_cache


def _rows(n=64):
    return [{"k": i % 8, "v": float(i)} for i in range(n)]


# ---------------------------------------------------------------------------
# hit / miss
# ---------------------------------------------------------------------------

def test_repeat_submission_hits():
    s = TpuSession({})
    df = s.createDataFrame(_rows(), num_partitions=2)
    q = df.filter(F.col("v") > 10.0).groupBy("k").agg(
        F.sum(F.col("v")).alias("sv"))
    first = q.collect()
    assert s._last_plan_cache == "miss"
    again = q.collect()
    assert s._last_plan_cache == "hit"
    assert sorted(map(str, first)) == sorted(map(str, again))
    st = _cache().stats()
    assert st["hits"] == 1 and st["misses"] == 1 and st["entries"] == 1


def test_different_plan_shape_misses():
    s = TpuSession({})
    df = s.createDataFrame(_rows(), num_partitions=2)
    df.filter(F.col("v") > 1.0).collect()
    assert s._last_plan_cache == "miss"
    # extra projection → different fingerprint, not a stale hit
    df.filter(F.col("v") > 1.0).select("k").collect()
    assert s._last_plan_cache == "miss"
    assert _cache().stats()["entries"] == 2


def test_param_slot_rebind_bit_identity_vs_cold():
    """Literal-varying resubmissions hit ONE entry; every hit's result is
    bit-identical to a cold-planned run of the same query."""
    import pyarrow as pa
    s = TpuSession({})
    t = pa.table({"k": list(range(32)), "v": [float(i) for i in range(32)]})
    df = s.createDataFrame(t, num_partitions=2)

    def q(cut):
        return df.filter(F.col("v") >= cut).select("v")

    cached = {}
    for cut in (4.0, 11.0, 27.0, 4.0):
        cached[cut] = q(cut).to_arrow()
    assert s._last_plan_cache == "hit"
    assert _cache().stats()["entries"] == 1
    assert _cache().stats()["hits"] == 3
    s.conf.set("spark.rapids.tpu.plan.cache.enabled", "false")
    for cut, table in cached.items():
        cold = q(cut).to_arrow()
        assert s._last_plan_cache == "off"
        assert cold.equals(table), f"cut={cut}: cached != cold-planned"


def test_rebound_literal_changes_result():
    s = TpuSession({})
    df = s.createDataFrame(_rows(64), num_partitions=2)
    n_lo = len(df.filter(F.col("v") > 10.0).collect())
    n_hi = len(df.filter(F.col("v") > 50.0).collect())
    assert s._last_plan_cache == "hit"
    assert n_lo == 53 and n_hi == 13  # the re-bound literal took effect


def test_cache_off_conf_plans_fresh():
    s = TpuSession({"spark.rapids.tpu.plan.cache.enabled": "false"})
    df = s.createDataFrame(_rows(), num_partitions=2)
    df.filter(F.col("v") > 1.0).collect()
    df.filter(F.col("v") > 1.0).collect()
    assert s._last_plan_cache == "off"
    st = _cache().stats()
    assert st["entries"] == 0 and st["hits"] == 0


# ---------------------------------------------------------------------------
# FileScan plans: cacheable, keyed on file identity
# ---------------------------------------------------------------------------

def test_file_scan_hits_and_rebinds_pushed_filters(tmp_path):
    """FileScan plans cache: file/row-group pruning happens at EXECUTION
    time, so a hit with a different probe literal must re-bind the pushed
    filter (and recompute the derived arrow filter) — probe B's rows, not
    a replay of probe A's pruning."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"k": list(range(64)),
                             "v": [float(i) for i in range(64)]}), path)
    s = TpuSession({})
    df = s.read.parquet(path)
    got = df.filter(F.col("k") == 3).collect()
    assert s._last_plan_cache == "miss"
    assert [r["v"] for r in got] == [3.0]
    got = df.filter(F.col("k") == 41).collect()
    assert s._last_plan_cache == "hit"
    assert [r["v"] for r in got] == [41.0]
    st = _cache().stats()
    assert st["entries"] == 1 and st["hits"] == 1


def test_file_rewrite_invalidates_fileset(tmp_path):
    """A table swap (same path, new bytes) changes the scan signature: the
    stale entry can never be served again, and inserting the re-planned
    entry evicts it (counted as a fileset invalidation)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"k": list(range(16)),
                             "v": [float(i) for i in range(16)]}), path)
    s = TpuSession({})
    q = s.read.parquet(path).filter(F.col("k") >= 0)
    assert len(q.collect()) == 16
    q.collect()
    assert s._last_plan_cache == "hit"
    # rewrite the file under the same path with different contents
    pq.write_table(pa.table({"k": list(range(40)),
                             "v": [float(i) for i in range(40)]}), path)
    before = _cache().stats()
    q2 = s.read.parquet(path).filter(F.col("k") >= 0)
    got = q2.collect()
    assert s._last_plan_cache == "miss"  # stale scan signature can't hit
    assert len(got) == 40
    st = _cache().stats()
    assert st["invalidations"] == before["invalidations"] + 1


# ---------------------------------------------------------------------------
# LRU bound
# ---------------------------------------------------------------------------

def test_lru_eviction_bounds_entries():
    s = TpuSession({"spark.rapids.tpu.plan.cache.maxEntries": "2"})
    df = s.createDataFrame(_rows(), num_partitions=2)
    cols = [None, "k", "v"]
    for c in cols:  # three distinct shapes through a capacity-2 cache
        (df if c is None else df.select(c)).collect()
    st = _cache().stats()
    assert st["entries"] == 2 and st["capacity"] == 2
    # the first shape (LRU victim) re-plans; the last still hits
    df.select("v").collect()
    assert s._last_plan_cache == "hit"
    df.collect()
    assert s._last_plan_cache == "miss"


# ---------------------------------------------------------------------------
# invalidation
# ---------------------------------------------------------------------------

def test_plan_relevant_conf_change_invalidates():
    s = TpuSession({})
    df = s.createDataFrame(_rows(256), num_partitions=4)
    q = df.repartition(4, "k").groupBy("k").agg(F.sum(F.col("v")).alias("s"))
    q.collect()
    q.collect()
    assert s._last_plan_cache == "hit"
    s.conf.set("spark.sql.shuffle.partitions", "3")
    st = _cache().stats()
    assert st["entries"] == 0 and st["invalidations"] >= 1
    q.collect()
    assert s._last_plan_cache == "miss"  # re-planned under the new conf


def test_ansi_and_timezone_conf_changes_invalidate():
    """The TL032 bug class: semantics-changing confs (ANSI mode, session
    time zone) must invalidate — a plan compiled under the old value can
    never serve the new one."""
    s = TpuSession({})
    df = s.createDataFrame(_rows(), num_partitions=2)
    df.select("v").collect()
    assert _cache().stats()["entries"] == 1
    s.conf.set("spark.sql.ansi.enabled", "true")
    assert _cache().stats()["entries"] == 0
    df.select("v").collect()
    assert s._last_plan_cache == "miss"
    s.conf.set("spark.sql.session.timeZone", "America/Los_Angeles")
    st = _cache().stats()
    assert st["entries"] == 0 and st["invalidations"] >= 2


def test_non_plan_conf_change_keeps_entries():
    s = TpuSession({})
    df = s.createDataFrame(_rows(), num_partitions=2)
    df.select("v").collect()
    s.conf.set("spark.rapids.tpu.trace.tag", "whatever")
    s.conf.set("spark.rapids.tpu.obs.metrics.enabled", "true")
    assert _cache().stats()["entries"] == 1
    df.select("v").collect()
    assert s._last_plan_cache == "hit"


def test_cached_relation_unpersist_invalidates():
    s = TpuSession({})
    # .cache() materializes the source plan (its OWN cache entry over the
    # LocalRelation) — only the entry over the CachedRelation must drop
    df = s.createDataFrame(_rows(), num_partitions=2).cache()
    df.select("v").collect()
    df.select("v").collect()
    assert s._last_plan_cache == "hit"
    before = _cache().stats()
    df._plan.unpersist()
    st = _cache().stats()
    assert st["entries"] == before["entries"] - 1
    assert st["invalidations"] == before["invalidations"] + 1


def test_fingerprint_conf_sig_excludes_nonplan_keys():
    c1 = TpuSession({"spark.rapids.tpu.trace.enabled": "true"})._rapids_conf()
    c2 = TpuSession({})._rapids_conf()
    assert plan_relevant_conf(c1) == plan_relevant_conf(c2)
    c3 = TpuSession({"spark.sql.shuffle.partitions": "3"})._rapids_conf()
    assert plan_relevant_conf(c3) != plan_relevant_conf(c2)


def test_fingerprint_punches_filter_literals_only():
    s = TpuSession({})
    df = s.createDataFrame(_rows(), num_partitions=2)
    conf = s._rapids_conf()
    f1 = fingerprint(df.filter(F.col("v") > 3.0)._plan, conf)
    f2 = fingerprint(df.filter(F.col("v") > 9.0)._plan, conf)
    assert f1.key == f2.key  # literal value is a slot, not key material
    assert [p.value for p in f1.params] == [3.0]
    assert [p.value for p in f2.params] == [9.0]


def test_failed_planning_leaves_no_half_inserted_entry(monkeypatch):
    """A submission cancelled/shed/crashed mid-planning must leave the
    cache exactly as it was — no half-inserted entry, and the cache stays
    functional afterwards (the TL020 half-registered-artifact sweep)."""
    import spark_rapids_tpu.plan.planner as planner_mod
    from spark_rapids_tpu.obs import metrics as obs_metrics

    def miss_counter():
        cells = obs_metrics.MetricsRegistry.get().snapshot()[
            "counters"].get("plan.cache_miss", {})
        return sum(cells.values())

    s = TpuSession({})
    df = s.createDataFrame(_rows(), num_partitions=2)
    df.select("k").collect()
    before = _cache().stats()
    m0 = miss_counter()
    real = planner_mod.plan_physical

    def boom(plan, conf):
        raise RuntimeError("cancelled mid-planning")

    monkeypatch.setattr(planner_mod, "plan_physical", boom)
    with pytest.raises(Exception, match="cancelled mid-planning"):
        df.select("v").collect()
    st = _cache().stats()
    # the lookup before planning legitimately counts an internal miss, but
    # nothing may have been inserted and no attributed miss counter fired
    assert st["entries"] == before["entries"]
    assert st["per_entry_hits"].keys() == before["per_entry_hits"].keys()
    assert miss_counter() == m0
    monkeypatch.setattr(planner_mod, "plan_physical", real)
    df.select("v").collect()  # the cache still works after the failure
    assert s._last_plan_cache == "miss"
    df.select("v").collect()
    assert s._last_plan_cache == "hit"


# ---------------------------------------------------------------------------
# cross-session sharing
# ---------------------------------------------------------------------------

def test_sessions_share_one_cache():
    import pyarrow as pa
    t = pa.table({"v": [float(i) for i in range(16)]})
    s1 = TpuSession({})
    df = s1.createDataFrame(t, num_partitions=2)
    df.filter(F.col("v") > 5.0).collect()
    assert s1._last_plan_cache == "miss"
    # a DIFFERENT session frontend submitting the same frame hits the one
    # scheduler-owned entry (same relation identity, same conf signature)
    from spark_rapids_tpu.session import DataFrame
    s2 = TpuSession({})
    df2 = DataFrame(df._plan, s2)
    df2.filter(F.col("v") > 8.0).collect()
    assert s2._last_plan_cache == "hit"
    st = _cache().stats()
    assert st["entries"] == 1 and st["hits"] == 1


# ---------------------------------------------------------------------------
# concurrent race soak
# ---------------------------------------------------------------------------

def test_concurrent_sessions_race_soak_no_leaks():
    """N=4 sessions hammer the same query shape with varying literals:
    every result must be correct (the re-bound literal, not a racing
    query's), the cache must converge to one entry, the 24 submissions
    must partition exactly into hits + misses, and device resources must
    return to baseline."""
    import pyarrow as pa
    baseline = {"cleaner": len(MemoryCleaner.get().live_resources()),
                "hbm": HbmBudget.get().used}
    t = pa.table({"k": [i % 8 for i in range(256)],
                  "v": [float(i) for i in range(256)]})
    s0 = TpuSession({})
    df = s0.createDataFrame(t, num_partitions=2)
    from spark_rapids_tpu.session import DataFrame
    sessions = [s0] + [TpuSession({}) for _ in range(3)]
    errors = []

    def worker(wid, s):
        wdf = DataFrame(df._plan, s)
        try:
            for it in range(6):
                cut = float((wid * 6 + it) % 20)
                got = len(wdf.filter(F.col("v") >= cut).collect())
                want = sum(1 for i in range(256) if float(i) >= cut)
                assert got == want, (wid, it, cut, got, want)
        except Exception as e:  # noqa: BLE001 — surface on main thread
            errors.append(f"worker {wid}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=worker, args=(i, s))
               for i, s in enumerate(sessions)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=300)
    assert not errors, errors
    st = _cache().stats()
    assert st["entries"] == 1
    assert st["hits"] + st["misses"] == 24  # exact hit-count partition
    assert st["hits"] >= 20  # first-planner race may double-plan, rest hit
    assert len(MemoryCleaner.get().live_resources()) == baseline["cleaner"]
    assert HbmBudget.get().used == baseline["hbm"]


# ---------------------------------------------------------------------------
# one planning entry: every way to a physical plan builds the same tree
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tpch_tables():
    s = tpch.make_session(tpu=True)
    return s, tpch.load_tables(s, 400, parts=2)


@pytest.mark.parametrize("name", [f"q{i}" for i in range(1, 23)])
def test_every_planning_entry_builds_the_same_tree(name, tpch_tables):
    """`plan_query`, the scheduler's planning step (plan cache off, a miss,
    the hit's re-bound clone) and `DataFrame.explain()` give one physical
    tree, and it is the tree of the three passes in the order spelled
    here — the only place outside `plan/overrides.py` that spells it.
    Planning only: nothing executes."""
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.plan.optimizer import optimize_logical
    from spark_rapids_tpu.plan.overrides import (TpuOverrides, plan_cpu,
                                                 plan_query)
    from spark_rapids_tpu.plan.planner import plan_physical
    from spark_rapids_tpu.serving.plan_cache import build_or_fetch
    s, tables = tpch_tables
    df = tpch.QUERIES[name](s, tables)
    conf = s._rapids_conf()
    optimized, rules = optimize_logical(df._plan, conf)
    cpu_plan = plan_physical(optimized, conf)
    cpu_tree = cpu_plan.tree_string()
    want = TpuOverrides.apply(cpu_plan, conf).tree_string()
    assert "Tpu" in want and want != cpu_tree  # the override pass ran

    cpu_plan, _, cpu_rules = plan_cpu(df._plan, conf)
    assert (cpu_plan.tree_string(), cpu_rules) == (cpu_tree, rules)
    final, _, got_rules = plan_query(df._plan, conf)
    assert (final.tree_string(), got_rules) == (want, rules)

    sched = QueryScheduler.get()
    off = RapidsConf({**s._settings,
                      "spark.rapids.tpu.plan.cache.enabled": "false"})
    for status, c in (("off", off), ("miss", conf), ("hit", conf)):
        plan, got_status, got_rules = build_or_fetch(s, sched, df._plan, c)
        assert got_status == status
        assert (plan.tree_string(), got_rules) == (want, rules), status

    explained = df.explain()  # planCache=…, then the tree or, with rules,
    # the rules and the logical plan before it under a heading
    _, heading, tree = explained.partition("== Physical Plan ==\n")
    assert (tree if heading else explained.split("\n", 1)[1]) == want


# ---------------------------------------------------------------------------
# bit-identity across the TPC-H sweep (cached vs fresh)
# ---------------------------------------------------------------------------

def test_tpch_sweep_cached_bit_identical():
    """q1/q3/q6/q18 + a dictionary-coded string query: the second (cached)
    run of each is bit-identical to the first, and both match a
    cache-off cold plan."""
    s = tpch.make_session(tpu=True)
    tables = tpch.load_tables(s, 2_000, parts=2)
    queries = {name: tpch.QUERIES[name] for name in
               ("q1", "q3", "q6", "q18")}
    # dictionary-coded string query: group by a string key
    queries["dict_string"] = (
        lambda _s, tb: tb["customer"]
        .groupBy("c_mktsegment")
        .agg(F.count(F.col("c_custkey")).alias("n")))
    for name, qfn in queries.items():
        first = qfn(s, tables).to_arrow()
        again = qfn(s, tables).to_arrow()
        assert s._last_plan_cache == "hit", name
        assert again.equals(first), f"{name}: cached run != first run"
        s.conf.set("spark.rapids.tpu.plan.cache.enabled", "false")
        cold = qfn(s, tables).to_arrow()
        s.conf.set("spark.rapids.tpu.plan.cache.enabled", "true")
        assert cold.equals(first), f"{name}: cached != cache-off cold plan"


# ---------------------------------------------------------------------------
# observability surface
# ---------------------------------------------------------------------------

def test_cache_counters_and_snapshot():
    from spark_rapids_tpu.obs import metrics as obs_metrics

    def counter(name):
        cells = obs_metrics.MetricsRegistry.get().snapshot()[
            "counters"].get(name, {})
        return sum(cells.values())

    h0, m0 = counter("plan.cache_hit"), counter("plan.cache_miss")
    s = TpuSession({})
    df = s.createDataFrame(_rows(), num_partitions=2)
    df.select("v").collect()
    df.select("v").collect()
    assert counter("plan.cache_miss") == m0 + 1
    assert counter("plan.cache_hit") == h0 + 1
    snap = QueryScheduler.get().snapshot()
    assert snap["plan_cache"]["entries"] == 1
    assert snap["plan_cache"]["per_entry_hits"]


def test_explain_reports_plan_cache_status(capsys):
    s = TpuSession({})
    df = s.createDataFrame(_rows(), num_partitions=2)
    q = df.filter(F.col("v") > 1.0).select("k")
    txt = q.explain()
    assert "planCache=miss" in txt
    q.collect()
    txt = q.explain()
    assert "planCache=hit" in txt
    s.conf.set("spark.rapids.tpu.plan.cache.enabled", "false")
    assert "planCache=off" in q.explain()


def test_plan_build_span_lands_in_profile(tmp_path):
    s = TpuSession({"spark.rapids.tpu.trace.enabled": "true",
                    "spark.rapids.tpu.trace.dir": str(tmp_path)})
    df = s.createDataFrame(_rows(), num_partitions=2)
    df.select("v").collect()
    prof = s.last_query_profile()
    assert prof is not None

    def find(node, name):
        if node.get("name") == name:
            return node
        for c in node.get("children") or ():
            got = find(c, name)
            if got is not None:
                return got
        return None

    span = find(prof["spans"], "plan.build")
    assert span is not None and span["cat"] == "plan"
    assert span["dur_ns"] is None or span["dur_ns"] >= 0
