"""TPC-H Q3 as the specification writes it (ISSUE 27): three device-resident
tables through session -> scheduler -> plan -> execs, held to the benchmark's
plain reference (`chipbench/queries/q3.py`); the phases and counters of the
join / segment / exchange path (docs/observability.md "Span model"); and the
cell `star-sf1-q3-stream` as BENCHMARK.json declares it.
"""

import threading
from types import SimpleNamespace

import numpy as np
import pytest

import spark_rapids_tpu.functions as F
from chipbench import check, datagen, engine, manifest
from chipbench.queries import q1, q3
from spark_rapids_tpu import obs
from spark_rapids_tpu.session import TpuSession

CELL = "star-sf1-q3-stream"
#: the CPU backend computes DOUBLE as float64, so a revenue differs from the
#: reference's extended-precision sum by the order of a float64 sum of at
#: most a few dozen products alone: a few ulp, 2.2e-16 each
DOUBLE_REL = 1e-13
#: what makes the plan exchange and join outside a segment at test sizes:
#: several input partitions, no broadcast, join fusion off; and a map task a
#: partition, so that the map side runs on the exchange's pool threads
SHUFFLED = {"spark.sql.autoBroadcastJoinThreshold": "-1",
            "spark.rapids.tpu.opjit.fuseJoins": "false",
            "spark.rapids.tpu.dispatch.partitionBatch": "1"}
JOIN_PHASES = ("segment.launch", "join.collect", "join.probe", "exchange.map",
               "exchange.fetch", "sort.topn")
NEW_METRICS = ("segment_launch_ms_per_batch", "join_collect_ms_per_query",
               "exchange_map_ms_per_query", "exchange_fetch_ms_per_query",
               "topn_ms_per_query")


def _config():
    return manifest.Cell(CELL).config


def _columns(seed, rows):
    """{table: {column: numpy}} of the configuration at `rows` lineitem rows,
    as generated (a choice column holds indices into its values)."""
    return {name: t.generate(seed, t.cached, 0, t.rows)
            for name, t in datagen.tables(_config(), rows).items()}


def _reference(cols, rows):
    """The plain reference over the same columns, handed over as a run of
    the cell hands them (`Table.kept`: a choice as its CHAR(n) values)."""
    schema = datagen.tables(_config(), rows)
    return q3.reference({name: schema[name].kept(c, q3.COLUMNS[name])
                         for name, c in cols.items()})


def _session(extra=None, partitions=8):
    conf = dict(_config()["session_conf"])
    conf["spark.sql.shuffle.partitions"] = str(partitions)
    conf.update(extra or {})
    return TpuSession(conf)


def _q3(session, columns, rows, parts=None):
    schema = datagen.tables(_config(), rows)
    tables = {name: session.createDataFrame(
        schema[name].to_arrow(cols), **({"num_partitions": parts} if parts else {})
    ).device_cache() for name, cols in columns.items()}
    return q3.build(F, tables)


def _same(got, want):
    c = check.compare_rows(got, want)
    assert c["inexact"] == 0 and c["max_rel_err"] <= DOUBLE_REL, (c, got, want)


def _self_ns(phases, name):
    return phases[name]["wall_ns"] - phases[name]["child_wall_ns"]


# ---------------------------------------------------------------------------
# the answer, through the served path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("partitions", [1, 8])
@pytest.mark.parametrize("seed,rows", [(7, 1 << 12), (2**31 + 29, 1 << 14),
                                       (1234567, 1 << 14)])
def test_q3_matches_the_reference(seed, rows, partitions):
    cols = _columns(seed, rows)
    want = _reference(cols, rows)
    assert len(want) == (10 if rows == 1 << 14 else 7), len(want)
    _same(_q3(_session(partitions=partitions), cols, rows).collect(), want)


def test_q3_fewer_than_ten_groups():
    cols = _columns(11, 1 << 12)
    want = _reference(cols, 1 << 12)
    assert len(want) == 5
    _same(_q3(_session(), cols, 1 << 12).collect(), want)


def test_q3_no_lineitem_qualifies():
    cols = _columns(11, 1 << 12)
    cols["lineitem"]["l_shipdate"] = np.minimum(cols["lineitem"]["l_shipdate"],
                                                q3.DATE).astype(np.int32)
    assert _reference(cols, 1 << 12) == []
    assert _q3(_session(), cols, 1 << 12).collect() == []


def test_q3_default_plan_is_the_general_join_path():
    """The specification's Q3 groups by `l_orderkey`, a fact column, so
    `compiled_join.try_extract_join_stage` passes it over (PERF.md section
    7): the PR that changes that eligibility changes this test knowingly."""
    plan = engine.plan_text(_q3(_session(), _columns(7, 1 << 12), 1 << 12))
    assert "HashJoin" in plan and "TpuTopN" in plan, plan
    assert engine.host_operators(plan) == [], plan
    assert "CompiledJoin" not in plan, plan


# ---------------------------------------------------------------------------
# phases and counters of the join path
# ---------------------------------------------------------------------------


def test_q3_phases_cover_the_query_and_count_the_joins():
    cols = _columns(7, 1 << 14)
    s = _session()
    df = _q3(s, cols, 1 << 14)
    df.collect()                                    # compiles
    _same(df.collect(), _reference(cols, 1 << 14))
    summ = s.last_query_phases()
    ph, counters = summ["phases"], summ["counters"]
    for name in ("segment.launch", "join.collect", "sort.topn"):
        assert ph[name]["count"] > 0 and ph[name]["wall_ns"] > 0, (name, ph)
    # both joins run inside segments and nothing is exchanged at this size
    assert not {"join.probe", "exchange.map", "exchange.fetch"} & set(ph), ph
    # every phase lies on the query's own thread here, so self times add up
    # to the root's wall exactly: each wall is some phase's child wall once
    inside = [n for n in ph if n != "sched.admit_wait"]
    assert sum(_self_ns(ph, n) for n in inside) == ph["query"]["wall_ns"]
    assert all(_self_ns(ph, n) >= 0 for n in inside), ph
    # the stage finds l_orderkey's domain too wide in the join's first batch
    # and hands what it pulled to the general aggregate: each join runs once
    assert counters["stage.fallback_handoffs"] == 1
    assert counters["stage.fallback_reruns"] == 0
    # a fallback the plan's nodes can take is in the summary, zero or not;
    # the plan holds no compiled join stage, so that one is not
    assert counters["join.subpartitioned"] == counters["agg.sort_fallback"] == 0
    assert "joinstage.fallback_reruns" not in counters
    orders, li = cols["orders"], cols["lineitem"]
    segments = _config()["tables"]["customer"]["columns"]["c_mktsegment"][1]
    building = cols["customer"]["c_mktsegment"] == segments.index(q3.SEGMENT)
    open_order = (orders["o_orderdate"] < q3.DATE) & building[orders["o_custkey"]]
    late = li["l_shipdate"] > q3.DATE
    joined = int(open_order.sum()) + int((late & open_order[li["l_orderkey"]]).sum())
    assert counters["join.rows_out"] == joined
    # each join's build is sorted and given its bucket directory once, each
    # probe batch reads one; the candidates hold every true pair and few more
    assert counters["join.builds_indexed"] == counters["join.probes_indexed"] == 2
    assert joined <= counters["join.candidate_pairs"] < 2.5 * joined
    # what the two joins' children put out, once
    assert counters["join.rows_left"] == \
        int((orders["o_orderdate"] < q3.DATE).sum()) + int(late.sum())
    assert counters["join.rows_right"] == \
        int(building.sum()) + int(open_order.sum())


def test_q3_hand_off_pulls_the_lower_joins_build_side_once(monkeypatch):
    """At 2^12 rows over 3 partitions the stage's child is the top join's
    segment and its fallback the aggregate over that same segment
    (`TpuStageSource`), where the fallback held a top join of its own: one
    `require_single` coalesce over the lower join's segment, and each of
    its partitions pulled once a query."""
    from spark_rapids_tpu.execs.compiled import (TpuCompiledAggStageExec,
                                                 TpuStageSourceExec)
    from spark_rapids_tpu.plan.overrides import plan_query
    rows = 1 << 12
    cols = _columns(7, rows)
    s = _session()
    df = _q3(s, cols, rows, parts=3)
    final, _, _ = plan_query(df._plan, s._rapids_conf())
    nodes = final.collect_nodes()
    stage = next(n for n in nodes if isinstance(n, TpuCompiledAggStageExec))
    assert stage.children[0].node_desc() == "TpuFusedSegment[BroadcastHashJoin]"
    assert stage.fallback.node_desc() == \
        "TpuFusedSegment[Project+Project+HashAggregate]"
    leaves = [n for n in stage.fallback.collect_nodes()
              if isinstance(n, TpuStageSourceExec)]
    assert len(leaves) == 1 and leaves[0].children[0] is stage.children[0]
    assert "HashJoin" not in stage.fallback.node_desc()
    # the same holds of the clone a query runs
    run = next(n for n in final.clone_for_execution().collect_nodes()
               if isinstance(n, TpuCompiledAggStageExec))
    assert run is not stage
    assert run._fallback_source().children[0] is run.children[0]

    # counted where a node is entered, whichever link led there (the
    # absorbed broadcast join pulls its build through its own child link,
    # PERF.md section 7): the lower join's segment under the top join's
    # build side, and each table's scan under it all
    from spark_rapids_tpu.execs.fusion import TpuFusedSegmentExec
    from spark_rapids_tpu.execs.transitions import TpuDeviceScanExec
    lower = "TpuFusedSegment[BroadcastHashJoin+Project]"
    pulls = []
    for cls in (TpuFusedSegmentExec, TpuDeviceScanExec):
        def counted(self, idx, ctx, real=cls.execute_partition):
            pulls.append((self.node_desc(), idx))
            return real(self, idx, ctx)
        monkeypatch.setattr(cls, "execute_partition", counted)
    _same(df.collect(), _reference(cols, rows))
    counters = s.last_query_phases()["counters"]
    assert (counters["stage.fallback_handoffs"],
            counters["stage.fallback_reruns"]) == (1, 0)
    assert sorted(i for d, i in pulls if d == lower) == [0, 1, 2], pulls
    # LINEITEM, the top join's probe side, was read twice a partition
    scans = sorted((d, i) for d, i in pulls if d.startswith("TpuDeviceScan"))
    assert len(scans) == 9 and len(set(scans)) == 9, scans


def _read_metric(name, n_queries):
    read, args = manifest.Cell(CELL).reader(name)
    assert read.__module__ == "chipbench.readers.phase_ms", name
    return read(SimpleNamespace(records=[None] * n_queries), **args)


def test_exchange_and_unfused_join_phases_land_in_their_own_query():
    """Two sessions over tables of two seeds, a Q3 each in flight at once:
    each summary holds what the same query counted when it ran alone."""
    rows, seeds = 1 << 12, (7, 11)
    sessions = [_session(SHUFFLED) for _ in seeds]
    frames = [_q3(s, _columns(seed, rows), rows, parts=3)
              for s, seed in zip(sessions, seeds)]
    plan = engine.plan_text(frames[0])
    assert "TpuShuffleExchange[hash" in plan and "FusedSegment[Shuffled" not in plan
    alone = []
    for s, df in zip(sessions, frames):
        df.collect()                                # compiles
        df.collect()
        alone.append(s.last_query_phases())
    # the exchange metrics of the cell read this plan's phases (the cell's
    # own plan at test sizes exchanges nothing)
    for name in ("exchange_map_ms_per_query", "exchange_fetch_ms_per_query"):
        assert _read_metric(name, 1) > 0, name
    start, errors = threading.Barrier(2), []

    def run(df):
        try:
            start.wait(timeout=60)
            df.collect()
        except Exception as e:  # noqa: BLE001 — reported by the assert below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(df,)) for df in frames]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors and not any(t.is_alive() for t in threads), errors
    both = [s.last_query_phases() for s in sessions]
    for summ, was in zip(both, alone):
        ph = summ["phases"]
        for name in ("exchange.map", "exchange.fetch", "join.collect",
                     "join.probe"):
            assert ph[name]["count"] == was["phases"][name]["count"] > 0, (name, ph)
        # map tasks on the pool and the right side's collector run beside the
        # query's thread: their walls come on top of the root's
        inside = [n for n in ph if n != "sched.admit_wait"]
        assert sum(_self_ns(ph, n) for n in inside) > ph["query"]["wall_ns"]
        assert summ["counters"] == was["counters"]
        assert summ["counters"]["exchange.partitions"] % 3 == 0
        assert summ["counters"]["exchange.bytes"] > summ["counters"]["exchange.rows"] > 0
    assert both[0]["counters"] != both[1]["counters"]


@pytest.mark.parametrize("fuse_joins", ["false", "true"])
def test_silent_fallbacks_are_counted(fuse_joins):
    """A batch budget of 257 rows makes a join split its sides (on its own,
    or delegated to by the segment that absorbed it) and the general-path
    aggregate sort its input out of core; the query's summary says which
    fallbacks it took (tests/test_overflow_paths.py holds their answers to
    the CPU's)."""
    import pyarrow as pa
    s = TpuSession({"spark.rapids.sql.batchSizeRows": "257",
                    "spark.rapids.tpu.agg.compiledStage.enabled": "false",
                    "spark.rapids.tpu.opjit.fuseJoins": fuse_joins})
    left = s.createDataFrame(pa.table({"k": pa.array(range(1500), type=pa.int64())}))
    right = s.createDataFrame(pa.table({"rk": pa.array(range(0, 3000, 2), type=pa.int64())}))
    got = left.join(right, left["k"] == right["rk"]).groupBy("k") \
        .agg(F.count("*").alias("c")).collect()
    assert sorted(r["k"] for r in got) == list(range(0, 1500, 2))
    summ = s.last_query_phases()
    counters = summ["counters"]
    assert counters["join.subpartitioned"] == 1 and counters["agg.sort_fallback"] == 1
    assert counters["join.rows_out"] == 750
    # a segment that delegates has pulled the build side whole to find it
    # over the budget, and the join it hands over to pulls it again
    assert (counters["join.rows_left"], counters["join.rows_right"]) == \
        (1500, 3000 if fuse_joins == "true" else 1500)
    # a pair per sub-partition, each a lap of `join.probe`
    assert summ["phases"]["join.probe"]["count"] >= 2


def test_laps_survive_a_consumer_that_leaves_early():
    """A limit, a cancel or a sibling's error closes an operator's generator
    before its end: the laps taken until then are flushed all the same."""
    from spark_rapids_tpu.execs.base import TaskContext
    from spark_rapids_tpu.plan.overrides import plan_query
    from spark_rapids_tpu.serving.query_context import QueryContext, bind
    s = _session()
    df = _q3(s, _columns(7, 1 << 12), 1 << 12, parts=3)
    conf = s._rapids_conf()
    final, _, _ = plan_query(df._plan, conf)
    # the lowest join, CUSTOMER broadcast into filtered ORDERS: no exchange
    # and no other segment below it
    seg = next(n for n in final.collect_nodes() if n.node_desc()
               == "TpuFusedSegment[BroadcastHashJoin+Project]")
    q = QueryContext("leaves-early", session_id="s")
    with bind(q):
        it = seg.execute_partition(0, TaskContext(0, conf))
        assert next(it).num_rows > 0
        # the build side's own segment ran to its end and has flushed; the
        # probe's lap is still with the open generator (nothing per batch)
        built = q.phase_table()["segment.launch"]["count"]
        it.close()
    assert q.phase_table()["segment.launch"]["count"] == built + 1


def test_a_failed_query_keeps_its_summary_and_carries_no_counters():
    """Folding a summary reads nothing a failed query may not have computed:
    the deadline passes while the plan is built, the query ends at its first
    task boundary, and its summary is in the ring with the failure marked."""
    from spark_rapids_tpu.serving.query_context import QueryDeadlineExceeded
    s = _session()
    df = _q3(s, _columns(7, 1 << 12), 1 << 12)
    with pytest.raises(QueryDeadlineExceeded):
        df.collect(timeout=1e-4)
    summ = s.last_query_phases()
    assert summ["failed"] is True and summ["counters"] == {}
    assert obs.metrics.recent_queries(1) == [summ]
    assert "result.drain" in summ["phases"]


def test_q1_over_one_table_holds_no_join_phase():
    conf = manifest.Cell("resident-q1-stream").config
    table = datagen.tables(conf, 1 << 12)["lineitem"]
    s = TpuSession(dict(conf["session_conf"]))
    df = s.createDataFrame(table.to_arrow(
        table.generate(7, table.cached, 0, table.rows))).device_cache()
    assert len(q1.build(F, {"lineitem": df}).collect()) >= 1
    summ = s.last_query_phases()
    assert "stage.launch" in summ["phases"]
    assert not set(JOIN_PHASES) & set(summ["phases"]), summ["phases"]
    # no join in the plan; the stage's fallback subtree did not run
    assert not [k for k in summ["counters"] if k.startswith("join.")]
    assert set(summ["counters"].values()) == {0}, summ["counters"]


# ---------------------------------------------------------------------------
# the cell, as BENCHMARK.json declares it
# ---------------------------------------------------------------------------


def test_the_manifest_validates_with_the_cell():
    assert manifest.validate() == []
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and cell.entry["traffic"] == "q3-spec-stream"
    assert cell.config["scale_factor"] == 1 and cell.config["rows"] == 6_001_215
    rows = {n: t.rows for n, t in datagen.tables(cell.config).items()}
    assert rows == {"customer": 150_000, "orders": 1_500_000, "lineitem": 6_001_215}
    listed = {m["name"] for m in cell.metrics("per_layer")}
    assert set(NEW_METRICS) <= listed
    assert {"rows_per_s", "setup_s"} == {m["name"] for m in cell.metrics("end_to_end")}


def test_the_cell_runs_and_its_metrics_read():
    from chipbench import run
    from spark_rapids_tpu.io import device_decode
    # a run is a process of its own; here the worker has run other files, and
    # the harness reads the scan's fallback counts as they stand
    device_decode.reset_for_tests()
    r = run.run_cell(CELL, 2**31 + 29, 1.5, trace=False, rehearsal_rows=1 << 14)
    assert r["correct"] is True and r["attempted"] >= 1, r["checks"]
    assert r["metrics"]["rows_per_s"]["value"] > 0
    summaries = obs.metrics.recent_queries(r["attempted"])
    held = set().union(*(q["phases"] for q in summaries))
    for name in NEW_METRICS:
        _, args = manifest.Cell(CELL).reader(name)
        value = _read_metric(name, r["attempted"])
        assert (value is not None and value > 0) == (args["phase"] in held), name
    assert {"segment.launch", "join.collect", "sort.topn"} <= held
