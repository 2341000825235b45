"""Mesh data plane (ISSUE 10 tentpole): plan-driven sharded multi-chip
execution over the simulated 8-device CPU mesh.

Covers the parity suite the tentpole names: a q3-shaped query on a mesh
session bit-identical to the MULTITHREADED host shuffle across fusion
on/off × coalesce on/off; the O(exchanges) collective-launch counter;
AQE's device-side partition statistics (no block fetch); planner selection
(collective_planned + alignPartitions); the single-partition collective
funnel; chaos lost-shard / slow-link healing via the FetchFailed/re-run
machinery with zero leaks; and the mesh.exchange obs span with exact
bundle reconciliation."""

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.chaos import FaultInjector
from spark_rapids_tpu.execs.base import TaskContext
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.shuffle.exchange import TpuShuffleExchangeExec
from spark_rapids_tpu.shuffle.ici import IciShuffleCatalog

N_DEV = 8


def _mesh_conf(**extra):
    base = {
        "spark.rapids.shuffle.mode": "ICI",
        "spark.rapids.tpu.mesh.enabled": "true",
        "spark.sql.shuffle.partitions": str(N_DEV),
        "spark.rapids.tpu.dispatch.partitionBatch": str(N_DEV),
        "spark.sql.autoBroadcastJoinThreshold": "0",
        # the parity targets are the EXCHANGES; compiled whole-stage
        # shortcuts would bypass them for these small plans
        "spark.rapids.tpu.agg.compiledStage.enabled": "false",
        "spark.rapids.tpu.join.compiledStage.enabled": "false",
    }
    base.update(extra)
    return base


def _host_conf(**extra):
    base = _mesh_conf(**extra)
    base["spark.rapids.shuffle.mode"] = "MULTITHREADED"
    base["spark.rapids.tpu.mesh.enabled"] = "false"
    return base


def _tables(seed=7, n=6000, n2=500):
    rng = np.random.default_rng(seed)
    fact = pa.table({
        "k": rng.integers(0, 60, n),
        "d": rng.integers(8000, 11000, n),
        "v": rng.integers(-1000, 1000, n),
        "w": rng.normal(size=n),
    })
    dim = pa.table({"k2": rng.integers(0, 60, n2),
                    "r": rng.integers(0, 9, n2)})
    return fact, dim


def _q3_shaped(s, fact, dim):
    """scan → filter → join → groupBy → sort: the q3 shape, with integer
    measures exact under any execution schedule and one float sum whose
    accumulation order the data plane must also preserve."""
    fd = s.createDataFrame(fact, num_partitions=4)
    dd = s.createDataFrame(dim, num_partitions=2)
    return (fd.filter(F.col("d") > 8500)
            .join(dd, on=fd["k"] == dd["k2"])
            .groupBy("k")
            .agg(F.sum(F.col("v")).alias("sv"),
                 F.count(F.col("w")).alias("cw"),
                 F.max(F.col("r")).alias("mr"))
            .sort("k"))


# collective_spy (per-exchange collective verdicts) comes from conftest.py,
# shared with tests/test_mesh_shuffle.py


# ---------------------------------------------------------------------------
# parity: mesh vs MULTITHREADED across fusion × coalesce
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fuse", ["true", "false"])
@pytest.mark.parametrize("coalesce", ["true", "false"])
def test_mesh_parity_vs_multithreaded(fuse, coalesce, collective_spy):
    fact, dim = _tables()
    runs = collective_spy
    knobs = {"spark.rapids.tpu.opjit.fuseStages": fuse,
             "spark.rapids.tpu.coalesce.enabled": coalesce}
    mesh = _q3_shaped(TpuSession(_mesh_conf(**knobs)), fact, dim).collect()
    host = _q3_shaped(TpuSession(_host_conf(**knobs)), fact, dim).collect()
    assert mesh == host  # bit-identical, float sum included
    assert any(runs), "mesh session never took the collective data plane"


def test_mesh_parity_cpu_oracle():
    fact, dim = _tables(seed=13)
    mesh = _q3_shaped(TpuSession(_mesh_conf()), fact, dim).collect()
    cpu = _q3_shaped(TpuSession({"spark.rapids.sql.enabled": "false"}),
                     fact, dim).collect()
    got = {r["k"]: r for r in mesh}
    want = {r["k"]: r for r in cpu}
    assert set(got) == set(want)
    for k, r in got.items():
        assert r["sv"] == want[k]["sv"]
        assert r["cw"] == want[k]["cw"]
        assert r["mr"] == want[k]["mr"]


# ---------------------------------------------------------------------------
# the O(exchanges) collective-launch counter
# ---------------------------------------------------------------------------

def test_collective_launches_O_exchanges():
    from spark_rapids_tpu.execs import opjit
    from spark_rapids_tpu.parallel import mesh as pmesh
    fact, dim = _tables(seed=3)
    s = TpuSession(_mesh_conf())
    q = _q3_shaped(s, fact, dim)
    q.collect()  # warm (compiles; exchanges cleaned up at query end)

    def kind():
        return opjit.cache_stats()["calls_by_kind"].get("mesh_collective", 0)

    before_kind = kind()
    before = pmesh.collective_stats()
    q.collect()
    after = pmesh.collective_stats()
    launches = after["launches"] - before["launches"]
    exchanges = sum(1 for nd in s._last_plan_tree
                    if "ShuffleExchange" in nd["name"])
    assert exchanges >= 2  # join (two sides) at least
    assert launches >= 1
    # ONE collective per exchange per query — NOT one per partition
    assert launches <= exchanges
    assert launches < exchanges * N_DEV
    # the dispatch accounting agrees with the mesh module's own counter
    assert kind() - before_kind == launches
    assert after["rows_sent"] > before["rows_sent"]
    assert after["launch_ns"] >= before["launch_ns"]


# ---------------------------------------------------------------------------
# AQE consumes device-side statistics — no block fetch, no unspill
# ---------------------------------------------------------------------------

def _find_exchange(plan):
    for node in plan.collect_nodes():
        if isinstance(node, TpuShuffleExchangeExec):
            return node
    return None


def _planned_exchange(s, fact, dim):
    from spark_rapids_tpu.plan.overrides import TpuOverrides
    from spark_rapids_tpu.plan.planner import plan_physical
    df = _q3_shaped(s, fact, dim)
    conf = s._rapids_conf()
    final = TpuOverrides.apply(plan_physical(df._plan, conf), conf)
    return _find_exchange(final), conf


def test_partition_sizes_from_device_counters(monkeypatch):
    """partition_sizes (the AQE map-output statistics) must come from the
    exchange-time counters / catalog metadata: zero SpillableColumnarBatch
    fetches, exact row counts surfaced for the collective path."""
    from spark_rapids_tpu.memory.spill import SpillableColumnarBatch
    fact, dim = _tables(seed=5, n=4000)
    s = TpuSession(_mesh_conf())
    exch, conf = _planned_exchange(s, fact, dim)
    assert exch is not None and getattr(exch, "collective_planned", False)
    ctx = TaskContext(0, conf)
    try:
        exch._ensure_materialized(ctx)
        assert getattr(exch, "_collective", False)
        fetches = []
        orig = SpillableColumnarBatch.get_batch

        def counting(self, *a, **k):
            fetches.append(1)
            return orig(self, *a, **k)

        monkeypatch.setattr(SpillableColumnarBatch, "get_batch", counting)
        sizes = exch.partition_sizes(ctx)
        rows = exch.partition_row_counts(ctx)
    finally:
        ctx.complete()
        exch.cleanup_shuffle(conf)
    assert not fetches, "AQE statistics fetched blocks"
    assert len(sizes) == exch.num_partitions()
    assert sum(sizes) > 0
    assert rows is not None and sum(rows) > 0
    # exact: the counters carry rows, and bytes = rows × fixed row width
    nz = [i for i, r in enumerate(rows) if r]
    assert all(sizes[i] > 0 for i in nz)


def test_partition_sizes_per_map_ici_metadata(monkeypatch):
    """The per-map ICI path's statistics come from catalog metadata
    (size tracked at put time) — no unspill either."""
    from spark_rapids_tpu.memory.spill import SpillableColumnarBatch
    fact, dim = _tables(seed=5, n=4000)
    s = TpuSession(_mesh_conf(**{
        "spark.rapids.tpu.mesh.collectiveExchange.enabled": "false"}))
    exch, conf = _planned_exchange(s, fact, dim)
    ctx = TaskContext(0, conf)
    try:
        exch._ensure_materialized(ctx)
        assert not getattr(exch, "_collective", False)
        fetches = []
        orig = SpillableColumnarBatch.get_batch

        def counting(self, *a, **k):
            fetches.append(1)
            return orig(self, *a, **k)

        monkeypatch.setattr(SpillableColumnarBatch, "get_batch", counting)
        sizes = exch.partition_sizes(ctx)
    finally:
        ctx.complete()
        exch.cleanup_shuffle(conf)
    assert not fetches
    assert len(sizes) == exch.num_partitions()
    assert sum(sizes) > 0


# ---------------------------------------------------------------------------
# planner selection: collective_planned + alignPartitions
# ---------------------------------------------------------------------------

def test_planner_selects_collective_and_aligns():
    fact, dim = _tables(n=2000)
    s = TpuSession(_mesh_conf(**{"spark.sql.shuffle.partitions": "16"}))
    exch, _ = _planned_exchange(s, fact, dim)
    assert exch is not None
    assert getattr(exch, "collective_planned", False)
    # child has 4 partitions and the conf asks for 16: the mesh planner
    # aligns to exactly the mesh size anyway
    assert exch.num_partitions() == N_DEV


def test_planner_align_off_keeps_conf_count():
    fact, dim = _tables(n=2000)
    s = TpuSession(_mesh_conf(**{
        "spark.rapids.tpu.mesh.alignPartitions": "false",
        "spark.sql.shuffle.partitions": "4"}))
    exch, _ = _planned_exchange(s, fact, dim)
    assert exch is not None
    assert exch.num_partitions() == 4
    # 4 != mesh size: not collective-eligible, flag stays off
    assert not getattr(exch, "collective_planned", False)


def test_planner_string_payload_dictionary_planned():
    """A string payload is collective-planned via the dictionary-encode
    pass (codes + one broadcast dictionary ride the fabric); with the
    conf off it keeps the per-map path as before."""
    rng = np.random.default_rng(2)
    t = pa.table({"k": rng.integers(0, 10, 500),
                  "s": pa.array([f"x{i % 5}" for i in range(500)])})

    def planned(extra):
        s = TpuSession(_mesh_conf(**extra))
        df = (s.createDataFrame(t, num_partitions=4)
              .groupBy("k").agg(F.max(F.col("s")).alias("ms")))
        from spark_rapids_tpu.plan.overrides import TpuOverrides
        from spark_rapids_tpu.plan.planner import plan_physical
        conf = s._rapids_conf()
        final = TpuOverrides.apply(plan_physical(df._plan, conf), conf)
        exch = _find_exchange(final)
        assert exch is not None
        return getattr(exch, "collective_planned", False)

    assert planned({})
    assert not planned(
        {"spark.rapids.tpu.exchange.dictionaryEncode.enabled": "false"})


# ---------------------------------------------------------------------------
# single-partition collective funnel
# ---------------------------------------------------------------------------

def test_mesh_single_exchange_funnels_to_shard_zero():
    from spark_rapids_tpu.columnar.batch import TpuColumnarBatch
    from spark_rapids_tpu.columnar.vector import TpuColumnVector
    from spark_rapids_tpu.parallel.mesh import (MeshContext,
                                                mesh_single_exchange)
    from spark_rapids_tpu.types import DoubleT, LongT
    import jax.numpy as jnp
    from spark_rapids_tpu.config import RapidsConf
    conf = RapidsConf({"spark.rapids.tpu.mesh.enabled": "true"})
    mesh = MeshContext.get(conf, N_DEV)
    assert mesh is not None
    batches = []
    total = 0
    for d in range(N_DEV):
        n = 10 + d
        total += n
        cols = [TpuColumnVector(LongT, jnp.arange(n, dtype=jnp.int64) + d,
                                None, n),
                TpuColumnVector(DoubleT,
                                jnp.full((n,), float(d), jnp.float64),
                                None, n)]
        batches.append(TpuColumnarBatch(cols, n, ["a", "b"]))
    res = mesh_single_exchange(mesh, batches, ["a", "b"], shuffle_id=99)
    assert res.rows[0] == total
    assert all(r == 0 for r in res.rows[1:])
    assert res.batches[0].num_rows == total
    assert res.bytes[0] > 0


def test_single_partitioning_exchange_collective(collective_spy):
    """A planner-selected single-partition exchange rides the funnel: one
    collective, one reduce partition, content preserved."""
    from spark_rapids_tpu.execs.transitions import HostToDeviceExec
    from spark_rapids_tpu.plan import logical as L
    from spark_rapids_tpu.plan.planner import plan_physical
    rng = np.random.default_rng(4)
    t = pa.table({"a": rng.integers(0, 1000, 3000),
                  "b": rng.normal(size=3000)})
    runs = collective_spy
    s = TpuSession(_mesh_conf())
    conf = s._rapids_conf()
    scan = plan_physical(L.LocalRelation(t, 4), conf)
    exch = TpuShuffleExchangeExec(HostToDeviceExec(scan), "single", [], 1)
    exch.collective_planned = True
    ctx = TaskContext(0, conf)
    try:
        got = [b.to_arrow() for b in exch.execute_partition(0, ctx)]
    finally:
        ctx.complete()
        exch.cleanup_shuffle(conf)
    assert any(runs)
    merged = pa.concat_tables(got).sort_by([("a", "ascending"),
                                            ("b", "ascending")])
    want = t.sort_by([("a", "ascending"), ("b", "ascending")])
    assert merged.equals(want)


# ---------------------------------------------------------------------------
# chaos: lost shard + slow link heal through FetchFailed/re-run
# ---------------------------------------------------------------------------

def test_chaos_lost_shard_heals_bit_identical(collective_spy):
    fact, dim = _tables(seed=21)
    clean = _q3_shaped(TpuSession(_mesh_conf()), fact, dim).collect()
    runs = collective_spy
    IciShuffleCatalog.reset_for_tests()
    s = TpuSession(_mesh_conf())
    inj = FaultInjector.get()
    inj.force("mesh.shard", "io_error", 1)
    try:
        got = _q3_shaped(s, fact, dim).collect()
    finally:
        inj.clear_forced()
    assert got == clean
    # the heal re-ran the collective: more collective materializations than
    # the clean run's exchange count
    assert sum(1 for r in runs if r) > 0
    assert inj.injection_count() >= 1
    assert any(r["site"] == "mesh.shard" for r in inj.trace())


def test_chaos_slow_link_transient_heals(collective_spy):
    fact, dim = _tables(seed=22)
    clean = _q3_shaped(TpuSession(_mesh_conf()), fact, dim).collect()
    runs = collective_spy
    s = TpuSession(_mesh_conf(**{
        "spark.rapids.tpu.deviceRetry.backoffBaseMs": "1",
        "spark.rapids.tpu.deviceRetry.backoffMaxMs": "4"}))
    inj = FaultInjector.get()
    inj.force("mesh.link", "transient", 1)
    try:
        got = _q3_shaped(s, fact, dim).collect()
    finally:
        inj.clear_forced()
    assert got == clean
    assert any(runs)
    assert any(r["site"] == "mesh.link" for r in inj.trace())


@pytest.mark.parametrize("seed", [111, 222])
def test_chaos_mesh_soak(seed):
    """Seeded chaos armed at the mesh sites (+ the generic ici/dispatch
    sites): bit-identical results, zero leaked device resources, all
    semaphore permits returned, catalog clean."""
    from spark_rapids_tpu.memory.cleaner import MemoryCleaner
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    fact, dim = _tables(seed=seed)
    TpuSemaphore.reset_for_tests()
    IciShuffleCatalog.reset_for_tests()
    clean = _q3_shaped(TpuSession(_mesh_conf()), fact, dim).collect()
    live_before = len(MemoryCleaner.get().live_resources())
    blocks_before = IciShuffleCatalog.get().block_count()
    chaos = {
        "spark.rapids.tpu.test.chaos.enabled": "true",
        "spark.rapids.tpu.test.chaos.seed": str(seed),
        "spark.rapids.tpu.test.chaos.sites":
            "mesh.shard,mesh.link,ici.fetch,device.dispatch",
        "spark.rapids.tpu.test.chaos.kinds":
            "io_error,transient,latency",
        "spark.rapids.tpu.test.chaos.probability": "0.2",
        "spark.rapids.tpu.test.chaos.latencyMs": "1",
        "spark.rapids.tpu.deviceRetry.maxAttempts": "8",
        "spark.rapids.tpu.deviceRetry.backoffBaseMs": "1",
        "spark.rapids.tpu.deviceRetry.backoffMaxMs": "4",
        "spark.rapids.tpu.shuffle.fetchRetry.maxAttempts": "8",
    }
    s = TpuSession(_mesh_conf(**chaos))
    injector = FaultInjector.get()
    assert injector.enabled
    got = _q3_shaped(s, fact, dim).collect()
    FaultInjector.reset_for_tests()
    assert got == clean
    assert injector.injection_count() > 0
    assert len(MemoryCleaner.get().live_resources()) == live_before
    assert IciShuffleCatalog.get().block_count() == blocks_before
    sem = TpuSemaphore._instance
    if sem is not None:
        assert all(s._value == sem.permits
                   for s in (sem._sem, *sem._chip_sems.values()))
    TpuSemaphore.reset_for_tests()


# ---------------------------------------------------------------------------
# exchange/compute overlap (ISSUE 16): bit-identity, dispatch accounting,
# and mid-segment chaos under donated buffers
# ---------------------------------------------------------------------------

def _overlap_conf(**extra):
    base = _mesh_conf(**{
        "spark.rapids.tpu.exchange.overlap.enabled": "true",
        "spark.rapids.tpu.exchange.overlap.segments": "3",
        # test payloads are tiny; drop the floor so they still segment
        "spark.rapids.tpu.exchange.overlap.minSlotRows": "1",
    })
    base.update(extra)
    return base


def test_overlap_bit_identity_and_dispatch_counts(collective_spy):
    """Overlap on vs off: bit-identical results (float sum accumulation
    order included — the segmented scatter lands every row at the same
    bases[src]+pos slot), ONE mesh_collective dispatch per exchange
    preserved, and the per-segment launches accounted under their own
    mesh_overlap_segment kind, agreeing with the mesh module's counter."""
    from spark_rapids_tpu.execs import opjit
    from spark_rapids_tpu.parallel import mesh as pmesh
    fact, dim = _tables(seed=31)
    runs = collective_spy
    off = _q3_shaped(TpuSession(_mesh_conf()), fact, dim).collect()
    s = TpuSession(_overlap_conf())
    q = _q3_shaped(s, fact, dim)
    assert q.collect() == off  # warm overlapped run already bit-identical
    assert any(runs)

    def kinds():
        by = opjit.cache_stats()["calls_by_kind"]
        return (by.get("mesh_collective", 0),
                by.get("mesh_overlap_segment", 0))

    coll0, seg0 = kinds()
    stats0 = pmesh.collective_stats()
    assert q.collect() == off
    coll1, seg1 = kinds()
    stats1 = pmesh.collective_stats()
    launches = stats1["launches"] - stats0["launches"]
    exchanges = sum(1 for nd in s._last_plan_tree
                    if "ShuffleExchange" in nd["name"])
    # O(exchanges) holds under overlap: segments are NOT extra collectives
    assert launches >= 1
    assert coll1 - coll0 == launches
    assert launches <= exchanges
    # every exchange segmented (minSlotRows=1): K segment dispatches each,
    # reconciled exactly against the registry's overlap_segments counter
    seg_delta = seg1 - seg0
    assert seg_delta == 3 * launches
    assert stats1["overlap_segments"] - stats0["overlap_segments"] \
        == seg_delta


def test_overlap_floor_keeps_unsegmented_path():
    """With the minSlotRows floor above the slot capacity the conf is on
    but every exchange stays on the single-program path: zero
    mesh_overlap_segment dispatches, results unchanged."""
    from spark_rapids_tpu.execs import opjit
    fact, dim = _tables(seed=32)
    off = _q3_shaped(TpuSession(_mesh_conf()), fact, dim).collect()

    def seg():
        return opjit.cache_stats()["calls_by_kind"].get(
            "mesh_overlap_segment", 0)

    before = seg()
    got = _q3_shaped(
        TpuSession(_overlap_conf(**{
            "spark.rapids.tpu.exchange.overlap.minSlotRows": "100000000"})),
        fact, dim).collect()
    assert got == off
    assert seg() == before


def test_chaos_mid_segment_transient_heals(collective_spy):
    """A mesh.link transient fired MID-SEGMENT under overlap: the failed
    exchange retries from the still-open spillables (donated staging
    buffers are consumed at most once — the abandoned accumulators are
    never re-fed), heals bit-identical, and the chaos trace shows the
    per-segment injection site detail."""
    fact, dim = _tables(seed=33)
    clean = _q3_shaped(TpuSession(_mesh_conf()), fact, dim).collect()
    runs = collective_spy
    s = TpuSession(_overlap_conf(**{
        "spark.rapids.tpu.deviceRetry.backoffBaseMs": "1",
        "spark.rapids.tpu.deviceRetry.backoffMaxMs": "4"}))
    inj = FaultInjector.get()
    inj.force("mesh.link", "transient", 1)
    try:
        got = _q3_shaped(s, fact, dim).collect()
    finally:
        inj.clear_forced()
    assert got == clean
    assert any(runs)
    # the fault landed on a segment launch, not the legacy whole-exchange
    # site: overlap mode tags mesh.link checks with the segment index
    assert any(r["site"] == "mesh.link" and "seg" in r["detail"]
               for r in inj.trace())


def test_chaos_mesh_soak_overlap():
    """The ISSUE 16 soak: seeded chaos armed at the mesh sites with the
    segmented overlap dataplane ON — faults land mid-segment, retries
    re-stage without double-applying donated buffers, results stay
    bit-identical, and nothing leaks (device resources, catalog blocks,
    semaphore permits)."""
    from spark_rapids_tpu.memory.cleaner import MemoryCleaner
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    seed = 333
    fact, dim = _tables(seed=seed)
    TpuSemaphore.reset_for_tests()
    IciShuffleCatalog.reset_for_tests()
    clean = _q3_shaped(TpuSession(_mesh_conf()), fact, dim).collect()
    live_before = len(MemoryCleaner.get().live_resources())
    blocks_before = IciShuffleCatalog.get().block_count()
    chaos = {
        "spark.rapids.tpu.test.chaos.enabled": "true",
        "spark.rapids.tpu.test.chaos.seed": str(seed),
        "spark.rapids.tpu.test.chaos.sites":
            "mesh.shard,mesh.link,ici.fetch,device.dispatch",
        "spark.rapids.tpu.test.chaos.kinds":
            "io_error,transient,latency",
        "spark.rapids.tpu.test.chaos.probability": "0.2",
        "spark.rapids.tpu.test.chaos.latencyMs": "1",
        "spark.rapids.tpu.deviceRetry.maxAttempts": "8",
        "spark.rapids.tpu.deviceRetry.backoffBaseMs": "1",
        "spark.rapids.tpu.deviceRetry.backoffMaxMs": "4",
        "spark.rapids.tpu.shuffle.fetchRetry.maxAttempts": "8",
    }
    s = TpuSession(_overlap_conf(**chaos))
    injector = FaultInjector.get()
    assert injector.enabled
    got = _q3_shaped(s, fact, dim).collect()
    FaultInjector.reset_for_tests()
    assert got == clean
    assert injector.injection_count() > 0
    assert len(MemoryCleaner.get().live_resources()) == live_before
    assert IciShuffleCatalog.get().block_count() == blocks_before
    sem = TpuSemaphore._instance
    if sem is not None:
        assert all(s._value == sem.permits
                   for s in (sem._sem, *sem._chip_sems.values()))
    TpuSemaphore.reset_for_tests()


# ---------------------------------------------------------------------------
# observability: mesh.exchange span + exact reconciliation
# ---------------------------------------------------------------------------

def test_mesh_exchange_span_and_reconciliation():
    from spark_rapids_tpu.obs.tracer import QueryTracer
    QueryTracer.reset_for_tests()
    fact, dim = _tables(seed=9, n=3000)
    s = TpuSession(_mesh_conf(**{"spark.rapids.tpu.trace.enabled": "true"}))
    q = _q3_shaped(s, fact, dim)
    q.collect()
    prof = s.last_query_profile()
    assert prof is not None
    rec = prof.get("reconcile") or {}
    assert rec.get("dispatch_ok", False)
    assert rec.get("sync_ok", False)
    spans = prof.get("spans") or {}

    def find(node, out):
        if isinstance(node, dict):
            if "mesh.exchange" in str(node.get("name", "")):
                out.append(node)
            for c in node.get("children", []):
                find(c, out)

    hits = []
    find(spans, hits)
    assert hits, "no mesh.exchange span in the traced query"
    # per-chip breakdown rides the span args
    args = hits[0].get("args", {})
    assert "per_chip_rows" in args and len(args["per_chip_rows"]) == N_DEV
    # the collective's dispatch lands in the bundle's by-kind counts
    kinds = prof.get("dispatches_by_kind") or {}
    assert kinds.get("mesh_collective", 0) >= 1
