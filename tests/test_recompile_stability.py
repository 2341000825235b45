"""Recompile-stability regression net — the dynamic twin of TL030/TL031.

jitlint proves statically that cached-program keys are value-stable and
shapes are bucketed; this suite proves the same contract end-to-end: after
a warmup submission, REPEATING a query must be all cache hits — zero new
opjit misses, zero new traces, zero growth in any process-wide program
cache (opjit, compiled agg/join stages, the mesh exchange programs).  One
unstable key component or unbucketed shape anywhere in the path turns a
repeat into a recompile and fails here with the exact counter that moved.

Coverage is routed deliberately: q6/q3/q1 fuse into the compiled agg/join
stage caches, q18 runs the general opjit path (its sort/limit tail cannot
fuse), and a mesh-session q3 shape (compiled stages disabled, collective
exchange on) drives the mesh program cache.

The cross-session case is the production one (ROADMAP item 2's plan cache
assumes it): the executables are process-wide, so a SECOND session
frontend submitting the same query shapes must trace NOTHING — a
per-session object leaking into a cache key (the TL030 identity-hash
failure mode) breaks exactly this assertion.
"""

import numpy as np
import pyarrow as pa
import pytest

import benchmarks.tpch as tpch
import spark_rapids_tpu.functions as F
from spark_rapids_tpu.execs import compiled, compiled_join, opjit
from spark_rapids_tpu.parallel import mesh
from spark_rapids_tpu.session import TpuSession

ROWS = 6_000

#: q6: scan→filter→agg (compiled agg stage); q3: star join (compiled join
#: stage); q1: grouped agg (second compiled stage); q18: join+having+
#: sort+limit — stays on the general opjit executable cache
QUERIES = ("q6", "q3", "q1", "q18")


def _program_cache_sizes():
    """Every process-wide compiled-program cache the workloads can grow."""
    return {
        "opjit": opjit.cache_len(),
        "compiled_stage": len(compiled._STAGE_FN_CACHE),
        "compiled_join_stage": len(compiled_join._JOIN_STAGE_FN_CACHE),
        "mesh_exchange": len(mesh._EXCHANGE_CACHE),
    }


def _compile_snapshot():
    stats = opjit.cache_stats()
    return {"misses": stats["misses"], "traces": stats["traces"],
            "caches": _program_cache_sizes()}


def _cache_hits():
    from spark_rapids_tpu.serving.scheduler import QueryScheduler
    return {"plan": QueryScheduler.get().plan_cache.stats()["hits"],
            "opjit": opjit.cache_stats()["hits"]}


def _assert_no_recompiles(before, after, what):
    assert after["misses"] == before["misses"], (
        f"{what} recompiled: opjit misses {before['misses']} -> "
        f"{after['misses']} — an unstable cache key or unbucketed shape "
        f"entered a jitted signature (TL030/TL031)")
    assert after["traces"] == before["traces"], (
        f"{what} re-traced: {before['traces']} -> {after['traces']}")
    assert after["caches"] == before["caches"], (
        f"program caches grew ({what}): {before['caches']} -> "
        f"{after['caches']}")


def _run(s, t, names=QUERIES):
    for name in names:
        out = tpch.QUERIES[name](s, t).to_arrow()
        assert out.num_rows > 0, f"{name} returned no rows"


@pytest.fixture(scope="module")
def warm_session():
    """A warmed TPU session: every program the workload needs is traced."""
    s = tpch.make_session(tpu=True)
    t = tpch.load_tables(s, ROWS)
    _run(s, t)
    return s, t


def test_repeat_submission_zero_recompiles(warm_session):
    s, t = warm_session
    before = _compile_snapshot()
    hits_before = _cache_hits()
    for _ in range(2):
        _run(s, t)
    after = _compile_snapshot()
    _assert_no_recompiles(before, after, "repeated q6/q3/q1/q18 submission")
    # the repeats must have been SERVED from a cache, not bypassed it: the
    # scheduler's plan cache hands back bound plans whose stage programs
    # are already resolved (so opjit's hit counter may stay flat), a plan
    # miss re-resolves them through opjit — either counter must rise
    hits_after = _cache_hits()
    assert (hits_after["plan"] > hits_before["plan"]
            or hits_after["opjit"] > hits_before["opjit"]), (
        f"repeat submissions hit no cache: {hits_before} -> {hits_after}")


def test_second_session_shares_process_wide_programs(warm_session):
    """A fresh session frontend submitting the same query shapes traces
    NOTHING: the executables are process-wide, and no per-session object
    (conf instance, session id, context identity) may reach a cache key."""
    _s, _t = warm_session  # ordering: programs already traced
    s2 = tpch.make_session(tpu=True)
    t2 = tpch.load_tables(s2, ROWS)  # same scale → same bucketed caps
    before = _compile_snapshot()
    _run(s2, t2)
    after = _compile_snapshot()
    _assert_no_recompiles(before, after, "a second session")


# ---------------------------------------------------------------------------
# mesh collective data plane: the exchange/overlap program cache
# ---------------------------------------------------------------------------


def _mesh_session():
    return TpuSession({
        "spark.rapids.shuffle.mode": "ICI",
        "spark.rapids.tpu.mesh.enabled": "true",
        "spark.sql.shuffle.partitions": "8",
        "spark.rapids.tpu.dispatch.partitionBatch": "8",
        "spark.sql.autoBroadcastJoinThreshold": "0",
        # compiled whole-stage shortcuts would bypass the exchanges
        "spark.rapids.tpu.agg.compiledStage.enabled": "false",
        "spark.rapids.tpu.join.compiledStage.enabled": "false",
    })


def _mesh_q3(s, fact, dim):
    fd = s.createDataFrame(fact, num_partitions=4)
    dd = s.createDataFrame(dim, num_partitions=2)
    return (fd.filter(F.col("d") > 8500)
            .join(dd, on=fd["k"] == dd["k2"])
            .groupBy("k")
            .agg(F.sum(F.col("v")).alias("sv"))
            .sort("k")).to_arrow()


def _mesh_tables(seed=7, n=6000, n2=500):
    rng = np.random.default_rng(seed)
    fact = pa.table({"k": rng.integers(0, 60, n),
                     "d": rng.integers(8000, 11000, n),
                     "v": rng.integers(-1000, 1000, n)})
    dim = pa.table({"k2": rng.integers(0, 60, n2),
                    "r": rng.integers(0, 9, n2)})
    return fact, dim


def test_mesh_exchange_programs_stable_across_repeats_and_sessions():
    """The collective exchange/overlap programs (mesh._EXCHANGE_CACHE,
    keyed mesh × device count × bucketed slot cap × payload signature)
    must trace once per shape: a repeat submission — and a second mesh
    session over the same-scale data — adds zero entries and zero opjit
    misses.  Same-seed datagen keeps row counts equal, so the bucketed
    slot caps land in the same buckets by construction."""
    fact, dim = _mesh_tables()
    s = _mesh_session()
    out1 = _mesh_q3(s, fact, dim)
    assert out1.num_rows > 0
    assert len(mesh._EXCHANGE_CACHE) > 0, (
        "mesh session never took the collective data plane — the test "
        "is not covering the exchange program cache")
    before = _compile_snapshot()
    out2 = _mesh_q3(s, fact, dim)                 # repeat, same session
    s2 = _mesh_session()
    out3 = _mesh_q3(s2, fact, dim)                # fresh session
    after = _compile_snapshot()
    _assert_no_recompiles(before, after,
                          "repeated/cross-session mesh collective exchange")
    assert out1.equals(out2) and out1.equals(out3)
