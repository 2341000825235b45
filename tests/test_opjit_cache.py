"""General-path executable cache (execs/opjit.py): cache keying (hit on same
bucketed shape, miss on shape/dtype change), LRU bound, and bit-parity of the
jitted general path against the eager general path across project / filter /
join / aggregate over mixed null/string batches."""

import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.columnar.batch import TpuColumnarBatch
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.execs import opjit
from spark_rapids_tpu.expressions.arithmetic import Add, Multiply
from spark_rapids_tpu.expressions.base import (AttributeReference, EvalContext,
                                               Literal)
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.types import LongT


@pytest.fixture(autouse=True)
def _fresh_cache():
    opjit.clear_cache()
    yield
    opjit.clear_cache()


def _long_batch(n: int, dtype=pa.int64()) -> TpuColumnarBatch:
    vals = pa.array([None if i % 7 == 0 else i for i in range(n)], type=dtype)
    return TpuColumnarBatch.from_arrow(pa.table({"a": vals}))


def _expr(mult: int):
    a = AttributeReference("a", LongT, ordinal=0)
    return Add(Multiply(a, Literal(mult)), Literal(1))


def _eval(batch, ctx, mult=3):
    e = _expr(mult)
    return opjit.eval_exprs([e], [e.dtype], batch, ctx)


def test_cache_hit_on_same_bucketed_shape():
    ctx = EvalContext(RapidsConf({}))
    _eval(_long_batch(100), ctx)  # cap 128: trace
    s0 = opjit.cache_stats()
    assert s0["misses"] >= 1 and s0["traces"] >= 1
    _eval(_long_batch(120), ctx)  # still cap 128: reuse
    s1 = opjit.cache_stats()
    assert s1["hits"] == s0["hits"] + 1
    assert s1["misses"] == s0["misses"]


def test_cache_miss_on_shape_or_dtype_change():
    ctx = EvalContext(RapidsConf({}))
    _eval(_long_batch(100), ctx)
    s0 = opjit.cache_stats()
    _eval(_long_batch(300), ctx)  # cap 512: new executable
    s1 = opjit.cache_stats()
    assert s1["misses"] == s0["misses"] + 1
    _eval(_long_batch(100, dtype=pa.int32()), ctx)  # carrier change
    s2 = opjit.cache_stats()
    assert s2["misses"] == s1["misses"] + 1


def test_lru_eviction_at_cache_size():
    ctx = EvalContext(RapidsConf({"spark.rapids.tpu.opjit.cacheSize": "2"}))
    for mult in (2, 3, 5, 7):
        _eval(_long_batch(64), ctx, mult=mult)
    assert opjit.cache_len() <= 2
    # the most recent entry survived: re-running it is a hit, not a trace
    s0 = opjit.cache_stats()
    _eval(_long_batch(64), ctx, mult=7)
    s1 = opjit.cache_stats()
    assert s1["hits"] == s0["hits"] + 1 and s1["traces"] == s0["traces"]


# ---------------------------------------------------------------------------
# parity: jit on vs off must be bit-identical across the general path
# ---------------------------------------------------------------------------

_ROWS = [
    {"k": i % 5, "v": None if i % 6 == 0 else float(i) * 0.25,
     "s": None if i % 9 == 0 else f"s{i % 4}",
     "w": None if i % 11 == 0 else i}
    for i in range(300)
]

_BASE_CONF = {
    # force the general path: no compiled stages, no broadcast
    "spark.rapids.tpu.agg.compiledStage.enabled": "false",
    "spark.rapids.tpu.join.compiledStage.enabled": "false",
    "spark.sql.autoBroadcastJoinThreshold": "-1",
    "spark.sql.shuffle.partitions": "3",
    "spark.rapids.shuffle.compression.codec": "none",
}


def _run(build, jit: bool):
    conf = dict(_BASE_CONF)
    conf["spark.rapids.tpu.opjit.enabled"] = "true" if jit else "false"
    return build(TpuSession(conf))


def _parity(build):
    opjit.clear_cache()
    on = _run(build, True)
    assert opjit.cache_stats()["misses"] > 0, "jit path never engaged"
    off = _run(build, False)
    assert on == off
    return on


def test_parity_project_filter():
    def build(s):
        df = s.createDataFrame(_ROWS, num_partitions=2)
        return (df.filter((F.col("w") % 2 == 0) | F.col("v").isNull())
                .withColumn("x", F.col("v") * 2 + 1)
                .withColumn("y", F.concat(F.col("s"), F.lit("_t")))
                .select("k", "x", "y", "w")).collect()
    out = _parity(build)
    assert len(out) > 0


def test_parity_shuffled_join():
    dim = [{"k2": i, "p": None if i == 3 else f"p{i}", "q": i * 10}
           for i in range(5)]

    def build(s):
        fd = s.createDataFrame(_ROWS, num_partitions=2)
        dd = s.createDataFrame(dim, num_partitions=1)
        return (fd.join(dd, on=fd["k"] == dd["k2"])
                .select("k", "v", "s", "p", "q").collect())
    out = _parity(build)
    assert len(out) > 0


def test_parity_aggregate_int_and_string_keys():
    def build_int(s):
        df = s.createDataFrame(_ROWS, num_partitions=2)
        return (df.groupBy("k")
                .agg(F.sum(F.col("v")).alias("sv"),
                     F.avg(F.col("w")).alias("aw"),
                     F.count(F.col("v")).alias("cv"),
                     F.min(F.col("w")).alias("mn"),
                     F.max(F.col("v")).alias("mx"))).collect()

    def build_str(s):
        # string group key: sort phase stays eager, reduce phase still jits
        df = s.createDataFrame(_ROWS, num_partitions=2)
        return (df.groupBy("s")
                .agg(F.sum(F.col("w")).alias("sw"),
                     F.count(F.col("w")).alias("cw"))).collect()

    assert len(_parity(build_int)) == 5
    assert len(_parity(build_str)) > 0


def test_parity_global_aggregate():
    def build(s):
        df = s.createDataFrame(_ROWS, num_partitions=2)
        return df.agg(F.sum(F.col("v")).alias("sv"),
                      F.avg(F.col("v")).alias("av"),
                      F.count(F.col("w")).alias("cw")).collect()
    _parity(build)


def test_host_assisted_expression_splits_trace():
    """A host-assisted parent over a device-pure subtree: the subtree runs
    compiled, the parent eagerly — results identical to fully-eager."""
    def build(s):
        df = s.createDataFrame(_ROWS, num_partitions=1)
        # format_number is registered host_assisted; its numeric child is
        # device-pure and becomes a cached executable
        return df.select(
            F.format_number(F.col("v") * 3 + 0.5, 2).alias("fx")).collect()
    try:
        _parity(build)
    except AttributeError:
        pytest.skip("format_number not exposed in functions API")


def test_ansi_mode_stays_correct():
    """ANSI checks host-sync inside eval: the trace fails once, the
    fingerprint pins eager, and ANSI semantics are preserved."""
    rows = [{"a": 2**62, "b": 2**62}]
    conf = dict(_BASE_CONF)
    conf["spark.sql.ansi.enabled"] = "true"
    s = TpuSession(conf)
    df = s.createDataFrame(rows, num_partitions=1)
    with pytest.raises(Exception):
        df.select((F.col("a") + F.col("b")).alias("x")).collect()


def test_dispatch_accounting_segments_not_operators():
    """Dispatch accounting (docs/configs.md): with stage fusion on, a fused
    project/filter chain dispatches ONE cached "segment" program per batch;
    with fusion off the same chain pays one "project"/"filter" program per
    operator per batch."""
    def build(s):
        df = s.createDataFrame(_ROWS, num_partitions=2)
        return (df.filter(F.col("w") % 2 == 0)
                .withColumn("x", F.col("v") * 2 + 1)
                .withColumn("y", F.col("x") + F.col("w"))
                .select("k", "x", "y").collect())

    def kinds(fuse: bool):
        opjit.clear_cache()
        conf = dict(_BASE_CONF)
        conf["spark.rapids.tpu.opjit.fuseStages"] = str(fuse).lower()
        before = opjit.cache_stats()["calls_by_kind"]
        out = build(TpuSession(conf))
        after = opjit.cache_stats()["calls_by_kind"]
        return out, {k: after.get(k, 0) - before.get(k, 0)
                     for k in set(after) | set(before)
                     if after.get(k, 0) != before.get(k, 0)}

    fused_out, fused = kinds(True)
    perop_out, perop = kinds(False)
    assert fused_out == perop_out
    # 2 batches through a 4-op chain: 2 segment dispatches total vs one
    # filter + computed-project dispatch per operator per batch
    assert fused.get("segment") == 2
    assert "project" not in fused and "filter" not in fused
    assert "segment" not in perop
    assert perop.get("filter", 0) == 2 and perop.get("project", 0) >= 4
    assert sum(fused.values()) < sum(perop.values())


def test_metrics_registered_on_tpu_execs():
    """Every TpuExec carries the opjit metric set (execs/base.py)."""
    from spark_rapids_tpu.execs.base import TpuExec
    from spark_rapids_tpu.plan.overrides import TpuOverrides
    from spark_rapids_tpu.plan.planner import plan_physical
    s = TpuSession(dict(_BASE_CONF))
    q = s.createDataFrame(_ROWS[:10]).withColumn("x", F.col("w") + 1)
    conf = RapidsConf(dict(_BASE_CONF))
    final = TpuOverrides.apply(plan_physical(q._plan, conf), conf)
    tpu_nodes = [n for n in final.collect_nodes() if isinstance(n, TpuExec)]
    assert tpu_nodes
    for n in tpu_nodes:
        for name in ("opJitCacheHits", "opJitCacheMisses", "opJitTraceTime"):
            assert name in n.metrics
