"""Whole-stage compiled aggregation (execs/compiled.py): eligibility,
CPU-oracle parity across key/measure types, and the transparent fallbacks."""

import math

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.session import TpuSession


def _cpu():
    return TpuSession({"spark.rapids.sql.enabled": "false"})


def _compare(q, approx=True):
    a = q(TpuSession({})).collect()
    b = q(_cpu()).collect()
    ka = sorted(map(repr, ({k: (round(v, 6) if isinstance(v, float)
                                and not math.isnan(v) else v)
                            for k, v in r.items()} for r in a)))
    kb = sorted(map(repr, ({k: (round(v, 6) if isinstance(v, float)
                                and not math.isnan(v) else v)
                            for k, v in r.items()} for r in b)))
    assert ka == kb, (ka[:3], kb[:3])
    return a


def _uses_stage(df) -> bool:
    return "TpuCompiledAggStage" in df.explain()


def test_stage_compiles_string_keys_full_q1_shape():
    rng = np.random.default_rng(1)
    n = 20000
    t = pa.table({
        "flag": pa.array([None if x % 19 == 0 else f"f{int(x) % 3}"
                          for x in rng.integers(0, 100, n)]),
        "qty": rng.normal(size=n) * 10,
        "price": rng.normal(size=n) * 100,
        "disc": rng.random(n),
        "ship": rng.integers(0, 3000, n).astype(np.int32)})

    def q(s):
        df = s.createDataFrame(t, num_partitions=3)
        return (df.filter(F.col("ship") <= 2500)
                .withColumn("dp", F.col("price") * (1 - F.col("disc")))
                .groupBy("flag")
                .agg(F.sum(F.col("qty")), F.sum(F.col("dp")),
                     F.avg(F.col("qty")), F.min(F.col("price")),
                     F.max(F.col("price")), F.count(F.col("qty"))))

    assert _uses_stage(q(TpuSession({})))
    _compare(q)


def test_stage_int_and_bool_keys_with_nulls():
    rng = np.random.default_rng(2)
    n = 5000
    t = pa.table({
        "ik": pa.array([None if x % 13 == 0 else int(x)
                        for x in rng.integers(-20, 20, n)], pa.int64()),
        "bk": pa.array([None if x % 7 == 0 else bool(x % 2)
                        for x in rng.integers(0, 100, n)]),
        "v": rng.normal(size=n)})

    def q(s):
        return (s.createDataFrame(t, num_partitions=2)
                .groupBy("ik", "bk")
                .agg(F.count(F.col("v")), F.sum(F.col("v")),
                     F.min(F.col("v")), F.max(F.col("v"))))

    assert _uses_stage(q(TpuSession({})))
    _compare(q)


def test_stage_nan_min_max_semantics():
    t = pa.table({
        "k": pa.array([1, 1, 1, 2, 2, 3, 3], pa.int32()),
        "x": pa.array([1.0, float("nan"), 2.0,
                       float("nan"), float("nan"),
                       None, 5.0], pa.float64())})

    def q(s):
        return (s.createDataFrame(t).groupBy("k")
                .agg(F.min(F.col("x")).alias("mn"),
                     F.max(F.col("x")).alias("mx")))

    rows = {r["k"]: r for r in _compare(q)}
    assert rows[1]["mn"] == 1.0 and math.isnan(rows[1]["mx"])
    assert math.isnan(rows[2]["mn"]) and math.isnan(rows[2]["mx"])
    assert rows[3]["mn"] == 5.0 and rows[3]["mx"] == 5.0


def test_stage_inf_sum_carry_merge_is_nan_correct(recwarn):
    """A group holding +inf in one batch and -inf in another must sum to NaN
    on both engines (Java float semantics), and the carry merge must do it
    without emitting a RuntimeWarning (r3 verdict weak #6)."""
    import warnings
    t = pa.table({
        "k": pa.array(["a", "a", "b", "b", "c"] * 2),
        "v": pa.array([float("inf"), 1.0, 2.0, 3.0, 5.0,
                       float("-inf"), 4.0, 2.0, 3.0, 5.0]),
    })

    def q(s):
        return (s.createDataFrame(t, num_partitions=2)
                .groupBy("k")
                .agg(F.sum(F.col("v")).alias("sv"),
                     F.avg(F.col("v")).alias("av")))

    df = q(TpuSession({}))
    assert _uses_stage(df)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        rows = {r["k"]: r["sv"] for r in df.collect()}
    assert math.isnan(rows["a"])  # inf + -inf
    assert rows["b"] == 10.0 and rows["c"] == 10.0
    _compare(q)


def test_stage_global_agg():
    rng = np.random.default_rng(3)
    t = pa.table({"x": rng.normal(size=4000), "f": rng.random(4000)})

    def q(s):
        return (s.createDataFrame(t, num_partitions=2)
                .filter(F.col("f") < 0.5)
                .agg(F.sum(F.col("x") * F.col("f")).alias("r"),
                     F.count(F.col("x")).alias("c")))

    assert _uses_stage(q(TpuSession({})))
    _compare(q)


def test_stage_empty_input():
    t = pa.table({"k": pa.array([], pa.int32()),
                  "v": pa.array([], pa.float64())})

    def qg(s):
        return s.createDataFrame(t).groupBy("k").agg(F.sum(F.col("v")))

    def qglobal(s):
        return s.createDataFrame(t).agg(F.count(F.col("v")),
                                        F.sum(F.col("v")))

    assert _compare(qg) == []
    rows = _compare(qglobal)
    assert len(rows) == 1


def test_stage_all_null_int_key():
    t = pa.table({"k": pa.array([None, None, None], pa.int64()),
                  "v": pa.array([1.0, 2.0, 3.0])})

    def q(s):
        return s.createDataFrame(t).groupBy("k").agg(F.sum(F.col("v")))

    rows = _compare(q)
    assert len(rows) == 1 and rows[0]["k"] is None


def test_stage_high_cardinality_falls_back():
    """Key domain beyond maxGroups: general sort-based path answers."""
    n = 20000
    t = pa.table({"k": pa.array(range(n), pa.int64()),
                  "v": pa.array([1.0] * n)})

    def q(s):
        return s.createDataFrame(t).groupBy("k").agg(F.count(F.col("v")))

    rows = _compare(q)
    assert len(rows) == n


def test_stage_string_measure_not_compiled():
    """String aggregation inputs are ineligible; plan keeps the general agg."""
    t = pa.table({"k": pa.array([1, 2], pa.int32()),
                  "s": pa.array(["a", "b"])})
    df = (TpuSession({}).createDataFrame(t)
          .groupBy("k").agg(F.max(F.col("s"))))
    assert not _uses_stage(df)


def test_stage_disabled_by_conf():
    t = pa.table({"k": pa.array([1, 2], pa.int32()),
                  "v": pa.array([1.0, 2.0])})
    df = (TpuSession({"spark.rapids.tpu.agg.compiledStage.enabled": "false"})
          .createDataFrame(t).groupBy("k").agg(F.sum(F.col("v"))))
    assert not _uses_stage(df)


def test_stage_repeated_runs_reuse_compiled_program():
    """Process-wide compile cache: re-planning the same query must not grow
    the cache (re-trace) on every run."""
    from spark_rapids_tpu.execs import compiled as C
    rng = np.random.default_rng(5)
    t = pa.table({"k": rng.integers(0, 10, 2000).astype(np.int32),
                  "v": rng.normal(size=2000)})
    s = TpuSession({})
    df = s.createDataFrame(t).groupBy("k").agg(F.sum(F.col("v")))
    df.collect()
    size_after_first = len(C._STAGE_FN_CACHE)
    for _ in range(3):
        df.collect()
    assert len(C._STAGE_FN_CACHE) == size_after_first


def test_stage_date_key():
    import datetime as dt
    days = [dt.date(2024, 1, 1) + dt.timedelta(days=int(i % 5))
            for i in range(300)]
    t = pa.table({"d": pa.array(days, pa.date32()),
                  "v": pa.array([float(i) for i in range(300)])})

    def q(s):
        return s.createDataFrame(t).groupBy("d").agg(F.sum(F.col("v")))

    assert _uses_stage(q(TpuSession({})))
    _compare(q)


def test_stage_result_feeds_downstream_sort_limit():
    """The stage's host-assembled result must be consumable by device execs
    above it (sort/limit), not just the final collect."""
    rng = np.random.default_rng(7)
    t = pa.table({"k": rng.integers(0, 8, 3000).astype(np.int32),
                  "v": rng.normal(size=3000)})

    def q(s):
        return (s.createDataFrame(t).groupBy("k")
                .agg(F.sum(F.col("v")).alias("sv"))
                .sort(F.col("sv").desc()).limit(3))

    a = [r["k"] for r in q(TpuSession({})).collect()]
    b = [r["k"] for r in q(_cpu()).collect()]
    assert a == b


# ---------------------------------------------------------------------------
# the hand-off: a stage that gives up never makes its source produce a batch
# a second time (ISSUE 30)
# ---------------------------------------------------------------------------

_SMALL_BATCHES = {"spark.rapids.sql.batchSizeRows": "1000"}
_FEW_GROUPS = {"spark.rapids.tpu.agg.compiled.maxGroups": "64"}


def _wide_first(n):
    return pa.table({"k": pa.array(range(0, 997 * n, 997), pa.int64()),
                     "v": pa.array([float(i % 7) for i in range(n)])})


def _wide_last(n):
    """Ten keys everywhere but in the very last row."""
    return pa.table({"k": pa.array([i % 10 for i in range(n - 1)] + [10**6],
                                   pa.int64()),
                     "v": pa.array([float(i % 7) for i in range(n)])})


def _wide_second_of_five(n):
    """Ten keys but for one row of the second of five partitions."""
    keys = [i % 10 for i in range(n)]
    keys[n // 5 + 1] = 10**6
    return pa.table({"k": pa.array(keys, pa.int64()),
                     "v": pa.array([float(i % 7) for i in range(n)])})


def _dictionary_grows(n):
    """Three strings in the first third, two hundred from there on."""
    return pa.table({"k": pa.array([f"s{i % 3}" if i < n // 3
                                    else f"t{i % 200}" for i in range(n)]),
                     "v": pa.array([float(i % 7) for i in range(n)])})


def _tag_host_data(batch, name="k"):
    """The batch with column `name` marked as the host-side columns are:
    the same values, and a layout the stage's program cannot read."""
    import dataclasses
    from spark_rapids_tpu.columnar.batch import TpuColumnarBatch
    i = batch.names.index(name)
    col = batch.columns[i]
    cols = list(batch.columns)
    cols[i] = dataclasses.replace(col, host_data=col.to_arrow(),
                                  host_capacity=col.capacity)
    return TpuColumnarBatch(cols, batch.num_rows, batch.names)


def _live_spillables():
    from spark_rapids_tpu.memory.cleaner import MemoryCleaner
    return [r.kind for r in MemoryCleaner.get().live_resources()
            if r.kind.startswith("SpillableColumnarBatch")]


def _source_rows(session):
    """Rows the stage's source put out in the last query, from its own
    metrics: the host→device scans the plan holds (one; one a side under a
    union)."""
    scans = [m for op, m in session.last_query_metrics("DEBUG").items()
             if op.split(":")[1] == "HostToDeviceExec"]
    assert scans, session.last_query_metrics("DEBUG")
    return sum(m.get("numOutputRows", 0) for m in scans)


@pytest.mark.parametrize("case,make,conf,parts", [
    ("int_key_over_in_the_first_batch", _wide_first, _SMALL_BATCHES, 3),
    ("int_key_over_in_the_last_batch_of_the_last_partition", _wide_last,
     _SMALL_BATCHES, 3),
    ("string_dictionary_overflows_mid_stream", _dictionary_grows,
     {**_SMALL_BATCHES, **_FEW_GROUPS}, 3),
    ("key_column_the_stage_cannot_take", _wide_last, _SMALL_BATCHES, 2),
    ("several_partitions_one_batch_each", _wide_first, {}, 5),
    ("an_empty_partition_among_them", _wide_last, {}, 3),
    # a map task a partition: the exchange's pool threads pull the hand-off,
    # two partitions of it held and three still to run
    ("map_tasks_on_pool_threads", _wide_second_of_five,
     {**_SMALL_BATCHES, "spark.rapids.tpu.dispatch.partitionBatch": "1"}, 5),
    ("memory_pressure_reruns", _wide_last, _SMALL_BATCHES, 3),
])
def test_stage_hands_its_input_to_the_fallback(case, make, conf, parts,
                                               monkeypatch):
    from spark_rapids_tpu.execs import compiled as C
    from spark_rapids_tpu.execs.transitions import HostToDeviceExec
    n = 6000
    t = make(n)
    grew = []
    real_grow = C.TpuCompiledAggStageExec._grow_domains

    def grow(self, b, domains):
        grew.append(real_grow(self, b, domains))
        return grew[-1]
    monkeypatch.setattr(C.TpuCompiledAggStageExec, "_grow_domains", grow)

    if case == "key_column_the_stage_cannot_take":
        t = t.slice(0, n - 1)  # ten keys: only the column's layout is at fault
        real = HostToDeviceExec.internal_do_execute_columnar

        def tagged(self, idx, ctx):
            for i, b in enumerate(real(self, idx, ctx)):
                yield _tag_host_data(b) if (idx, i) == (1, 1) else b
        monkeypatch.setattr(HostToDeviceExec, "internal_do_execute_columnar",
                            tagged)
    if case == "memory_pressure_reruns":
        from spark_rapids_tpu.memory.hbm import TpuRetryOOM
        t = t.slice(0, n - 1)  # the stage would answer, but for the pressure

        def pressed(self, b, domains, ctx):
            raise TpuRetryOOM("injected")
        monkeypatch.setattr(C.TpuCompiledAggStageExec, "_run_batch", pressed)

    def q(s):
        df = s.createDataFrame(t, num_partitions=parts)
        if case == "an_empty_partition_among_them":
            nothing = s.createDataFrame(t.slice(0, 0), num_partitions=1)
            df = nothing.union(df.union(nothing))
        return df.groupBy("k").agg(F.sum(F.col("v")).alias("sv"),
                                   F.count(F.col("v")).alias("c"))

    before = _live_spillables()
    s = TpuSession(conf)
    df = q(s)
    assert _uses_stage(df)
    got = df.collect()
    want = q(_cpu()).collect()
    assert sorted(map(repr, got)) == sorted(map(repr, want))
    assert _live_spillables() == before
    counters = s.last_query_phases()["counters"]
    if case == "memory_pressure_reruns":
        # pass 1 pulled the whole source, the pressure came in pass 2, and
        # the general path ran the source again
        assert (counters["stage.fallback_handoffs"],
                counters["stage.fallback_reruns"]) == (0, 1)
        assert _source_rows(s) == 2 * t.num_rows
        return
    assert (counters["stage.fallback_handoffs"],
            counters["stage.fallback_reruns"]) == (1, 0)
    # every source partition executed once: each row left the scan once
    assert _source_rows(s) == t.num_rows
    # statistics stop with the batch that decides
    assert grew.count(False) == 1 and grew[-1] is False
    if case == "int_key_over_in_the_first_batch":
        assert grew == [False]
    elif case in ("int_key_over_in_the_last_batch_of_the_last_partition",
                  "an_empty_partition_among_them"):
        assert len(grew) == (6 if conf else parts), grew
    elif case == "string_dictionary_overflows_mid_stream":
        assert 2 < len(grew) < 6, grew
    elif case == "key_column_the_stage_cannot_take":
        assert len(grew) == 3 + 2, grew     # partition 0's three, then (1, 1)
    elif case == "map_tasks_on_pool_threads":
        assert len(grew) == 2 + 1, grew     # partition 0's two, then (1, 0)
