"""Window function tests (reference window_function_test.py slices)."""

import pytest

from asserts import assert_tpu_and_cpu_are_equal_collect
from data_gen import DoubleGen, IntegerGen, LongGen, StringGen, gen_df

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.window import Window


def _df(s, n=200, seed=50):
    gens = [("k", IntegerGen(min_val=0, max_val=5, null_prob=0.1)),
            ("o", IntegerGen(min_val=0, max_val=100)),
            ("v", LongGen(null_prob=0.2)),
            ("d", DoubleGen(null_prob=0.2))]
    return s.createDataFrame(gen_df(gens, n, seed))


def test_row_number():
    w = Window.partitionBy("k").orderBy("o")
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _df(s).select(
            F.col("k"), F.col("o"),
            F.row_number().over(w).alias("rn")),
        ignore_order=True)


def test_rank_dense_rank():
    w = Window.partitionBy("k").orderBy("o")
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _df(s).select(
            F.col("k"), F.col("o"),
            F.rank().over(w).alias("r"),
            F.dense_rank().over(w).alias("dr")),
        ignore_order=True)


def test_lead_lag():
    w = Window.partitionBy("k").orderBy("o", "v")
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _df(s).select(
            F.col("k"), F.col("o"), F.col("v"),
            F.lead(F.col("v")).over(w).alias("ld"),
            F.lag(F.col("v"), 2).over(w).alias("lg2")),
        ignore_order=True)


def test_running_aggregates():
    w = Window.partitionBy("k").orderBy("o", "v")
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _df(s).select(
            F.col("k"), F.col("o"), F.col("v"),
            F.sum(F.col("v")).over(w).alias("rsum"),
            F.count(F.col("v")).over(w).alias("rcnt"),
            F.min(F.col("v")).over(w).alias("rmin"),
            F.max(F.col("v")).over(w).alias("rmax")),
        ignore_order=True)


def test_whole_partition_aggregate():
    w = Window.partitionBy("k")
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _df(s).select(
            F.col("k"), F.col("v"),
            F.sum(F.col("v")).over(w).alias("total"),
            F.avg(F.col("d")).over(w).alias("mean")),
        ignore_order=True, approx_float=True)


def test_bounded_rows_frame():
    w = Window.partitionBy("k").orderBy("o", "v").rowsBetween(-2, 2)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _df(s).select(
            F.col("k"), F.col("o"), F.col("v"),
            F.sum(F.col("v")).over(w).alias("wsum"),
            F.count(F.col("v")).over(w).alias("wcnt"),
            F.avg(F.col("v")).over(w).alias("wavg")),
        ignore_order=True, approx_float=True)


def test_window_no_partition():
    w = Window.orderBy("o", "v")
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _df(s, n=80).select(
            F.col("o"), F.col("v"),
            F.row_number().over(w).alias("rn"),
            F.sum(F.col("v")).over(w).alias("rsum")),
        ignore_order=True)


def test_window_string_partition():
    def fn(s):
        df = s.createDataFrame(gen_df(
            [("g", StringGen(alphabet="xyz", max_len=1, null_prob=0.1)),
             ("o", IntegerGen()), ("v", IntegerGen())], 150, 60))
        w = Window.partitionBy("g").orderBy("o", "v")
        return df.select(F.col("g"), F.col("o"),
                         F.row_number().over(w).alias("rn"),
                         F.sum(F.col("v")).over(w).alias("rs"))
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


def test_bounded_minmax_frames():
    """Bounded min/max frames run on device via the
    sparse-table range reduce (reference batched-bounded strategy,
    GpuWindowExecMeta.scala:262-299) — previously tagged unsupported."""
    import random
    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.window import Window
    tpu = TpuSession({"spark.rapids.sql.enabled": "true"})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    rng = random.Random(3)
    rows = [{"g": i % 4, "o": i, "v": rng.randint(-50, 50) if i % 7 else None}
            for i in range(120)]

    def q(sess, lo, hi, agg):
        w = Window.partitionBy("g").orderBy("o").rowsBetween(lo, hi)
        df = sess.createDataFrame(rows)
        return (df.select("g", "o", agg(F.col("v")).over(w).alias("x"))
                  .orderBy("g", "o"))

    for lo, hi in ((-3, 0), (-2, 2), (0, 4), (-5, -1), (1, 3)):
        for agg in (F.min, F.max):
            assert q(tpu, lo, hi, agg).collect() == \
                q(cpu, lo, hi, agg).collect(), (lo, hi, agg)
    plan = q(tpu, -3, 0, F.min).explain()
    assert "TpuWindow" in plan, plan


def test_bounded_minmax_nan_frames():
    """Spark float ordering in bounded frames: NaN is greatest — max sees it,
    min skips it unless the whole frame is NaN."""
    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.window import Window
    nan = float("nan")
    tpu = TpuSession({"spark.rapids.sql.enabled": "true"})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    rows = [{"g": 0, "o": i, "v": v} for i, v in enumerate(
        [1.0, nan, 3.0, nan, nan, 2.0, None, 5.0])]

    def q(sess, agg):
        w = Window.partitionBy("g").orderBy("o").rowsBetween(-1, 1)
        df = sess.createDataFrame(rows)
        return (df.select("o", agg(F.col("v")).over(w).alias("x"))
                  .orderBy("o"))

    import math

    def canon(rs):
        return [("nan" if isinstance(r["x"], float) and math.isnan(r["x"])
                 else r["x"]) for r in rs]

    for agg in (F.min, F.max):
        assert canon(q(tpu, agg).collect()) == canon(q(cpu, agg).collect()), \
            agg.__name__


def test_running_minmax_nan():
    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.window import Window
    import math
    nan = float("nan")
    tpu = TpuSession({"spark.rapids.sql.enabled": "true"})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    rows = [{"g": 0, "o": i, "v": v} for i, v in enumerate(
        [nan, 1.0, nan, 2.0, None, 0.5])]

    def q(sess, agg):
        w = Window.partitionBy("g").orderBy("o")  # running frame
        df = sess.createDataFrame(rows)
        return df.select("o", agg(F.col("v")).over(w).alias("x")).orderBy("o")

    def canon(rs):
        return [("nan" if isinstance(r["x"], float) and math.isnan(r["x"])
                 else r["x"]) for r in rs]

    for agg in (F.min, F.max):
        assert canon(q(tpu, agg).collect()) == canon(q(cpu, agg).collect()), \
            agg.__name__


def test_ntile():
    w = Window.partitionBy("k").orderBy("o", "v")
    for n in (1, 3, 4, 7):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s, n=n: _df(s).select(
                F.col("k"), F.col("o"), F.col("v"),
                F.ntile(n).over(w).alias("t")),
            ignore_order=True)


def test_percent_rank_cume_dist():
    w = Window.partitionBy("k").orderBy("o")
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _df(s).select(
            F.col("k"), F.col("o"),
            F.percent_rank().over(w).alias("pr"),
            F.cume_dist().over(w).alias("cd")),
        ignore_order=True)


def test_percent_rank_single_row_partitions():
    """size-1 partitions: percent_rank 0.0, cume_dist 1.0."""
    w = Window.partitionBy("o").orderBy("v")  # o nearly unique at n=40
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _df(s, n=40).select(
            F.col("o"), F.col("v"),
            F.percent_rank().over(w).alias("pr"),
            F.cume_dist().over(w).alias("cd")),
        ignore_order=True)


def test_collect_list_over_window_running_and_whole():
    """Device ragged-gather path: unbounded..current and whole-partition
    frames; nulls dropped, empty frames yield []."""
    wr = Window.partitionBy("k").orderBy("o", "v")
    ww = Window.partitionBy("k")
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _df(s, n=120).select(
            F.col("k"), F.col("o"), F.col("v"),
            F.collect_list(F.col("v")).over(wr).alias("running"),
            F.collect_list(F.col("v")).over(ww).alias("whole")),
        ignore_order=True)


def test_collect_set_over_window_host_assisted():
    w = Window.partitionBy("k")
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _df(s, n=80).select(
            F.col("k"),
            F.collect_set(F.col("k")).over(w).alias("ks")),
        ignore_order=True)


def test_collect_list_bounded_frame_host_path():
    w = Window.partitionBy("k").orderBy("o", "v").rowsBetween(-1, 1)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _df(s, n=60).select(
            F.col("k"), F.col("o"), F.col("v"),
            F.collect_list(F.col("v")).over(w).alias("nbrs")),
        ignore_order=True)


def test_default_frame_is_range_with_peers():
    """Spark's default ordered frame is RANGE UNBOUNDED..CURRENT ROW: rows
    tied on the order key all see the full peer group (r3 review finding —
    ROWS semantics on ties silently diverges)."""
    import pyarrow as pa

    t = pa.table({"k": [1, 1, 1, 1, 2, 2],
                  "o": [10, 10, 10, 20, 5, 5],
                  "v": [1.0, 2.0, 4.0, 8.0, 16.0, 32.0]})
    w = Window.partitionBy("k").orderBy("o")

    def fn(s):
        df = s.createDataFrame(t)
        return df.select(F.col("k"), F.col("o"), F.col("v"),
                         F.sum(F.col("v")).over(w).alias("rsum"),
                         F.min(F.col("v")).over(w).alias("rmin"),
                         F.count(F.col("v")).over(w).alias("rcnt"),
                         F.collect_list(F.col("v")).over(w).alias("rlist"))
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)
    # explicit golden: all three o=10 ties share sum 7.0 and the same list
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({})
    rows = fn(s).collect()
    tied = [r for r in rows if r["k"] == 1 and r["o"] == 10]
    assert all(r["rsum"] == 7.0 for r in tied)
    assert all(r["rcnt"] == 3 for r in tied)
    assert all(sorted(r["rlist"]) == [1.0, 2.0, 4.0] for r in tied)


def test_rows_between_keeps_row_semantics_on_ties():
    import pyarrow as pa
    t = pa.table({"o": [10, 10, 20], "v": [1.0, 2.0, 4.0]})
    w = Window.orderBy("o", "v").rowsBetween(-10**9, 0)  # unbounded..current
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({})
    rows = (s.createDataFrame(t)
            .select(F.col("v"), F.sum(F.col("v")).over(w).alias("rs"))
            .collect())
    by_v = {r["v"]: r["rs"] for r in rows}
    assert by_v == {1.0: 1.0, 2.0: 3.0, 4.0: 7.0}
