"""Join CPU-vs-TPU equality (reference join_test.py slices)."""

import pytest

from asserts import assert_tpu_and_cpu_are_equal_collect
from data_gen import (DoubleGen, FloatGen, IntegerGen, LongGen, StringGen,
                      gen_df)

import spark_rapids_tpu.functions as F

ALL_JOIN_TYPES = ["inner", "left", "right", "full", "semi", "anti"]


def _sides(s, n_left=128, n_right=64, key_lo=0, key_hi=20, seed_l=1, seed_r=2,
           null_prob=0.2):
    left = s.createDataFrame(gen_df(
        [("k", IntegerGen(min_val=key_lo, max_val=key_hi, null_prob=null_prob)),
         ("lv", IntegerGen())], n_left, seed_l))
    right = s.createDataFrame(gen_df(
        [("k", IntegerGen(min_val=key_lo, max_val=key_hi, null_prob=null_prob)),
         ("rv", DoubleGen())], n_right, seed_r))
    return left, right


@pytest.mark.parametrize("join_type", ALL_JOIN_TYPES)
def test_join_int_key(join_type):
    def fn(s):
        l, r = _sides(s)
        return l.join(r, on="k", how=join_type)
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


@pytest.mark.parametrize("join_type", ["inner", "left", "full"])
def test_join_string_key(join_type):
    def fn(s):
        l = s.createDataFrame(gen_df(
            [("k", StringGen(alphabet="abcde", max_len=3, null_prob=0.2)),
             ("lv", IntegerGen())], 100, 3))
        r = s.createDataFrame(gen_df(
            [("k", StringGen(alphabet="abcde", max_len=3, null_prob=0.2)),
             ("rv", IntegerGen())], 60, 4))
        return l.join(r, on="k", how=join_type)
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


def test_join_multi_key():
    def fn(s):
        l = s.createDataFrame(gen_df(
            [("k1", IntegerGen(min_val=0, max_val=5)),
             ("k2", IntegerGen(min_val=0, max_val=3, null_prob=0.2)),
             ("lv", IntegerGen())], 100, 5))
        r = s.createDataFrame(gen_df(
            [("k1", IntegerGen(min_val=0, max_val=5)),
             ("k2", IntegerGen(min_val=0, max_val=3, null_prob=0.2)),
             ("rv", IntegerGen())], 80, 6))
        return l.join(r, on=["k1", "k2"], how="inner")
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


def test_join_float_key_nan():
    """Spark joins match NaN==NaN and -0.0==0.0 (normalized keys)."""
    def fn(s):
        import pyarrow as pa
        l = s.createDataFrame(pa.table({
            "k": pa.array([1.0, float("nan"), -0.0, None, 2.5], pa.float64()),
            "lv": pa.array([1, 2, 3, 4, 5])}))
        r = s.createDataFrame(pa.table({
            "k": pa.array([float("nan"), 0.0, 2.5, None], pa.float64()),
            "rv": pa.array([10, 20, 30, 40])}))
        return l.join(r, on="k", how="inner")
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


def test_join_condition_expression_keys():
    def fn(s):
        l, r = _sides(s)
        lr = l.withColumnRenamed("k", "lk")
        return lr.join(r, on=lr["lk"] == r["k"], how="inner")
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


@pytest.mark.parametrize("join_type", ["inner", "left", "semi", "anti"])
def test_join_with_residual_condition(join_type):
    def fn(s):
        l, r = _sides(s, null_prob=0.1)
        lr = l.withColumnRenamed("k", "lk")
        cond = (lr["lk"] == r["k"]) & (lr["lv"] > r["rv"])
        return lr.join(r, on=cond, how=join_type)
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


def test_cross_join():
    def fn(s):
        l = s.range(0, 13).withColumnRenamed("id", "a")
        r = s.range(0, 7).withColumnRenamed("id", "b")
        return l.crossJoin(r)
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


def test_nested_loop_conditional_join():
    def fn(s):
        l = s.range(0, 40).withColumnRenamed("id", "a")
        r = s.range(0, 30).withColumnRenamed("id", "b")
        return l.join(r, on=(l["a"] % 7) > (r["b"] % 5), how="inner")
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


@pytest.mark.parametrize("join_type", ["left", "right", "full", "semi", "anti"])
def test_nested_loop_join_types(join_type):
    """Non-equi conditions route through BNLJ; every join type must apply
    semi/anti/outer semantics, not inner (reference
    GpuBroadcastNestedLoopJoinExec join-type handling)."""
    def fn(s):
        l = s.range(0, 23).withColumnRenamed("id", "a")
        r = s.range(0, 17).withColumnRenamed("id", "b")
        return l.join(r, on=(l["a"] % 5) > (r["b"] % 4), how=join_type)
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


@pytest.mark.parametrize("join_type", ["semi", "anti"])
def test_null_safe_equality_join(join_type):
    """eqNullSafe (<=>) conditions must match null keys to null keys (used by
    the Iceberg equality-delete path for null-bearing delete rows)."""
    def fn(s):
        import pyarrow as pa
        l = s.createDataFrame(pa.table({
            "k": pa.array([1, 2, None, 4], pa.int64()),
            "v": pa.array(["a", "b", "c", "d"])}))
        r = s.createDataFrame(pa.table({"dk": pa.array([2, None], pa.int64())}))
        return l.join(r, on=l["k"].eqNullSafe(r["dk"]), how=join_type)
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


def test_subpartition_seed_distinct_from_exchange():
    """Sub-partitioning must re-bucket with a different murmur3 seed than the
    hash exchange, or co-partitioned inputs collapse into one sub-partition
    (reference GpuSubPartitionHashJoin.scala hashSeed=100)."""
    import numpy as np

    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.batch import TpuColumnarBatch
    from spark_rapids_tpu.columnar.vector import TpuColumnVector
    from spark_rapids_tpu.execs.base import TaskContext
    from spark_rapids_tpu.expressions.base import AttributeReference
    from spark_rapids_tpu.shuffle.partitioner import hash_partition_ids
    from spark_rapids_tpu.types import LongT

    n_exchange, k_sub = 4, 2
    keys = np.arange(4096, dtype=np.int64)
    col = TpuColumnVector(LongT, jnp.asarray(keys), None, len(keys))
    batch = TpuColumnarBatch([col], len(keys))
    ref = AttributeReference("k", LongT, False, ordinal=0)
    ctx = TaskContext()
    ids42 = np.asarray(hash_partition_ids(batch, [ref], n_exchange, ctx))
    # take one exchange partition's rows (co-partitioned input) and re-bucket
    part0 = keys[ids42[: len(keys)] == 0]
    col0 = TpuColumnVector(LongT, jnp.asarray(part0), None, len(part0))
    b0 = TpuColumnarBatch([col0], len(part0))
    sub = np.asarray(hash_partition_ids(b0, [ref], k_sub, ctx,
                                        seed=100))[: len(part0)]
    counts = np.bincount(sub, minlength=k_sub)
    # with the same seed every row lands in sub-partition 0; with a distinct
    # seed the split is roughly even
    assert counts.min() > len(part0) // 4, counts


def test_join_empty_sides():
    def fn_empty_right(s):
        l, _ = _sides(s)
        r = s.createDataFrame(gen_df(
            [("k", IntegerGen()), ("rv", DoubleGen())], 0))
        return l.join(r, on="k", how="left")
    assert_tpu_and_cpu_are_equal_collect(fn_empty_right, ignore_order=True)


def test_tpch_q3_shape():
    """TPC-H Q3-shaped query: scan→join→join→agg (BASELINE milestone #3)."""
    def fn(s):
        cust = s.createDataFrame(gen_df(
            [("custkey", IntegerGen(min_val=0, max_val=200, null_prob=0.0)),
             ("mktsegment", StringGen(alphabet="AB", max_len=1, null_prob=0.0))],
            200, 11))
        orders = s.createDataFrame(gen_df(
            [("orderkey", IntegerGen(min_val=0, max_val=500, null_prob=0.0)),
             ("o_custkey", IntegerGen(min_val=0, max_val=200, null_prob=0.0)),
             ("orderdate", IntegerGen(min_val=8000, max_val=11000, null_prob=0.0))],
            500, 12))
        lineitem = s.createDataFrame(gen_df(
            [("l_orderkey", IntegerGen(min_val=0, max_val=500, null_prob=0.0)),
             ("extendedprice", DoubleGen(null_prob=0.0)),
             ("discount", DoubleGen(null_prob=0.0))], 1000, 13))
        return (cust.filter(F.col("mktsegment") == "A")
                .join(orders, on=cust["custkey"] == orders["o_custkey"])
                .join(lineitem, on=orders["orderkey"] == lineitem["l_orderkey"])
                .withColumn("revenue",
                            F.col("extendedprice") * (1 - F.col("discount")))
                .groupBy("orderkey", "orderdate")
                .agg(F.sum(F.col("revenue")).alias("rev"))
                .sort(F.col("rev").desc(), F.col("orderdate").asc())
                .limit(10))
    assert_tpu_and_cpu_are_equal_collect(fn, approx_float=True)


def test_broadcast_hash_join():
    """Small build side over a partitioned stream side converts to the
    broadcast hash join (reference GpuBroadcastHashJoinExec)."""
    from spark_rapids_tpu.session import TpuSession

    def fn(s):
        big = s.createDataFrame(gen_df(
            [("k", IntegerGen(min_val=0, max_val=20, null_prob=0.1)),
             ("v", IntegerGen())], 500, 91), num_partitions=4)
        small = s.createDataFrame(gen_df(
            [("k", IntegerGen(min_val=0, max_val=20, null_prob=0.1)),
             ("w", DoubleGen())], 30, 92))
        return big.join(small, on="k", how="left")
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)
    # verify the broadcast exec is actually chosen
    s = TpuSession({})
    df = fn(s)
    tree = df.explain()
    assert "BroadcastHashJoin" in tree


def test_outer_bnlj_duplicate_output_names():
    """Join output may carry the same column name from both sides; the padded
    outer path and device→host conversion must not collapse duplicates."""
    def fn(s):
        import pyarrow as pa
        l = s.createDataFrame(pa.table({"k": [1, 2, 3], "v": [10, 0, 5]}))
        r = s.createDataFrame(pa.table({"k": [100, 900]}))
        return l.join(r, on=l["v"] > r["k"], how="left")
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


def test_int64_keys_distinct_above_32_bits():
    """BIGINT join keys are compared at their full width on every backend
    (the TPU carries s64 exactly as u32 pairs): keys equal mod 2^32 must NOT
    spuriously join (r3 review finding: a truncated i32 encoding verified
    1 == 2^32+1)."""
    import pyarrow as pa

    def fn(s):
        l = s.createDataFrame(pa.table(
            {"k": pa.array([1, 2**32 + 1, 7], pa.int64()),
             "lv": [1, 2, 3]}))
        r = s.createDataFrame(pa.table(
            {"k": pa.array([1, 7, 2**32 + 7], pa.int64()),
             "rv": [10, 20, 30]}))
        return l.join(r, on="k")
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


# DOUBLE keys on a backend without f64 bit views (the TPU): four doubles that
# are ONE value in f32 (100000.01f == 100000.0078125) and three values in f64
_F32_EQUAL_DOUBLES = [100000.011, 100000.009, 100000.01, 100000.011]


@pytest.fixture
def no_f64_bit_views(monkeypatch):
    """Steer utils/hw the way the v5e compiler answers it: f64 cannot be
    reinterpreted as integer bits, so DOUBLE keys take the f32-pair
    encoding. The program caches are keyed without the probe (it is a
    process constant in production), so they are cleared around the test."""
    from spark_rapids_tpu.execs import opjit
    from spark_rapids_tpu.utils import hw
    opjit.clear_cache()
    monkeypatch.setattr(hw, "f64_bit_views", lambda: False)
    yield
    opjit.clear_cache()


def test_double_keys_are_f32_equal_premise():
    import numpy as np
    assert len(set(np.float32(_F32_EQUAL_DOUBLES))) == 1
    assert len(set(_F32_EQUAL_DOUBLES)) == 3


def test_double_sort_key_not_narrowed_without_bit_views(no_f64_bit_views):
    import pyarrow as pa
    from spark_rapids_tpu.session import TpuSession
    import spark_rapids_tpu.functions as F
    s = TpuSession({"spark.rapids.sql.enabled": "true"})
    df = s.createDataFrame(pa.table(
        {"k": pa.array(_F32_EQUAL_DOUBLES, pa.float64()),
         "i": [0, 1, 2, 3]}))
    q = df.orderBy(F.col("k").desc())
    assert "TpuSort" in q.explain()
    assert [r["k"] for r in q.collect()] == sorted(_F32_EQUAL_DOUBLES,
                                                   reverse=True)


def test_double_group_key_not_narrowed_without_bit_views(no_f64_bit_views):
    import pyarrow as pa
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({"spark.rapids.sql.enabled": "true"})
    df = s.createDataFrame(pa.table(
        {"k": pa.array(_F32_EQUAL_DOUBLES, pa.float64())}))
    got = {r["k"]: r["count"] for r in df.groupBy("k").count().collect()}
    assert got == {100000.011: 2, 100000.009: 1, 100000.01: 1}


def test_double_join_key_not_narrowed_without_bit_views(no_f64_bit_views):
    import pyarrow as pa

    def fn(s):
        l = s.createDataFrame(pa.table(
            {"k": pa.array(_F32_EQUAL_DOUBLES, pa.float64()),
             "lv": [1, 2, 3, 4]}))
        r = s.createDataFrame(pa.table(
            {"k": pa.array([100000.01, 100000.009, 100000.0078125],
                           pa.float64()),
             "rv": [10, 20, 30]}))
        return l.join(r, on="k")
    assert_tpu_and_cpu_are_equal_collect(fn, ignore_order=True)


@pytest.mark.parametrize("how", ["inner", "left", "semi"])
def test_mixed_width_key_join_ground_truth(how):
    """int32 FK ⋈ int64 PK across multi-partition exchanges: without join-key
    type coercion, the two exchange sides hash different byte widths (murmur3
    hashes int32 and int64 differently by Spark spec) and co-partitioning
    silently drops ~(1-1/N) of matches ON BOTH ENGINES — so this asserts
    against a python ground truth, not the CPU oracle (r4 root-cause of the
    TPC-H q3 undercount)."""
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.session import TpuSession

    rng = np.random.default_rng(11)
    fk = rng.integers(0, 500, 5000).astype(np.int32)
    pk = np.arange(500, dtype=np.int64)
    want_inner = 5000  # every fk has exactly one pk match

    for enabled in ("true", "false"):
        s = TpuSession({"spark.rapids.sql.enabled": enabled,
                        "spark.sql.shuffle.partitions": "4"})
        dim = s.createDataFrame(pa.table({"pk": pk}))
        fact = s.createDataFrame(pa.table({"fk": fk}), num_partitions=4)
        out = fact.join(dim, on=fact["fk"] == dim["pk"], how=how)
        got = out.to_arrow().num_rows
        want = want_inner if how != "semi" else 5000
        assert got == want, (enabled, how, got, want)


# ---------------------------------------------------------------------------
# the probe's bucket directory (execs/joins.py: _join_prepare_build,
# _join_probe_ranges) against a plain sort-merge reference
# ---------------------------------------------------------------------------


def _sort_merge_pairs(b_keys, b_ok, p_keys, p_ok):
    """Plain reference: for each probe lane in order, the build lanes whose
    keys all equal its own, in build order (equal keys hash alike and the
    build's sort is stable, so that is the matcher's order too); a lane
    with a null key or beyond its side's rows pairs with nothing."""
    import numpy as np
    b_rows = np.stack(b_keys, axis=1)
    p_rows = np.stack(p_keys, axis=1)
    return [(pi, bi)
            for pi in np.flatnonzero(p_ok)
            for bi in np.flatnonzero(b_ok)
            if (p_rows[pi] == b_rows[bi]).all()]


def _directory_pairs(b_enc, b_rows, p_enc, p_rows, bits=None):
    """The matcher's three programs composed as `_device_equi_join` composes
    them, with the directory's size open to the test: (verified pairs in
    emit order, candidate count, the directory)."""
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_tpu.columnar.vector import bucket_capacity
    from spark_rapids_tpu.execs import joins

    def split(enc):
        cap = enc[0][0].shape[0]
        return ([v for v, _ in enc],
                [vd if vd is not None else jnp.ones((cap,), jnp.bool_)
                 for _, vd in enc])

    b_vals, b_valids = split(b_enc)
    p_vals, p_valids = split(p_enc)
    prep = joins._join_prepare_build(
        b_vals, b_valids, jnp.int32(b_rows),
        bits=bits or joins.dir_bits(b_vals[0].shape[0]))
    counts, lo, p_ok, total = joins._join_probe_ranges(
        prep.directory, p_vals, p_valids, jnp.int32(p_rows))
    total = int(total)
    pi, bi, ok, n_ok = joins._join_emit_pairs(
        counts, lo, prep.order, prep.b_ok, p_ok, list(prep.b_vals), p_vals,
        jnp.int32(total), out_cap=bucket_capacity(max(total, 1)))
    ok = np.asarray(ok)
    assert int(n_ok) == ok.sum()
    pairs = list(zip(np.asarray(pi)[ok].tolist(), np.asarray(bi)[ok].tolist()))
    return pairs, total, np.asarray(prep.directory)


def _int_side(keys, valid, rows, cap):
    """One side's encoded keys: int64 codes in `cap` lanes, the lanes past
    `rows` filled with a key that real rows hold too (padding must not pair)."""
    import jax.numpy as jnp
    import numpy as np
    enc = []
    for col, ok in zip(keys, valid):
        buf = np.full(cap, col[0] if len(col) else 0, np.int64)
        buf[:rows] = col
        v = np.ones(cap, bool)
        v[:rows] = ok
        enc.append((jnp.asarray(buf), jnp.asarray(v)))
    return enc


def _directory_case(name):
    """(build keys, build validity, probe keys, probe validity, bits) as
    lists a key column; bits None = the size production takes."""
    import numpy as np
    rng = np.random.default_rng(sum(map(ord, name)))
    yes = lambda n: np.ones(n, bool)  # noqa: E731
    if name == "unique":
        b = rng.permutation(400)[:150].astype(np.int64)
        p = rng.integers(0, 400, 300)
        return [b], [yes(150)], [p], [yes(300)], None
    if name == "duplicate_build":
        b = rng.integers(0, 40, 150)
        p = rng.integers(0, 60, 300)
        return [b], [yes(150)], [p], [yes(300)], None
    if name == "shared_bucket":
        # two buckets for 90 distinct keys: every lane's range holds build
        # rows of other keys, and only the equality pass tells them apart
        b = rng.integers(0, 90, 150)
        p = rng.integers(0, 120, 300)
        return [b], [yes(150)], [p], [yes(300)], 1
    if name == "nulls_and_padding":
        b = rng.integers(0, 30, 100)
        p = rng.integers(0, 30, 200)
        return [b], [rng.random(100) > 0.3], [p], [rng.random(200) > 0.3], None
    if name == "two_columns":
        b = [rng.integers(0, 8, 150), rng.integers(0, 6, 150)]
        p = [rng.integers(0, 8, 300), rng.integers(0, 6, 300)]
        return (b, [yes(150), rng.random(150) > 0.2],
                p, [yes(300), rng.random(300) > 0.2], None)
    raise AssertionError(name)


@pytest.mark.parametrize("name", ["unique", "duplicate_build", "shared_bucket",
                                  "nulls_and_padding", "two_columns"])
def test_directory_probe_pairs_match_sort_merge(name):
    import numpy as np

    from spark_rapids_tpu.columnar.vector import bucket_capacity
    from spark_rapids_tpu.execs import joins
    b_keys, b_valid, p_keys, p_valid, bits = _directory_case(name)
    b_rows, p_rows = len(b_keys[0]), len(p_keys[0])
    b_cap, p_cap = bucket_capacity(b_rows + 1), bucket_capacity(p_rows + 1)
    assert b_cap > b_rows and p_cap > p_rows  # padding lanes on both sides
    b_enc = _int_side(b_keys, b_valid, b_rows, b_cap)
    p_enc = _int_side(p_keys, p_valid, p_rows, p_cap)
    want = _sort_merge_pairs(b_keys, np.logical_and.reduce(b_valid),
                             p_keys, np.logical_and.reduce(p_valid))
    assert want, name
    got, total, directory = _directory_pairs(b_enc, b_rows, p_enc, p_rows, bits)
    assert got == want
    # the directory: a bucket's [start, end), a prefix sum over the valid
    # build rows alone
    assert directory.shape == (1 << (bits or joins.dir_bits(b_cap)), 2)
    assert directory[0, 0] == 0 and (directory[1:, 0] == directory[:-1, 1]).all()
    assert (directory[:, 1] >= directory[:, 0]).all()
    assert directory[-1, 1] == np.logical_and.reduce(b_valid).sum()
    assert total >= len(want)
    if bits == 1:
        assert total > 2 * len(want)     # the false candidates were there
    else:
        # the join's own entry sizes the directory itself: same pairs
        pi, bi, ok, _, total2, _ = joins._device_equi_join(
            b_enc, b_rows, p_enc, p_rows)
        ok = np.asarray(ok)
        assert total2 == total
        assert list(zip(np.asarray(pi)[ok].tolist(),
                        np.asarray(bi)[ok].tolist())) == want


def test_directory_probe_string_key_pairs_match_sort_merge():
    """A string key reaches the matcher as codes of a dictionary over both
    sides (`_encode_sides`), nulls among them."""
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.columnar.vector import TpuColumnVector
    from spark_rapids_tpu.execs import joins
    rng = np.random.default_rng(5)
    words = np.array(["ab", "abc", "b", "", "ba", "cab", "c"])
    b = [None if rng.random() < 0.2 else str(w) for w in rng.choice(words, 60)]
    p = [None if rng.random() < 0.2 else str(w) for w in rng.choice(words, 90)]
    bc = TpuColumnVector.from_arrow(pa.array(b, pa.string()))
    pc = TpuColumnVector.from_arrow(pa.array(p, pa.string()))
    p_enc, b_enc = joins._encode_sides([pc], [bc], 90, 60,
                                       pc.capacity, bc.capacity)
    want = [(pi, bi) for pi, pw in enumerate(p) for bi, bw in enumerate(b)
            if pw is not None and pw == bw]
    got, total, _ = _directory_pairs(b_enc, 60, p_enc, 90)
    assert got == want and total >= len(want)


#: every equi-join outside a segment: no broadcast, join fusion off
_UNFUSED = {"spark.sql.autoBroadcastJoinThreshold": "-1",
            "spark.rapids.tpu.opjit.fuseJoins": "false"}


@pytest.mark.parametrize("join_type", ALL_JOIN_TYPES)
def test_directory_probe_every_join_type(join_type):
    """`TpuShuffledHashJoinExec._join` reads verified pairs only, whatever
    the type: the CPU's rows, and the three counters of the directory."""
    from spark_rapids_tpu.session import TpuSession

    def fn(s):
        l, r = _sides(s, n_left=300, n_right=120, key_hi=60)
        return l.join(r, on="k", how=join_type)
    assert_tpu_and_cpu_are_equal_collect(fn, conf=_UNFUSED, ignore_order=True)
    s = TpuSession({**_UNFUSED, "spark.rapids.sql.enabled": "true"})
    df = fn(s)
    assert "TpuShuffledHashJoin" in df.explain() \
        or "TpuShuffledSymmetricHashJoin" in df.explain()
    rows = df.collect()
    c = s.last_query_phases()["counters"]
    assert c["join.builds_indexed"] == c["join.probes_indexed"] == 1
    if join_type == "inner":
        assert c["join.candidate_pairs"] >= len(rows) > 0
    assert c["join.candidate_pairs"] > 0


def test_a_build_probed_by_two_batches_is_prepared_once():
    """A broadcast build inside a segment: the probe side's two partitions
    share one prepared build (sort + directory), as they share the build."""
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.execs import opjit
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({"spark.rapids.sql.enabled": "true"})
    rng = np.random.default_rng(3)
    fk = rng.integers(0, 400, 4000)
    fact = s.createDataFrame(pa.table({"fk": fk, "v": np.arange(4000)}),
                             num_partitions=2)
    dim = s.createDataFrame(pa.table({"pk": np.arange(0, 400, 2),
                                      "w": np.arange(200) * 1.5}))
    df = fact.join(dim, on=fact["fk"] == dim["pk"]) \
        .select((F.col("v") + 1).alias("v1"), "w")
    assert "TpuFusedSegment[BroadcastHashJoin" in df.explain()
    before = dict(opjit.cache_stats()["calls_by_kind"])
    rows = df.collect()
    after = opjit.cache_stats()["calls_by_kind"]
    assert len(rows) == int((fk % 2 == 0).sum())
    c = s.last_query_phases()["counters"]
    assert (c["join.builds_indexed"], c["join.probes_indexed"]) == (1, 2)
    assert len(rows) <= c["join.candidate_pairs"] < 2 * len(rows)
    assert c["join.rows_out"] == len(rows)
    launched = {k: after.get(k, 0) - before.get(k, 0)
                for k in ("joinbuild", "joinprobe", "joinemit")}
    assert launched == {"joinbuild": 1, "joinprobe": 2, "joinemit": 2}
