"""Delta Lake read path + cache serializer tests."""

import json
import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from asserts import assert_tpu_and_cpu_are_equal_collect
from data_gen import DoubleGen, IntegerGen, gen_df

import spark_rapids_tpu.functions as F


def _write_delta_table(path: str, partitioned: bool = False) -> None:
    """Minimal writer for test fixtures: add-file commits incl. a remove."""
    os.makedirs(os.path.join(path, "_delta_log"), exist_ok=True)
    actions0 = [{"metaData": {"id": "t", "partitionColumns":
                              ["p"] if partitioned else []}}]
    files = []
    for i in range(3):
        t = gen_df([("a", IntegerGen(null_prob=0.0)),
                    ("v", DoubleGen(null_prob=0.0))], 50, 200 + i)
        if partitioned:
            rel = f"p={i}/part-{i}.parquet"
            os.makedirs(os.path.join(path, f"p={i}"), exist_ok=True)
        else:
            rel = f"part-{i}.parquet"
        pq.write_table(t, os.path.join(path, rel))
        files.append(rel)
        actions0.append({"add": {"path": rel, "partitionValues":
                                 {"p": str(i)} if partitioned else {},
                                 "size": 1, "modificationTime": 0,
                                 "dataChange": True}})
    with open(os.path.join(path, "_delta_log", "00000000000000000000.json"), "w") as f:
        for a in actions0:
            f.write(json.dumps(a) + "\n")
    # second commit removes file 2
    with open(os.path.join(path, "_delta_log", "00000000000000000001.json"), "w") as f:
        f.write(json.dumps({"remove": {"path": files[2], "dataChange": True}}) + "\n")


def test_delta_read_snapshot(tmp_path):
    path = str(tmp_path / "dtable")
    _write_delta_table(path)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.read.format("delta").load(path), ignore_order=True)
    # removed file is excluded: 2 files x 50 rows
    from spark_rapids_tpu.session import TpuSession
    assert TpuSession({}).read.format("delta").load(path).count() == 100


def test_delta_partitioned_read(tmp_path):
    path = str(tmp_path / "dtable_p")
    _write_delta_table(path, partitioned=True)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.read.format("delta").load(path)
        .groupBy("p").agg(F.count(F.col("a")).alias("c")),
        ignore_order=True)


def test_cache_roundtrip():
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({})
    df = s.createDataFrame(gen_df(
        [("a", IntegerGen()), ("v", DoubleGen())], 200, 17))
    cached = df.filter(F.col("a") > 0).cache()
    from spark_rapids_tpu.io.cache import CachedRelation
    assert isinstance(cached._plan, CachedRelation)
    assert cached._plan.compressed_bytes > 0
    r1 = cached.agg(F.count(F.col("a")).alias("c")).collect()
    r2 = cached.agg(F.count(F.col("a")).alias("c")).collect()
    assert r1 == r2
    expected = df.filter(F.col("a") > 0).count()
    assert r1[0]["c"] == expected


def test_cache_per_batch_serializer(tmp_path):
    """df.cache() stores per-batch parquet-compressed
    entries that decode independently and spill whole batches to disk under
    a host budget (reference ParquetCachedBatchSerializer)."""
    import pyarrow as pa
    from spark_rapids_tpu.io.cache import CachedRelation
    t = pa.table({"a": list(range(10_000)), "b": [f"s{i}" for i in range(10_000)]})
    rel = CachedRelation(t, batch_rows=1024)
    assert len(rel.batches) == 10  # ceil(10000/1024)
    assert rel.table().equals(t)
    # per-batch decode
    chunks = list(rel.iter_tables())
    assert [c.num_rows for c in chunks][:3] == [1024, 1024, 1024]
    # host budget forces disk spill of whole compressed batches
    budget = rel.compressed_bytes // 2
    rel2 = CachedRelation(t, batch_rows=1024, host_limit_bytes=budget,
                          spill_dir=str(tmp_path))
    assert any(b.on_disk for b in rel2.batches)
    assert rel2.host_bytes <= budget
    assert rel2.table().equals(t)  # decodes transparently from both tiers
    rel2.unpersist()
    assert not any(b.on_disk and b._path for b in rel2.batches)


def test_cache_through_session():
    from spark_rapids_tpu.session import TpuSession
    import spark_rapids_tpu.functions as F
    tpu = TpuSession({"spark.rapids.sql.enabled": "true"})
    df = tpu.createDataFrame([{"k": i % 5, "v": i} for i in range(200)])
    cached = df.cache()
    r1 = cached.groupBy("k").agg(F.sum(F.col("v")).alias("s")).orderBy("k").collect()
    r2 = cached.groupBy("k").agg(F.sum(F.col("v")).alias("s")).orderBy("k").collect()
    assert r1 == r2 and len(r1) == 5
    assert "CachedRelation" in cached.explain()
