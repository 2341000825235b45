"""Bucketed writes/reads (reference
GpuFileFormatWriter bucketing + GpuFileSourceScanExec bucket pruning)."""

import os

import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.session import TpuSession


def _write(tmp_path, n_buckets=4):
    s = TpuSession({})
    t = pa.table({"k": list(range(100)), "v": [f"v{i}" for i in range(100)]})
    df = s.createDataFrame(t, num_partitions=2)
    (df.write.bucketBy(n_buckets, "k").mode("overwrite")
     .parquet(str(tmp_path / "bt")))
    return s, str(tmp_path / "bt")


def test_bucketed_write_layout(tmp_path):
    _, path = _write(tmp_path)
    files = sorted(os.listdir(path))
    assert "_bucket_spec.json" in files
    data = [f for f in files if f.endswith(".parquet")]
    # per task up to 4 bucket files, named part-NNNNN_BBBBB
    assert data and all("_" in f for f in data)
    buckets = {f.split("_")[1].split(".")[0] for f in data}
    assert buckets <= {f"{b:05d}" for b in range(4)}
    assert len(buckets) > 1


def test_bucketed_roundtrip_and_pruning(tmp_path):
    s, path = _write(tmp_path)
    df = s.read.parquet(path)
    out = df.to_arrow()
    assert out.num_rows == 100
    assert sorted(r["k"] for r in out.to_pylist()) == list(range(100))
    # equality filter on the bucket column: result correct AND the scan
    # reads only that bucket's files
    q = df.filter(F.col("k") == 37)
    rows = q.collect()
    assert rows == [{"k": 37, "v": "v37"}]
    # count pruned files via the physical scan
    from spark_rapids_tpu.io.parquet import FileScanBase
    import spark_rapids_tpu.io.parquet as P
    seen = {}
    orig = FileScanBase._prune_by_bucket

    def spy(self, files, conf):
        kept = orig(self, files, conf)
        seen["before"], seen["after"] = len(files), len(kept)
        return kept
    FileScanBase._prune_by_bucket = spy
    try:
        q.collect()
    finally:
        FileScanBase._prune_by_bucket = orig
    assert seen["after"] < seen["before"], seen


def test_bucketing_disabled_by_conf(tmp_path):
    s = TpuSession({
        "spark.rapids.sql.format.write.bucketing.enabled": "false"})
    t = pa.table({"k": [1, 2, 3]})
    df = s.createDataFrame(t)
    df.write.bucketBy(4, "k").mode("overwrite").parquet(
        str(tmp_path / "nb"))
    files = os.listdir(str(tmp_path / "nb"))
    assert "_bucket_spec.json" not in files
    assert all("_0" not in f for f in files if f.endswith(".parquet"))


def test_bucket_pruning_int32_column(tmp_path):
    """The pruning hash must use the COLUMN type, not the literal's inferred
    int64 — murmur3 of int32 and int64 differ (r4 review finding)."""
    s = TpuSession({})
    t = pa.table({"k": pa.array(list(range(60)), pa.int32()),
                  "v": list(range(60))})
    df = s.createDataFrame(t)
    df.write.bucketBy(4, "k").mode("overwrite").parquet(str(tmp_path / "b32"))
    rdf = s.read.parquet(str(tmp_path / "b32"))
    for probe in (0, 7, 33, 59):
        rows = rdf.filter(F.col("k") == probe).collect()
        assert rows == [{"k": probe, "v": probe}], (probe, rows)


def test_bucketed_append_spec_mismatch_rejected(tmp_path):
    """Appending with a different bucket spec must fail, not silently mix
    two hash moduli behind one sidecar (ADVICE r4)."""
    s, path = _write(tmp_path, n_buckets=4)
    t2 = pa.table({"k": [200, 201], "v": ["a", "b"]})
    df2 = s.createDataFrame(t2)
    with pytest.raises(ValueError, match="bucket spec"):
        df2.write.bucketBy(8, "k").mode("append").parquet(path)
    # same spec appends fine and stays readable
    df2.write.bucketBy(4, "k").mode("append").parquet(path)
    out = s.read.parquet(path).to_arrow()
    assert out.num_rows == 102
