"""Parquet parity hardening: legacy-calendar rebase, INT96 timestamps, and
bounded-memory chunked decode (reference
datetimeRebaseUtils.scala + GpuParquetScan.scala:446 + chunked reader)."""

import datetime as dt
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.io.rebase import (julian_to_gregorian_days,
                                        julian_to_gregorian_micros,
                                        needs_rebase)
from spark_rapids_tpu.session import TpuSession


def _sessions():
    return (TpuSession({}), TpuSession({"spark.rapids.sql.enabled": "false"}))


def test_julian_to_gregorian_known_pairs():
    # civil fields are preserved: hybrid-days(civil) -> proleptic-days(civil)
    # pairs computed from python's proleptic calendar + the 5/10-day era gaps
    assert julian_to_gregorian_days(np.array([-354280]))[0] == \
        (dt.date(1000, 1, 1) - dt.date(1970, 1, 1)).days
    assert julian_to_gregorian_days(np.array([-719164]))[0] == \
        (dt.date(1, 1, 1) - dt.date(1970, 1, 1)).days
    # on/after 1582-10-15 the calendars agree: identity
    mod = np.array([0, 10957, -141427], np.int64)
    assert (julian_to_gregorian_days(mod) == mod).all()
    # micros: day part shifts, intra-day part intact
    us = np.int64(-354280) * 86_400_000_000 + 12_345
    got = julian_to_gregorian_micros(np.array([us]))[0]
    want_day = (dt.date(1000, 1, 1) - dt.date(1970, 1, 1)).days
    assert got == want_day * 86_400_000_000 + 12_345


def test_needs_rebase_marker_and_mode():
    assert needs_rebase({b"org.apache.spark.legacyDateTime": b""},
                        "CORRECTED")
    assert needs_rebase({b"org.apache.spark.legacyINT96": b""}, "CORRECTED")
    assert not needs_rebase({b"other": b""}, "CORRECTED")
    assert not needs_rebase(None, "CORRECTED")
    assert needs_rebase(None, "LEGACY")


def test_legacy_marked_file_rebases_on_read(tmp_path):
    """A fixture file simulating a Spark 2.x writer: hybrid-calendar day
    values + the legacy footer marker. The scan must yield the civil dates
    the legacy writer meant."""
    civil = [dt.date(1000, 1, 1), dt.date(1, 1, 1), dt.date(2020, 5, 17)]
    hybrid_days = [-354280, -719164,
                   (dt.date(2020, 5, 17) - dt.date(1970, 1, 1)).days]
    t = pa.table({"d": pa.array(hybrid_days, pa.int32()).cast(pa.date32()),
                  "v": [1, 2, 3]})
    t = t.replace_schema_metadata(
        {b"org.apache.spark.legacyDateTime": b""})
    path = os.path.join(tmp_path, "legacy.parquet")
    pq.write_table(t, path)
    for s in _sessions():
        out = s.read.parquet(path).to_arrow()
        got = sorted((r["v"], r["d"]) for r in out.to_pylist())
        assert [d for _, d in got] == civil, got


def test_unmarked_file_reads_as_corrected(tmp_path):
    days = [(dt.date(1000, 1, 6) - dt.date(1970, 1, 1)).days]
    t = pa.table({"d": pa.array(days, pa.int32()).cast(pa.date32())})
    path = os.path.join(tmp_path, "modern.parquet")
    pq.write_table(t, path)
    s, _ = _sessions()
    out = s.read.parquet(path).to_arrow()
    assert out.column("d").to_pylist() == [dt.date(1000, 1, 6)]


def test_int96_timestamps_read(tmp_path):
    """INT96-encoded timestamps (old Spark/Impala writers) decode and
    normalize to microseconds."""
    ts = [dt.datetime(2015, 3, 14, 9, 26, 53, 589793),
          dt.datetime(1970, 1, 1, 0, 0, 0),
          dt.datetime(2038, 1, 19, 3, 14, 7)]
    t = pa.table({"ts": pa.array(ts, pa.timestamp("us"))})
    path = os.path.join(tmp_path, "int96.parquet")
    pq.write_table(t, path, use_deprecated_int96_timestamps=True)
    # confirm the file really is INT96
    assert pq.ParquetFile(path).schema.column(0).physical_type == "INT96"
    want = [v.replace(tzinfo=dt.timezone.utc) for v in ts]
    for s in _sessions():
        out = s.read.parquet(path).to_arrow()
        got = [v.astimezone(dt.timezone.utc)
               for v in out.column("ts").to_pylist()]
        assert got == want


def test_chunked_decode_bounded_and_equal(tmp_path):
    """A multi-row-group file reads identically with a tiny decode cap (many
    chunks) and with chunking disabled (one table)."""
    n = 50_000
    rng = np.random.default_rng(5)
    t = pa.table({"k": rng.integers(0, 100, n), "v": rng.random(n)})
    path = os.path.join(tmp_path, "big.parquet")
    pq.write_table(t, path, row_group_size=2_000)
    assert pq.ParquetFile(path).metadata.num_row_groups >= 20
    res = {}
    for cap in ("1024", "0"):  # 1 KiB cap -> one chunk per row group; 0=off
        s = TpuSession({
            "spark.rapids.sql.reader.chunked.maxDecodeBytes": cap,
            "spark.rapids.sql.format.parquet.reader.type": "PERFILE"})
        import spark_rapids_tpu.functions as F
        df = s.read.parquet(path)
        out = df.groupBy("k").agg(F.count_star().alias("n"),
                                  F.sum(F.col("v")).alias("sv")).to_arrow()
        res[cap] = sorted((r["k"], r["n"], round(r["sv"], 6))
                          for r in out.to_pylist())
    assert res["1024"] == res["0"]
    assert sum(x[1] for x in res["0"]) == n


def test_chunked_decode_respects_rowgroup_pruning(tmp_path):
    """Pushed filters prune row groups by footer statistics in the chunked
    reader too."""
    t = pa.table({"a": list(range(10_000))})
    path = os.path.join(tmp_path, "pruned.parquet")
    pq.write_table(t, path, row_group_size=1_000)
    import spark_rapids_tpu.functions as F
    s = TpuSession({
        "spark.rapids.sql.reader.chunked.maxDecodeBytes": "1024",
        "spark.rapids.sql.format.parquet.reader.type": "PERFILE"})
    out = s.read.parquet(path).filter(F.col("a") >= 9_500).to_arrow()
    assert out.num_rows == 500
    assert min(out.column("a").to_pylist()) == 9_500


def test_nanosecond_timestamps_truncate_to_micros(tmp_path):
    """Files with genuine ns precision must read (Spark truncates to us),
    not crash on a safe-cast error (r4 review finding)."""
    t = pa.table({"ts": pa.array([1_000_000_001, 1_500_000_999],
                                 pa.timestamp("ns"))})
    path = os.path.join(tmp_path, "ns.parquet")
    pq.write_table(t, path, coerce_timestamps=None)
    assert pq.read_schema(path).field("ts").type == pa.timestamp("ns")
    s = TpuSession({})
    out = s.read.parquet(path).to_arrow()
    got = [v.microsecond for v in out.column("ts").to_pylist()]
    assert got == [0, 500000]  # sub-us digits truncated
