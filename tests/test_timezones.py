"""Non-UTC session timezone support.

The device path localizes timestamp micros through tzdb.TimeZoneDB (TZif
transition tables, searchsorted + gather — reference GpuTimeZoneDB); the CPU
oracle localizes through arrow/zoneinfo. Both must agree, including across
DST transitions with java.time gap/overlap resolution.
"""

import datetime as dt

import numpy as np
import pyarrow as pa
import pytest
from zoneinfo import ZoneInfo

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.columnar.batch import TpuColumnarBatch
from spark_rapids_tpu.columnar.vector import TpuColumnVector
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.expressions import datetime as DT
from spark_rapids_tpu.expressions.base import (AttributeReference, EvalContext,
                                               Literal)
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.tzdb import TimeZoneDB

ZONES = ["America/New_York", "Europe/Berlin", "Asia/Kolkata",
         "Australia/Lord_Howe", "America/Sao_Paulo"]

# instants straddling DST transitions + ordinary dates, 1960..2036
INSTANTS = [
    dt.datetime(2024, 3, 10, 6, 59, 59),   # just before US spring-forward
    dt.datetime(2024, 3, 10, 7, 0, 1),     # just after
    dt.datetime(2024, 11, 3, 5, 30),       # inside US fall-back overlap (UTC)
    dt.datetime(2024, 11, 3, 6, 30),
    dt.datetime(1969, 12, 31, 23, 59, 59),
    dt.datetime(2000, 2, 29, 12, 0),
    dt.datetime(2036, 7, 1, 0, 0),
    dt.datetime(1960, 1, 1, 6, 0),
    None,
]


def _ctx(tz):
    conf = RapidsConf({"spark.sql.session.timeZone": tz})
    return EvalContext(conf)


def _batch():
    vals = [None if v is None else v.replace(tzinfo=dt.timezone.utc)
            for v in INSTANTS]
    arr = pa.array(vals, pa.timestamp("us", tz="UTC"))
    col = TpuColumnVector.from_arrow(arr)
    batch = TpuColumnarBatch([col], len(vals), names=["ts"])
    ref = AttributeReference("ts", col.dtype, ordinal=0)
    return batch, pa.table({"ts": arr}), ref


@pytest.mark.parametrize("zone", ZONES)
def test_tzdb_matches_zoneinfo(zone):
    db = TimeZoneDB.get(zone)
    assert db is not None, f"no TZif table for {zone}"
    zi = ZoneInfo(zone)
    rng = np.random.default_rng(7)
    micros = rng.integers(-631152000, 2114380800, size=500) * 1_000_000
    local = db.utc_to_local_np(micros)
    for m, l in zip(micros[:100], local[:100]):
        t = dt.datetime.fromtimestamp(m / 1e6, dt.timezone.utc).astimezone(zi)
        want = int((t.replace(tzinfo=None)
                    - dt.datetime(1970, 1, 1)).total_seconds() * 1e6)
        assert want == l, (zone, m)


@pytest.mark.parametrize("zone", ZONES)
@pytest.mark.parametrize("field", [DT.Year, DT.Month, DT.DayOfMonth, DT.Hour,
                                   DT.Minute, DT.DayOfWeek, DT.DayOfYear])
def test_timestamp_fields_local(zone, field):
    batch, tbl, ref = _batch()
    ctx = _ctx(zone)
    expr = field(ref)
    got = expr.eval_tpu(batch, ctx).to_arrow().to_pylist()[: len(INSTANTS)]
    want = expr.eval_cpu(tbl, ctx).to_pylist()
    assert got == want, f"{zone} {field.__name__}: {got} != {want}"
    # ground truth via zoneinfo for one probe row
    zi = ZoneInfo(zone)
    probe = INSTANTS[0].replace(tzinfo=dt.timezone.utc).astimezone(zi)
    truth = {DT.Year: probe.year, DT.Month: probe.month,
             DT.DayOfMonth: probe.day, DT.Hour: probe.hour,
             DT.Minute: probe.minute,
             DT.DayOfWeek: probe.isoweekday() % 7 + 1,
             DT.DayOfYear: probe.timetuple().tm_yday}[field]
    assert got[0] == truth


def test_java_gap_overlap_parsing():
    """unix_timestamp parsing of skipped/ambiguous wall times follows
    java.time: gap shifts forward, overlap takes the earlier offset."""
    strs = pa.array(["2024-03-10 02:30:00",   # gap in New York
                     "2024-11-03 01:30:00",   # ambiguous in New York
                     "2024-06-01 12:00:00"], pa.string())
    col = TpuColumnVector.from_arrow(strs)
    batch = TpuColumnarBatch([col], 3, names=["s"])
    ref = AttributeReference("s", col.dtype, ordinal=0)
    ctx = _ctx("America/New_York")
    got = DT.ToUnixTimestamp(ref).eval_tpu(batch, ctx).to_arrow().to_pylist()[:3]
    gap = int(dt.datetime(2024, 3, 10, 7, 30,
                          tzinfo=dt.timezone.utc).timestamp())
    overlap = int(dt.datetime(2024, 11, 3, 5, 30,
                              tzinfo=dt.timezone.utc).timestamp())
    plain = int(dt.datetime(2024, 6, 1, 16, 0,
                            tzinfo=dt.timezone.utc).timestamp())
    assert got == [gap, overlap, plain]
    want = DT.ToUnixTimestamp(ref).eval_cpu(
        pa.table({"s": strs}), ctx).to_pylist()
    assert got == want


def test_from_unixtime_session_tz():
    secs = pa.array([0, 1700000000, None], pa.int64())
    col = TpuColumnVector.from_arrow(secs)
    batch = TpuColumnarBatch([col], 3, names=["sec"])
    ref = AttributeReference("sec", col.dtype, ordinal=0)
    ctx = _ctx("Asia/Kolkata")
    got = DT.FromUnixTime(ref).eval_tpu(batch, ctx).to_arrow().to_pylist()[:3]
    assert got[0] == "1970-01-01 05:30:00"  # IST = UTC+5:30
    want = DT.FromUnixTime(ref).eval_cpu(pa.table({"sec": secs}),
                                         ctx).to_pylist()
    assert got == want


def test_session_level_timezone_query():
    """spark.sql.session.timeZone flows through TaskContext into the plan."""
    conf = {"spark.sql.session.timeZone": "America/New_York"}
    tpu = TpuSession({"spark.rapids.sql.enabled": "true", **conf})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false", **conf})
    rows = [{"ts": dt.datetime(2024, 3, 10, 6, 59, tzinfo=dt.timezone.utc)},
            {"ts": dt.datetime(2024, 3, 10, 7, 1, tzinfo=dt.timezone.utc)},
            {"ts": None}]

    def q(sess):
        df = sess.createDataFrame(rows)
        return df.select(F.hour(F.col("ts")).alias("h"),
                         F.dayofmonth(F.col("ts")).alias("d"))

    got, want = q(tpu).collect(), q(cpu).collect()
    assert got == want
    assert got[0]["h"] == 1 and got[1]["h"] == 3  # EST 1:59 → EDT 3:01


def test_unknown_zone_raises_clearly():
    """An invalid session timezone fails loudly (Spark: ZoneRulesException),
    not silently-as-UTC."""
    batch, tbl, ref = _batch()
    ctx = _ctx("Not/AZone")
    with pytest.raises(Exception, match="Not/AZone"):
        DT.Year(ref).eval_tpu(batch, ctx)
