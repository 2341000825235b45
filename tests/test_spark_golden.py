"""Spark-golden parity fixtures.

No Apache Spark exists in this environment, so these expectations are
VENDORED, hand-derived from the exact JVM semantics Spark's Cast delegates
to (Java narrowing conversions, Double.toString/parseDouble, HALF_UP
decimal rounding) and from Spark's documented DateTimeUtils string grammar
— NOT from running this framework (that would be circular). Each group
notes its derivation. Every case runs through BOTH the TPU plan and the
CPU oracle via the public session API, so a framework change that drifts
from Spark semantics fails here even though both in-repo engines agree
with each other.

Known, deliberate divergences (excluded): denormal float shortest-repr
ties (Java Ryu prints 4.9E-324 for Double.MIN_VALUE; shortest-repr here
gives 5.0E-324 — both round-trip)."""

import datetime
import decimal
import math

import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.expressions.base import ExpressionError
from spark_rapids_tpu.session import TpuSession

NAN = float("nan")
INF = float("inf")


def _sessions():
    return (TpuSession({}),
            TpuSession({"spark.rapids.sql.enabled": "false"}))


def _run_cast(in_type, vals, to):
    """Returns collected values from both engines for cast(col AS to)."""
    outs = []
    for s in _sessions():
        df = s.createDataFrame(pa.table({"c": pa.array(vals, in_type)}))
        rows = df.select(F.col("c").cast(to).alias("o")).collect()
        outs.append([r["o"] for r in rows])
    return outs


def _check(in_type, vals, to, want):
    got_tpu, got_cpu = _run_cast(in_type, vals, to)
    for engine, got in (("tpu", got_tpu), ("cpu", got_cpu)):
        assert len(got) == len(want)
        for g, w, v in zip(got, want, vals):
            if isinstance(w, float) and math.isnan(w):
                assert isinstance(g, float) and math.isnan(g), \
                    f"{engine}: cast({v!r}) = {g!r}, want NaN"
            else:
                assert g == w, f"{engine}: cast({v!r}) = {g!r}, want {w!r}"
            if isinstance(w, float) and w == 0.0 and not math.isnan(w):
                assert math.copysign(1, g) == math.copysign(1, w), \
                    f"{engine}: cast({v!r}) sign: {g!r} want {w!r}"


# --- integral narrowing: Java (byte)/(short)/(int) conversion ---------------
# derivation: JLS 5.1.3 narrowing = low-order bits, two's complement

def test_int_to_byte_wraps():
    _check(pa.int32(), [300, -200, 128, -129, 0, 127, -128, 255, 256, None],
           "tinyint", [44, 56, -128, 127, 0, 127, -128, -1, 0, None])


def test_int_to_short_wraps():
    _check(pa.int32(), [70000, 40000, -40000, 32768, -32769, None],
           "smallint", [4464, -25536, 25536, -32768, 32767, None])


def test_long_to_int_wraps():
    _check(pa.int64(), [2147483653, -2147483653, 2**32, 2**32 + 7, None],
           "int", [-2147483643, 2147483643, 0, 7, None])


# --- float -> integral: Java (int)x semantics -------------------------------
# derivation: JLS 5.1.3 FP-to-integral: NaN -> 0, round toward zero,
# out-of-range saturates at MIN/MAX

def test_double_to_int_trunc_clamp_nan():
    _check(pa.float64(), [2.9, -2.9, 0.5, -0.5, NAN, 1e20, -1e20,
                          2147483647.9, None],
           "int", [2, -2, 0, 0, 0, 2147483647, -2147483648,
                   2147483647, None])


def test_double_to_long_saturates():
    _check(pa.float64(), [9.3e18, -9.3e18, 2.5, NAN, None],
           "bigint", [9223372036854775807, -9223372036854775808, 2, 0, None])


# --- float -> string: Java Double.toString / Float.toString -----------------
# derivation: JLS Double.toString: plain decimal iff 1e-3 <= |v| < 1e7,
# else scientific d.dddEexp; shortest round-trip digits

def test_double_to_string_java_format():
    _check(pa.float64(),
           [0.0, -0.0, 1.0, 1e7, 9999999.0, 12345678.0, 0.001, 9.99e-4,
            1e-4, NAN, INF, -INF, 123456.789, 1e300, -1.5, 1e23, 1e-7,
            6.02e23, None],
           "string",
           ["0.0", "-0.0", "1.0", "1.0E7", "9999999.0", "1.2345678E7",
            "0.001", "9.99E-4", "1.0E-4", "NaN", "Infinity", "-Infinity",
            "123456.789", "1.0E300", "-1.5", "1.0E23", "1.0E-7",
            "6.02E23", None])


def test_float_to_string_java_format():
    _check(pa.float32(),
           [1.1, 1e7, 0.5, -0.0, 3.4028235e38, NAN, None],
           "string",
           ["1.1", "1.0E7", "0.5", "-0.0", "3.4028235E38", "NaN", None])


# --- bool casts -------------------------------------------------------------
# derivation: Spark Cast numeric->bool is x != 0 (NaN != 0 is true);
# string->bool accepts t/true/y/yes/1 and f/false/n/no/0 case-insensitively

def test_numeric_to_boolean():
    _check(pa.float64(), [0.0, -0.0, 5.0, -1.5, NAN, None],
           "boolean", [False, False, True, True, True, None])
    _check(pa.int32(), [0, 1, -7, None], "boolean",
           [False, True, True, None])


def test_string_to_boolean():
    _check(pa.string(),
           ["t", "TRUE", " yes ", "1", "f", "No", "0", "tr", "2", "", None],
           "boolean",
           [True, True, True, True, False, False, False, None, None, None,
            None])


def test_boolean_to_string():
    _check(pa.bool_(), [True, False, None], "string",
           ["true", "false", None])


# --- string -> numeric ------------------------------------------------------
# derivation: UTF8String.toInt accepts [+-]?digits only (so '1.5' is null);
# Double.parseDouble accepts inf/nan literals and d/f type suffixes

def test_string_to_int():
    _check(pa.string(),
           [" 5 ", "+5", "-0", "2147483647", "2147483648", "-2147483649",
            "1.5", "", "abc", "0x1A", "--5", None],
           "int",
           [5, 5, 0, 2147483647, None, None, None, None, None, None, None,
            None])


def test_string_to_byte_overflow_null():
    _check(pa.string(), ["127", "128", "-128", "-129", None],
           "tinyint", [127, None, -128, None, None])


def test_string_to_double():
    _check(pa.string(),
           ["1.5", " 1e3 ", "NaN", "Infinity", "-Infinity", "+inf", "1d",
            "2.5f", "1e", "", None],
           "double",
           [1.5, 1000.0, NAN, INF, -INF, INF, 1.0, 2.5, None, None, None])


# --- string -> date: Spark DateTimeUtils.stringToDate grammar ---------------
# derivation: accepts [+-]y{1,7}[-m[-d]] with optional ' '/'T' tail after a
# full date; invalid calendar dates are null (proleptic Gregorian)

D = datetime.date


def test_string_to_date_partial_forms():
    _check(pa.string(),
           ["2021", "2021-3", "2021-03", "2021-3-4", "2021-03-04",
            " 2021-01-02 ", "2021-01-02 12:30:00", "2021-01-02T01:02:03",
            None],
           "date",
           [D(2021, 1, 1), D(2021, 3, 1), D(2021, 3, 1), D(2021, 3, 4),
            D(2021, 3, 4), D(2021, 1, 2), D(2021, 1, 2), D(2021, 1, 2),
            None])


def test_string_to_date_invalid_null():
    _check(pa.string(),
           ["2021-13-01", "2021-02-30", "2021-00-01", "01-02-2021",
            "2021/01/02", "not a date", "", "2021-01-02x", None],
           "date",
           [None, None, None, None, None, None, None, None, None])


def test_string_to_date_leap_years():
    _check(pa.string(), ["2020-02-29", "2021-02-29", "2000-02-29",
                         "1900-02-29"],
           "date", [D(2020, 2, 29), None, D(2000, 2, 29), None])


# --- string -> timestamp (UTC session zone) ---------------------------------
# derivation: DateTimeUtils.stringToTimestamp: partial date/time forms,
# fraction to micros, Z/UTC/[+-]h[h][:mm] zones

TS = datetime.datetime


def _ts(y, mo=1, d=1, h=0, mi=0, s=0, us=0):
    # the framework's timestamps are tz-aware (UTC session zone), like
    # Spark's TimestampType; naive datetimes would never compare equal
    return TS(y, mo, d, h, mi, s, us, tzinfo=datetime.timezone.utc)


def test_string_to_timestamp_forms():
    _check(pa.string(),
           ["2021-01-02 03:04:05", "2021-01-02T03:04:05.123456",
            "2021-01-02 03:04", "2021-01-02 03", "2021-01-02", "2021",
            "2021-01-02 03:04:05Z", "2021-01-02 03:04:05+01",
            "2021-01-02 03:04:05+01:30", "2021-01-02 03:04:05 UTC",
            "epoch", None],
           "timestamp",
           [_ts(2021, 1, 2, 3, 4, 5), _ts(2021, 1, 2, 3, 4, 5, 123456),
            _ts(2021, 1, 2, 3, 4), _ts(2021, 1, 2, 3), _ts(2021, 1, 2),
            _ts(2021), _ts(2021, 1, 2, 3, 4, 5), _ts(2021, 1, 2, 2, 4, 5),
            _ts(2021, 1, 2, 1, 34, 5), _ts(2021, 1, 2, 3, 4, 5),
            _ts(1970), None])


def test_string_to_timestamp_fraction_truncates_to_micros():
    _check(pa.string(),
           ["2021-01-02 00:00:00.1", "2021-01-02 00:00:00.123456789"],
           "timestamp",
           [_ts(2021, 1, 2, us=100000), _ts(2021, 1, 2, us=123456)])


def test_string_to_timestamp_invalid_null():
    _check(pa.string(),
           ["2021-01-02 25:00:00", "2021-01-02 00:61:00", "junk",
            "2021-01-02 03:04:05 PST?"],
           "timestamp", [None, None, None, None])


# --- timestamp <-> long -----------------------------------------------------
# derivation: Spark ts->long is floorDiv(micros, 1e6); long->ts is micros*1e6

def test_timestamp_long_round_trip():
    ts = [_ts(1970, 1, 1, 0, 0, 1), _ts(1969, 12, 31, 23, 59, 59, 500000),
          _ts(2021, 6, 1, 12), None]
    _check(pa.timestamp("us"), ts, "bigint",
           [1, -1, 1622548800, None])  # -0.5s floors to -1
    _check(pa.int64(), [1, -1, 1622548800, None], "timestamp",
           [_ts(1970, 1, 1, 0, 0, 1), _ts(1969, 12, 31, 23, 59, 59),
            _ts(2021, 6, 1, 12), None])


# --- string -> decimal: HALF_UP to scale, overflow null ---------------------
# derivation: Spark Decimal.changePrecision with ROUND_HALF_UP

def test_string_to_decimal():
    DEC = decimal.Decimal
    _check(pa.string(),
           ["1.005", "-1.005", "123.454", "123.455", "999.994", "999.995",
            "1e2", "0.005", "abc", "", None],
           "decimal(5,2)",
           [DEC("1.01"), DEC("-1.01"), DEC("123.45"), DEC("123.46"),
            DEC("999.99"), None, DEC("100.00"), DEC("0.01"), None, None,
            None])


# --- ANSI mode: overflow raises --------------------------------------------
# derivation: Spark ANSI cast throws on overflow/invalid input

@pytest.mark.parametrize("tpu", [True, False])
def test_ansi_overflow_raises(tpu):
    s = TpuSession({"spark.rapids.sql.enabled": str(tpu).lower(),
                    "spark.sql.ansi.enabled": "true"})
    df = s.createDataFrame(pa.table({"c": pa.array([300], pa.int32())}))
    with pytest.raises(ExpressionError):
        df.select(F.col("c").cast("tinyint").alias("o")).collect()
    df2 = s.createDataFrame(pa.table({"c": pa.array(["xyz"], pa.string())}))
    with pytest.raises(ExpressionError):
        df2.select(F.col("c").cast("int").alias("o")).collect()


# --- NaN / -0.0 ordering ----------------------------------------------------
# derivation: Spark sorts NaN greatest; -0.0 and 0.0 compare equal; min/max
# treat NaN as greatest

def test_nan_ordering_sort_and_minmax():
    vals = [NAN, INF, -INF, -0.0, 0.0, 1.5, None]
    for tpu in (True, False):
        s = TpuSession({"spark.rapids.sql.enabled": str(tpu).lower()})
        df = s.createDataFrame(pa.table({"v": pa.array(vals, pa.float64())}))
        rows = [r["v"] for r in df.sort("v").collect()]
        assert rows[0] is None and rows[1] == -INF
        assert rows[-1] is not None and math.isnan(rows[-1])
        assert rows[-2] == INF
        agg = df.agg(F.max(F.col("v")).alias("mx"),
                     F.min(F.col("v")).alias("mn")).collect()[0]
        assert math.isnan(agg["mx"])  # NaN greatest
        assert agg["mn"] == -INF
