"""Avro + Hive-text serde tests (reference avro_test.py and
hive_delimited_text_test.py slices; the Avro container reader is our own —
fastavro is not in the image)."""

import datetime
import decimal

import pyarrow as pa
import pytest

from asserts import assert_tpu_and_cpu_are_equal_collect
from data_gen import (BooleanGen, DoubleGen, IntegerGen, LongGen, StringGen,
                      gen_df)

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.io.avro import read_avro, write_avro
from spark_rapids_tpu.io.hive_text import read_hive_text, write_hive_text

GENS = [("a", IntegerGen()), ("b", LongGen()), ("d", DoubleGen()),
        ("s", StringGen()), ("bo", BooleanGen())]


def _rows_table():
    return pa.table({
        "i": pa.array([1, None, 3], type=pa.int32()),
        "l": pa.array([10**12, -5, None], type=pa.int64()),
        "f": pa.array([1.5, None, -0.25], type=pa.float32()),
        "dbl": pa.array([2.5, float("inf"), None], type=pa.float64()),
        "s": pa.array(["x", None, "日本"], type=pa.string()),
        "b": pa.array([True, False, None], type=pa.bool_()),
        "bin": pa.array([b"\x00\x01", None, b""], type=pa.binary()),
        "dt": pa.array([datetime.date(2024, 1, 2), None,
                        datetime.date(1969, 12, 31)], type=pa.date32()),
        "ts": pa.array([datetime.datetime(2024, 5, 1, 12, 30, 1, 123456),
                        None, datetime.datetime(1970, 1, 1)],
                       type=pa.timestamp("us", tz="UTC")),
        "dec": pa.array([decimal.Decimal("12.34"), None,
                         decimal.Decimal("-0.01")],
                        type=pa.decimal128(9, 2)),
        "arr": pa.array([[1, 2], None, []], type=pa.list_(pa.int64())),
        "m": pa.array([[("k", 1)], None, []],
                      type=pa.map_(pa.string(), pa.int64())),
        "st": pa.array([{"x": 1, "y": "a"}, None, {"x": None, "y": None}],
                       type=pa.struct([("x", pa.int64()), ("y", pa.string())])),
    })


@pytest.mark.parametrize("codec", [
    "null", "deflate", "snappy", "bzip2", "xz", "zstandard"])
def test_avro_roundtrip_codecs(tmp_path, codec):
    t = _rows_table()
    p = str(tmp_path / "t.avro")
    write_avro(t, p, codec=codec)
    got = read_avro(p)
    assert got.equals(t)


def test_avro_column_projection(tmp_path):
    t = _rows_table()
    p = str(tmp_path / "t.avro")
    write_avro(t, p, codec="deflate")
    got = read_avro(p, columns=["s", "i"])
    assert got.column_names == ["s", "i"]
    assert got.column("i").to_pylist() == [1, None, 3]


def test_avro_multiblock(tmp_path):
    n = 10_000
    t = pa.table({"a": pa.array(range(n), type=pa.int64()),
                  "s": pa.array([f"r{i}" for i in range(n)])})
    p = str(tmp_path / "big.avro")
    write_avro(t, p, codec="snappy", block_rows=512)
    got = read_avro(p)
    assert got.equals(t)


def test_avro_empty(tmp_path):
    t = pa.table({"a": pa.array([], type=pa.int64())})
    p = str(tmp_path / "empty.avro")
    write_avro(t, p)
    got = read_avro(p)
    assert got.num_rows == 0 and got.column_names == ["a"]


def test_avro_scan_tpu_vs_cpu(tmp_path):
    t = gen_df(GENS, 500, seed=7)
    p = str(tmp_path / "gen.avro")
    write_avro(t, p)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.read.avro(p).filter(F.col("a") > 0)
        .select(F.col("a"), F.col("s"), (F.col("b") * 2).alias("b2")),
        ignore_order=True)


def test_avro_write_through_session(tmp_path, session):
    t = gen_df(GENS, 300, seed=11)
    src = str(tmp_path / "src.avro")
    write_avro(t, src)
    out = str(tmp_path / "out")
    session.read.avro(src).write.format("avro").save(out)
    import glob as _glob
    back = session.read.avro(
        _glob.glob(out + "/part-*.avro")[0]).collect()
    assert len(back) == 300


# ---------------------------------------------------------------------------
# hive text


def test_hive_text_roundtrip_default_delims(tmp_path):
    t = pa.table({
        "i": pa.array([1, None, -3], type=pa.int32()),
        "s": pa.array(["a", "", None], type=pa.string()),
        "b": pa.array([True, None, False]),
        "d": pa.array([1.5, None, -2.0], type=pa.float64()),
    })
    p = str(tmp_path / "t.txt")
    write_hive_text(t, p)
    from spark_rapids_tpu.types import (BooleanType, DoubleType, IntegerType,
                                        StringType, StructField, StructType)
    schema = StructType([StructField("i", IntegerType()),
                         StructField("s", StringType()),
                         StructField("b", BooleanType()),
                         StructField("d", DoubleType())])
    got = read_hive_text(p, {"__user_schema__": schema})
    assert got.column("i").to_pylist() == [1, None, -3]
    assert got.column("s").to_pylist() == ["a", "", None]
    assert got.column("b").to_pylist() == [True, None, False]
    assert got.column("d").to_pylist() == [1.5, None, -2.0]


def test_hive_text_nested(tmp_path):
    t = pa.table({
        "arr": pa.array([[1, 2, None], [], None], type=pa.list_(pa.int64())),
        "m": pa.array([[("k1", 1), ("k2", None)], [], None],
                      type=pa.map_(pa.string(), pa.int64())),
    })
    p = str(tmp_path / "n.txt")
    write_hive_text(t, p)
    raw = open(p, encoding="utf-8").read()
    assert "\x02" in raw and "\x03" in raw
    schema = pa.schema([("arr", pa.list_(pa.int64())),
                        ("m", pa.map_(pa.string(), pa.int64()))])
    from spark_rapids_tpu.io.hive_text import _parse_value
    assert _parse_value("1\x022\x02\\N", schema.field("arr").type,
                        "\x02", "\x03", "\\N") == [1, 2, None]
    assert _parse_value("k1\x031\x02k2\x03\\N", schema.field("m").type,
                        "\x02", "\x03", "\\N") == [("k1", 1), ("k2", None)]


def test_hive_text_custom_delims(tmp_path):
    t = pa.table({"a": pa.array([1, 2], type=pa.int64()),
                  "s": pa.array(["x", "y"])})
    p = str(tmp_path / "c.txt")
    write_hive_text(t, p, {"field.delim": "|",
                           "serialization.null.format": "NULL"})
    raw = open(p).read()
    assert raw == "1|x\n2|y\n"


def test_hive_text_scan_tpu_vs_cpu(tmp_path):
    t = gen_df([("a", IntegerGen()), ("s", StringGen()),
                ("d", DoubleGen())], 400, seed=3)
    p = str(tmp_path / "h.txt")
    write_hive_text(t, p)
    from spark_rapids_tpu.types import (DoubleType, IntegerType, StringType,
                                        StructField, StructType)
    schema = StructType([StructField("a", IntegerType()),
                         StructField("s", StringType()),
                         StructField("d", DoubleType())])
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.read.hive_text(p, schema=schema)
        .select(F.col("a"), (F.col("d") + 1.0).alias("d1")),
        ignore_order=True)
