"""Device struct columns: structs are
child-column tuples in HBM (cuDF STRUCT ColumnView analogue), field access
is zero-copy child selection, and the structural ops (gather/filter/concat)
recurse through children."""

import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.columnar.batch import TpuColumnarBatch
from spark_rapids_tpu.columnar.vector import TpuColumnVector, device_layout_ok
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.types import (IntegerT, StringT, StructField,
                                    StructType, MapType)


def _struct_arr():
    return pa.array([{"a": 1, "b": "x"}, None, {"a": 3, "b": None},
                     {"a": None, "b": "zz"}],
                    pa.struct([("a", pa.int64()), ("b", pa.string())]))


def test_struct_layout_is_device_resident():
    st = StructType([StructField("a", IntegerT, True),
                     StructField("b", StringT, True)])
    assert device_layout_ok(st)
    col = TpuColumnVector.from_arrow(_struct_arr())
    assert col.host_data is None, "struct must NOT fall back to host_data"
    assert col.children is not None and len(col.children) == 2
    # roundtrip preserves values and nulls
    assert col.to_arrow().to_pylist() == _struct_arr().to_pylist()


def test_struct_map_field_is_device():
    """r5: maps moved to the device offsets + struct<key,value> layout, so
    a struct carrying a map is device-resident too."""
    from spark_rapids_tpu.types import StructType as St
    st = St([StructField("m", MapType(StringT, IntegerT), True)])
    assert device_layout_ok(st)


def test_get_struct_field_is_zero_copy_child():
    from spark_rapids_tpu.expressions.base import AttributeReference
    from spark_rapids_tpu.expressions.collections import GetStructField
    col = TpuColumnVector.from_arrow(_struct_arr())
    batch = TpuColumnarBatch([col], 4, names=["s"])
    ref = AttributeReference("s", col.dtype, ordinal=0)
    out = GetStructField(ref, "a").eval_tpu(batch)
    assert out.host_data is None
    # row 1: struct null -> field null; row 3: field null
    assert out.to_arrow().to_pylist()[:4] == [1, None, 3, None]
    sb = GetStructField(ref, "b").eval_tpu(batch)
    assert sb.to_arrow().to_pylist()[:4] == ["x", None, None, "zz"]


def test_struct_pipeline_parity():
    t = pa.table({
        "s": _struct_arr(),
        "arr": pa.array([[{"p": 1.5}, {"p": 2.5}], [], None, [{"p": None}]],
                        pa.list_(pa.struct([("p", pa.float64())]))),
        "k": [10, 20, 30, 40],
    })
    res = {}
    for en in ("true", "false"):
        s = TpuSession({"spark.rapids.sql.enabled": en,
                        "spark.sql.shuffle.partitions": "2"})
        df = s.createDataFrame(t, num_partitions=2)
        out = (df.filter(F.col("k") > 10)
               .select(df["s"].getField("a").alias("sa"),
                       df["s"].getItem("b").alias("sb"),
                       df["arr"].getItem("p").alias("ap"),
                       F.named_struct("k2", F.col("k") * 2).alias("ns"),
                       df["s"], F.col("k"))
               .sort(F.col("k").desc()))
        res[en] = out.collect()
    assert res["true"] == res["false"]
    assert res["true"][0]["ns"] == {"k2": 80}
    assert res["true"][-1]["sa"] is None  # k=20 row: struct null


def test_struct_groupby_passthrough_and_shuffle():
    """Structs survive exchanges and aggregation carriers (first/collect)."""
    t = pa.table({
        "g": [1, 1, 2, 2],
        "s": _struct_arr(),
    })
    res = {}
    for en in ("true", "false"):
        s = TpuSession({"spark.rapids.sql.enabled": en,
                        "spark.sql.shuffle.partitions": "2"})
        df = s.createDataFrame(t, num_partitions=2)
        out = (df.groupBy("g")
               .agg(F.first(F.col("s"), ignorenulls=False).alias("fs"))
               .sort("g"))
        res[en] = out.collect()
    assert res["true"] == res["false"]
