"""Expression breadth 2: registry completion toward the reference's 219
rules. Parity: eval_tpu vs eval_cpu on mixed corpora.
Reference: mathExpressions.scala, nullExpressions.scala, GpuInSet,
GpuRandomExpressions, datetimeExpressions.scala, complexTypeExtractors.scala,
higherOrderFunctions.scala."""

import math

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.batch import TpuColumnarBatch
from spark_rapids_tpu.columnar.vector import TpuColumnVector
from spark_rapids_tpu.expressions.base import (AttributeReference, EvalContext,
                                               Literal)
from spark_rapids_tpu.expressions import mathexprs as M
from spark_rapids_tpu.expressions import nullexprs as N
from spark_rapids_tpu.expressions import predicates as P
from spark_rapids_tpu.expressions import datetime as DT
from spark_rapids_tpu.expressions import collections as C
from spark_rapids_tpu.expressions import misc as MISC
from spark_rapids_tpu.expressions import strings as S
from spark_rapids_tpu.expressions.hashexprs import Md5

NAN = float("nan")


def _mkbatch(cols: dict):
    arrays = {k: (v if isinstance(v, pa.Array) else pa.array(*v)) for k, v in cols.items()}
    tcols = [TpuColumnVector.from_arrow(a) for a in arrays.values()]
    n = len(next(iter(arrays.values())))
    batch = TpuColumnarBatch(tcols, n, names=list(arrays))
    refs = {k: AttributeReference(k, c.dtype, ordinal=i)
            for i, (k, c) in enumerate(zip(arrays, tcols))}
    return batch, pa.table(arrays), refs, n


def _canon(x):
    if isinstance(x, float):
        if math.isnan(x):
            return "nan"
        return round(x, 10)
    if isinstance(x, list):
        return [_canon(e) for e in x]
    if isinstance(x, dict):
        return {k: _canon(v) for k, v in x.items()}
    return x


def _check(expr, batch, tbl, n, ctx=None):
    kw = {} if ctx is None else {"ctx": ctx}
    got = expr.eval_tpu(batch, **kw).to_arrow().to_pylist()[:n]
    kw = {} if ctx is None else {"ctx": ctx}
    want = expr.eval_cpu(tbl, **kw)
    want = want.to_pylist() if hasattr(want, "to_pylist") else [want] * n
    assert _canon(got) == _canon(want), f"{expr.pretty()}: {got} != {want}"


DBL = ([0.5, -1.2, None, 2.0, NAN, 100.0, -0.5, 1.0], pa.float64())
INT = ([5, -3, None, 1250, 7, -1250, 0, 9], pa.int64())

MATH_CASES = [
    ("asinh", lambda r: M.Asinh(r["d"])),
    ("acosh", lambda r: M.Acosh(r["d"])),
    ("atanh", lambda r: M.Atanh(r["d"])),
    ("cot", lambda r: M.Cot(r["d"])),
    ("degrees", lambda r: M.ToDegrees(r["d"])),
    ("radians", lambda r: M.ToRadians(r["d"])),
    ("rint", lambda r: M.Rint(r["d"])),
    ("hypot", lambda r: M.Hypot(r["d"], Literal(3.0))),
    ("logarithm", lambda r: M.Logarithm(Literal(2.0), r["d"])),
    ("bround_f", lambda r: M.BRound(r["d"], Literal(0))),
    ("bround_i", lambda r: M.BRound(r["i"], Literal(-2))),
]


@pytest.mark.parametrize("name,make", MATH_CASES, ids=[c[0] for c in MATH_CASES])
def test_math_breadth(name, make):
    batch, tbl, refs, n = _mkbatch({"d": DBL, "i": INT})
    _check(make(refs), batch, tbl, n)


def test_bround_half_even():
    batch, tbl, refs, n = _mkbatch(
        {"d": ([0.5, 1.5, 2.5, -0.5, -1.5, None, 2.675, 3.0], pa.float64()),
         "i": ([50, 150, 250, -50, -150, None, 267, 300], pa.int64())})
    _check(M.BRound(refs["d"], Literal(0)), batch, tbl, n)
    _check(M.BRound(refs["i"], Literal(-2)), batch, tbl, n)


def test_at_least_n_non_nulls():
    batch, tbl, refs, n = _mkbatch({"d": DBL, "i": INT})
    for k in (0, 1, 2, 3):
        _check(N.AtLeastNNonNulls(k, refs["d"], refs["i"]), batch, tbl, n)


def test_normalize_nan_and_zero():
    batch, tbl, refs, n = _mkbatch(
        {"d": ([-0.0, 0.0, NAN, 1.5, None, -2.0, 3.0, -0.0], pa.float64())})
    got = N.NormalizeNaNAndZero(refs["d"]).eval_tpu(batch)
    vals = np.asarray(got.data[:n])
    # -0.0 must be canonicalized: no sign bit on any zero
    zero_bits = np.signbit(vals[vals == 0])
    assert not zero_bits.any()
    _check(N.KnownNotNull(refs["d"]), batch, tbl, n)
    _check(N.KnownFloatingPointNormalized(refs["d"]), batch, tbl, n)


def test_inset():
    batch, tbl, refs, n = _mkbatch({"i": INT, "d": DBL})
    _check(P.InSet(refs["i"], [5, 7, 99]), batch, tbl, n)
    _check(P.InSet(refs["i"], [5, None, 99]), batch, tbl, n)
    _check(P.InSet(refs["d"], [0.5, NAN]), batch, tbl, n)
    _check(P.InSet(refs["i"], []), batch, tbl, n)


def test_ascii_instr_md5():
    vals = (["hello", "", None, "Apple", "~tilde", "z", "0", " "], pa.string())
    batch, tbl, refs, n = _mkbatch({"s": vals})
    _check(S.Ascii(refs["s"]), batch, tbl, n)
    _check(S.StringInstr(refs["s"], Literal("l")), batch, tbl, n)
    _check(Md5(refs["s"]), batch, tbl, n)


def test_datetime_breadth():
    import datetime as _dt
    dates = pa.array([_dt.date(2024, 2, 29), None, _dt.date(1969, 12, 31),
                      _dt.date(2000, 1, 1)], pa.date32())
    secs = pa.array([0, 86400, None, -1], pa.int64())
    batch, tbl, refs, n = _mkbatch({"dt": dates, "sec": secs})
    _check(DT.DateSub(refs["dt"], Literal(30)), batch, tbl, n)
    _check(DT.SecondsToTimestamp(refs["sec"]), batch, tbl, n)
    _check(DT.MillisToTimestamp(refs["sec"]), batch, tbl, n)
    _check(DT.MicrosToTimestamp(refs["sec"]), batch, tbl, n)
    _check(DT.FromUnixTime(refs["sec"]), batch, tbl, n)
    _check(DT.FromUnixTime(refs["sec"], Literal("yyyy/MM/dd")), batch, tbl, n)


def test_unix_timestamp_paths():
    import datetime as _dt
    strs = pa.array(["2024-01-15 10:30:00", "bogus", None,
                     "1970-01-01 00:00:00"], pa.string())
    ts = pa.array([_dt.datetime(2024, 1, 15, 10, 30, tzinfo=_dt.timezone.utc),
                   None,
                   _dt.datetime(1969, 12, 31, 23, 59, 59,
                                tzinfo=_dt.timezone.utc),
                   _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)],
                  pa.timestamp("us", tz="UTC"))
    batch, tbl, refs, n = _mkbatch({"s": strs, "ts": ts})
    _check(DT.ToUnixTimestamp(refs["s"]), batch, tbl, n)
    _check(DT.UnixTimestamp(refs["ts"]), batch, tbl, n)
    _check(DT.DateFormatClass(refs["ts"], Literal("yyyy-MM-dd HH:mm")),
           batch, tbl, n)


def test_array_remove():
    lists = pa.array([[1, 2, 1, None], [], None, [1, 1], [3]],
                     pa.list_(pa.int64()))
    batch, tbl, refs, n = _mkbatch({"a": lists})
    _check(C.ArrayRemove(refs["a"], Literal(1)), batch, tbl, n)
    flists = pa.array([[1.0, NAN, 2.0], [NAN], None], pa.list_(pa.float64()))
    batch, tbl, refs, n = _mkbatch({"a": flists})
    _check(C.ArrayRemove(refs["a"], Literal(NAN)), batch, tbl, n)


def test_map_ops():
    from spark_rapids_tpu.expressions.collections import (LambdaFunction,
                                                          NamedLambdaVariable)
    from spark_rapids_tpu.types import LongT, StringT
    maps = pa.array([[("a", 1), ("b", 2)], [], None, [("c", None)]],
                    pa.map_(pa.string(), pa.int64()))
    batch, tbl, refs, n = _mkbatch({"m": maps})
    _check(C.MapEntries(refs["m"]), batch, tbl, n)
    k = NamedLambdaVariable("k", StringT)
    v = NamedLambdaVariable("v", LongT)
    from spark_rapids_tpu.expressions.predicates import GreaterThan
    from spark_rapids_tpu.expressions.arithmetic import Add
    flt = LambdaFunction(GreaterThan(v, Literal(1)), [k, v])
    _check(C.MapFilter(refs["m"], flt), batch, tbl, n)
    tv = LambdaFunction(Add(v, Literal(10)), [k, v])
    _check(C.TransformValues(refs["m"], tv), batch, tbl, n)
    tk = LambdaFunction(S.Upper(k), [k, v])
    _check(C.TransformKeys(refs["m"], tk), batch, tbl, n)


def test_transform_keys_null_key_raises():
    from spark_rapids_tpu.expressions.base import ExpressionError
    from spark_rapids_tpu.expressions.collections import (LambdaFunction,
                                                          NamedLambdaVariable)
    from spark_rapids_tpu.types import LongT, StringT
    maps = pa.array([[("a", 1)]], pa.map_(pa.string(), pa.int64()))
    batch, tbl, refs, n = _mkbatch({"m": maps})
    k = NamedLambdaVariable("k", StringT)
    v = NamedLambdaVariable("v", LongT)
    tk = LambdaFunction(Literal(None), [k, v])
    with pytest.raises(ExpressionError):
        C.TransformKeys(refs["m"], tk).eval_tpu(batch)


def test_unsupported_datetime_pattern_rejected():
    """SSS / DD have no exact strftime mapping — must raise, not mis-format."""
    from spark_rapids_tpu.expressions.datetime import _java_to_strftime
    with pytest.raises(ValueError):
        _java_to_strftime("HH:mm:ss.SSS")
    assert _java_to_strftime("yyyy-MM-dd") == "%Y-%m-%d"


def test_at_least_n_non_nulls_scalar_children():
    batch, tbl, refs, n = _mkbatch({"d": DBL})
    _check(N.AtLeastNNonNulls(1, Literal(5.0), refs["d"]), batch, tbl, n)
    _check(N.AtLeastNNonNulls(2, Literal(None), refs["d"]), batch, tbl, n)
    _check(N.AtLeastNNonNulls(1, Literal(NAN)), batch, tbl, n)


def test_struct_ops():
    structs = pa.array([{"x": 1, "y": "a"}, None, {"x": None, "y": "b"}],
                       pa.struct([("x", pa.int64()), ("y", pa.string())]))
    batch, tbl, refs, n = _mkbatch({"st": structs})
    _check(C.GetStructField(refs["st"], "x"), batch, tbl, n)
    _check(C.GetStructField(refs["st"], "y"), batch, tbl, n)
    arr = pa.array([[{"x": 1}, {"x": 2}], None, [{"x": None}]],
                   pa.list_(pa.struct([("x", pa.int64())])))
    batch, tbl, refs, n = _mkbatch({"a": arr})
    _check(C.GetArrayStructFields(refs["a"], "x"), batch, tbl, n)
    batch, tbl, refs, n = _mkbatch({"st": structs})
    _check(C.CreateNamedStruct(["p", "q"],
                               [C.GetStructField(refs["st"], "x"),
                                Literal("z")]), batch, tbl, n)


def test_partition_context_exprs():
    batch, tbl, refs, n = _mkbatch({"i": INT})
    ctx = EvalContext(partition_id=3)
    got = MISC.SparkPartitionID().eval_tpu(batch, ctx).to_arrow().to_pylist()[:n]
    assert got == [3] * n
    ctx2 = EvalContext(partition_id=2)
    mid = MISC.MonotonicallyIncreasingID()
    got1 = mid.eval_tpu(batch, ctx2).to_arrow().to_pylist()[:n]
    got2 = mid.eval_tpu(batch, ctx2).to_arrow().to_pylist()[:n]
    base = 2 << 33
    assert got1 == list(range(base, base + n))
    assert got2 == list(range(base + n, base + 2 * n))  # counter advances
    # rand: deterministic per (seed, partition, row); in [0, 1)
    r = MISC.Rand(Literal(42))
    a = r.eval_tpu(batch, EvalContext(partition_id=1)).to_arrow().to_pylist()[:n]
    b = MISC.Rand(Literal(42)).eval_tpu(
        batch, EvalContext(partition_id=1)).to_arrow().to_pylist()[:n]
    assert a == b and all(0.0 <= x < 1.0 for x in a)
    c = MISC.Rand(Literal(42)).eval_tpu(
        batch, EvalContext(partition_id=2)).to_arrow().to_pylist()[:n]
    assert a != c
    # input-file exprs default to '' / -1 outside a scan
    assert MISC.InputFileName().eval_tpu(batch, ctx).to_arrow().to_pylist()[:n] \
        == [""] * n
    assert MISC.InputFileBlockStart().eval_tpu(
        batch, ctx).to_arrow().to_pylist()[:n] == [-1] * n


def test_registry_reaches_reference_scale():
    """Exit criterion: >= 196 expression rules."""
    import spark_rapids_tpu.plan.overrides  # noqa: F401
    from spark_rapids_tpu.plan.typechecks import all_expr_rules
    rules = all_expr_rules()
    assert len(rules) >= 196, len(rules)
    ha = [c for c, r in rules.items() if r.host_assisted]
    assert len(ha) <= 40, [c.__name__ for c in ha]
