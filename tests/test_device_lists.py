"""Device list ops: ragged gather / sort / set operations vs the CPU oracle.

Device-resident collections, continued: slice, reverse,
concat, flatten, sequence, repeat run as ragged gathers sharing
kernels/strings.gather_plan; sort_array/array_distinct/union/intersect/
except/overlap run as segment sorts + per-row binary search over total-order
integer keys (IEEE bit trick for floats: NaN greatest, -0.0 == 0.0).
Reference: collectionOperations.scala (GpuSortArray, GpuArrayDistinct,
GpuArrayUnion/Intersect/Except, GpuArraysOverlap, GpuSlice, GpuFlatten,
GpuSequence, GpuArrayRepeat).
"""

import math

import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.batch import TpuColumnarBatch
from spark_rapids_tpu.columnar.vector import TpuColumnVector
from spark_rapids_tpu.expressions.base import AttributeReference, Literal
from spark_rapids_tpu.expressions import collections as C

NAN = float("nan")

INT_A = [[3, 1, 2, 1, None, 3], [], None, [5, 5, 5], [None, None, 1], [7, 8],
         [2**62, -2**62, 0], [1]]
INT_B = [[1, 4], [1], [2], None, [None], [9], [2**62], []]
FLT_A = [[1.0, -0.0, NAN, 2.0, NAN], [0.0], None, [1.5, None], [], [-1.0]]
FLT_B = [[0.0, NAN], [], [1.0], [None, 1.5], [2.0], None]


def _setup(alists, blists, patype, ints=None):
    arr_a = pa.array(alists, patype)
    arr_b = pa.array(blists, patype)
    cols = [TpuColumnVector.from_arrow(arr_a), TpuColumnVector.from_arrow(arr_b)]
    names = ["a", "b"]
    tdata = {"a": arr_a, "b": arr_b}
    if ints is not None:
        iarr = pa.array(ints, pa.int64())
        cols.append(TpuColumnVector.from_arrow(iarr))
        names.append("i")
        tdata["i"] = iarr
    batch = TpuColumnarBatch(cols, len(alists), names=names)
    refs = [AttributeReference(n, c.dtype, ordinal=k)
            for k, (n, c) in enumerate(zip(names, cols))]
    return batch, pa.table(tdata), refs


def _canon(x):
    if isinstance(x, float) and math.isnan(x):
        return "nan"
    if isinstance(x, list):
        return [_canon(e) for e in x]
    return x


def _check(expr, batch, tbl, n):
    got = expr.eval_tpu(batch).to_arrow().to_pylist()[:n]
    want = expr.eval_cpu(tbl).to_pylist()
    assert _canon(got) == _canon(want), f"{expr.pretty()}: {got} != {want}"


GATHER_CASES = [
    ("slice_2_2", lambda a, b, i: C.Slice(a, Literal(2), Literal(2))),
    ("slice_neg", lambda a, b, i: C.Slice(a, Literal(-2), Literal(5))),
    ("slice_len0", lambda a, b, i: C.Slice(a, Literal(1), Literal(0))),
    ("slice_col_start", lambda a, b, i: C.Slice(a, i, Literal(2))),
    ("reverse", lambda a, b, i: C.ArrayReverse(a)),
    ("concat", lambda a, b, i: C.ConcatArrays([a, b])),
    ("concat3", lambda a, b, i: C.ConcatArrays([a, b, a])),
    ("flatten", lambda a, b, i: C.Flatten(C.CreateArray([a, b]))),
    ("repeat_lit", lambda a, b, i: C.ArrayRepeat(i, Literal(2))),
    ("repeat_col", lambda a, b, i: C.ArrayRepeat(Literal(7), i)),
    ("sequence", lambda a, b, i: C.Sequence(Literal(1), i)),
    ("sequence_step", lambda a, b, i: C.Sequence(i, Literal(0), Literal(-2))),
]

SETOP_CASES = [
    ("sort_asc", lambda a, b: C.SortArray(a)),
    ("sort_desc", lambda a, b: C.SortArray(a, Literal(False))),
    ("distinct", lambda a, b: C.ArrayDistinct(a)),
    ("union", lambda a, b: C.ArrayUnion(a, b)),
    ("intersect", lambda a, b: C.ArrayIntersect(a, b)),
    ("except", lambda a, b: C.ArrayExcept(a, b)),
    ("overlap", lambda a, b: C.ArraysOverlap(a, b)),
]


@pytest.mark.parametrize("name,make", GATHER_CASES, ids=[c[0] for c in GATHER_CASES])
def test_gather_ops_int(name, make):
    ints = [2, 1, None, 3, 5, -2, 4, 1]  # no 0: slice(start=0) raises in both paths
    batch, tbl, (ra, rb, ri) = _setup(INT_A, INT_B, pa.list_(pa.int64()), ints)
    _check(make(ra, rb, ri), batch, tbl, len(INT_A))


@pytest.mark.parametrize("name,make", SETOP_CASES, ids=[c[0] for c in SETOP_CASES])
def test_set_ops_int(name, make):
    batch, tbl, (ra, rb) = _setup(INT_A, INT_B, pa.list_(pa.int64()))
    _check(make(ra, rb), batch, tbl, len(INT_A))


@pytest.mark.parametrize("name,make", SETOP_CASES, ids=[c[0] for c in SETOP_CASES])
def test_set_ops_float_nan_negzero(name, make):
    """NaN groups as one value and sorts greatest; -0.0 == 0.0 (Spark SQL
    equality) — exercised through the IEEE-bit sort keys."""
    batch, tbl, (ra, rb) = _setup(FLT_A, FLT_B, pa.list_(pa.float64()))
    _check(make(ra, rb), batch, tbl, len(FLT_A))


def test_sequence_int64_range():
    """Regression: sequence over bigint values beyond int32 must not truncate
    (the arithmetic runs in the element carrier dtype)."""
    big = 8589934592  # 2^33
    ints = [big, None, big + 2]
    batch, tbl, (ra, rb, ri) = _setup(INT_A[:3], INT_B[:3],
                                      pa.list_(pa.int64()), ints)
    _check(C.Sequence(ri, Literal(big + 2)), batch, tbl, 3)
    _check(C.Sequence(Literal(big + 2), ri, Literal(-1)), batch, tbl, 3)


def test_slice_errors():
    batch, tbl, (ra, rb) = _setup(INT_A, INT_B, pa.list_(pa.int64()))
    from spark_rapids_tpu.expressions.base import ExpressionError
    with pytest.raises(ExpressionError):
        C.Slice(ra, Literal(0), Literal(1)).eval_tpu(batch)
    with pytest.raises(ExpressionError):
        C.Slice(ra, Literal(1), Literal(-1)).eval_tpu(batch)


def test_sequence_step_zero_errors():
    batch, tbl, (ra, rb) = _setup(INT_A, INT_B, pa.list_(pa.int64()))
    from spark_rapids_tpu.expressions.base import ExpressionError
    with pytest.raises(ExpressionError):
        C.Sequence(Literal(1), Literal(5), Literal(0)).eval_tpu(batch)


def test_flatten_null_inner():
    """Any null inner array nulls the whole row (Spark flatten)."""
    outer = [[[1, 2], None], [[3], [4]], None, [[]]]
    arr = pa.array(outer, pa.list_(pa.list_(pa.int64())))
    col = TpuColumnVector.from_arrow(arr)
    batch = TpuColumnarBatch([col], len(outer), names=["a"])
    ref = AttributeReference("a", col.dtype, ordinal=0)
    tbl = pa.table({"a": arr})
    _check(C.Flatten(ref), batch, tbl, len(outer))


def test_flatten_string_elements():
    """Offset composition is layout-generic: list<list<string>> flattens on
    device too (inner child is a string column)."""
    outer = [[["ab", "c"], ["d"]], [[]], [["e", None]]]
    arr = pa.array(outer, pa.list_(pa.list_(pa.string())))
    col = TpuColumnVector.from_arrow(arr)
    batch = TpuColumnarBatch([col], len(outer), names=["a"])
    ref = AttributeReference("a", col.dtype, ordinal=0)
    tbl = pa.table({"a": arr})
    _check(C.Flatten(ref), batch, tbl, len(outer))


def test_host_assisted_collections_shrunk():
    import spark_rapids_tpu.plan.overrides  # noqa: F401 — trigger registration
    from spark_rapids_tpu.plan.typechecks import all_expr_rules
    ha = [c.__name__ for c, r in all_expr_rules().items() if r.host_assisted]
    # target: <= 40 (was 62). Breadth additions (maps/structs/
    # datetime formatting) add NEW host-assisted surface on top of the sweep.
    assert len(ha) <= 40, ha
    for name in ("SortArray", "ArrayDistinct", "ArrayUnion", "ArrayIntersect",
                 "ArrayExcept", "ArraysOverlap", "Slice", "ConcatArrays",
                 "Flatten", "Sequence", "ArrayRepeat", "ArrayReverse",
                 "Size", "GetArrayItem", "ElementAt"):
        assert name not in ha, f"{name} should be device now"
