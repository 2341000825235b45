"""Decimal beyond precision 18: two-int64-limb device arithmetic.
Reference: spark-rapids-jni DecimalUtils
(__int128 CUDA kernels); here the 128-bit value is (hi, lo) int64 limbs and
every op is explicit-carry int64 math — kernels/decimal128.py.
"""

import decimal
import random

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.batch import TpuColumnarBatch, compact, gather
from spark_rapids_tpu.columnar.vector import TpuColumnVector
from spark_rapids_tpu.expressions.arithmetic import Add, Multiply, Subtract
from spark_rapids_tpu.expressions.base import (AttributeReference, EvalContext,
                                               ExpressionError, Literal)
from spark_rapids_tpu.kernels import decimal128 as D
from spark_rapids_tpu.types import DecimalType
from spark_rapids_tpu.config import RapidsConf

DEC = decimal.Decimal
BOUND = 10 ** 38 - 1


def test_limb_kernels_fuzz():
    """Property test vs python bignum: add/sub/mul/cmp/precision-overflow."""
    import jax.numpy as jnp
    rng = random.Random(7)
    a = [rng.randint(-BOUND, BOUND) for _ in range(300)] + \
        [0, 1, -1, BOUND, -BOUND, 2**64, -(2**64), 2**63, -(2**63)]
    b = [rng.randint(-BOUND, BOUND) for _ in range(300)] + \
        [1, -1, -BOUND, BOUND, 0, -(2**64), 2**64, -(2**63), 2**63]
    A, B = D.pack(a), D.pack(b)
    ah, al = jnp.asarray(A[:, 0]), jnp.asarray(A[:, 1])
    bh, bl = jnp.asarray(B[:, 0]), jnp.asarray(B[:, 1])
    h, l, _ = D.add128(ah, al, bh, bl)
    got = D.unpack(np.stack([np.asarray(h), np.asarray(l)], 1))
    for g, x, y in zip(got, a, b):
        if abs(x + y) < 2 ** 127:
            assert g == x + y
    h, l, _ = D.sub128(ah, al, bh, bl)
    got = D.unpack(np.stack([np.asarray(h), np.asarray(l)], 1))
    for g, x, y in zip(got, a, b):
        if abs(x - y) < 2 ** 127:
            assert g == x - y
    h, l, ovf = D.mul128(ah, al, bh, bl)
    got = D.unpack(np.stack([np.asarray(h), np.asarray(l)], 1))
    for g, x, y, o in zip(got, a, b, np.asarray(ovf)):
        if abs(x * y) < 2 ** 127:
            assert not o and g == x * y
        else:
            assert o
    c = np.asarray(D.cmp128(ah, al, bh, bl))
    for g, x, y in zip(c, a, b):
        assert g == (x > y) - (x < y)
    po = np.asarray(D.precision_overflow(ah, al, 38))
    for g, x in zip(po, a):
        assert bool(g) == (abs(x) > BOUND)


def _setup(vals_a, vals_b, scale=8):
    t = pa.decimal128(38, scale)
    arr_a, arr_b = pa.array(vals_a, t), pa.array(vals_b, t)
    ca, cb = TpuColumnVector.from_arrow(arr_a), TpuColumnVector.from_arrow(arr_b)
    batch = TpuColumnarBatch([ca, cb], len(vals_a), names=["a", "b"])
    return (batch, pa.table({"a": arr_a, "b": arr_b}),
            AttributeReference("a", ca.dtype, ordinal=0),
            AttributeReference("b", cb.dtype, ordinal=1))


VALS_A = [DEC("12345678901234567890.12345678"),
          DEC("9" * 30 + ".12345678"), None,
          DEC("-" + "9" * 30 + ".00000001"), DEC("0.00000001"),
          DEC("-0.00000001")]
VALS_B = [DEC("98765432109876543210.87654321"),
          DEC("9" * 30 + ".12345678"), DEC("1.00000000"),
          DEC("9" * 30 + ".0"), DEC("-0.00000002"), None]


@pytest.mark.parametrize("op", [Add, Subtract, Multiply])
def test_decimal38_matches_oracle(op):
    batch, tbl, ra, rb = _setup(VALS_A, VALS_B)
    e = op(ra, rb)
    got = e.eval_tpu(batch).to_arrow().to_pylist()[: len(VALS_A)]
    want = e.eval_cpu(tbl).to_pylist()
    assert got == want, f"{got} != {want}"


def test_decimal38_overflow_null_and_ansi():
    """Result precision overflow → null (non-ANSI) / error (ANSI)."""
    batch, tbl, ra, rb = _setup([DEC("9" * 30)], [DEC("9" * 30)], scale=0)
    e = Multiply(ra, rb)
    assert e.eval_tpu(batch).to_arrow().to_pylist()[:1] == [None]
    ansi = EvalContext(RapidsConf({"spark.sql.ansi.enabled": "true"}))
    with pytest.raises(ExpressionError):
        e.eval_tpu(batch, ansi)


def test_decimal38_scalar_operand():
    batch, tbl, ra, rb = _setup(VALS_A, VALS_B)
    e = Multiply(ra, Literal(DEC("2.00000000"), DecimalType(38, 8)))
    got = e.eval_tpu(batch).to_arrow().to_pylist()[: len(VALS_A)]
    want = e.eval_cpu(tbl).to_pylist()
    assert got == want


def test_decimal128_column_roundtrip_and_batch_ops():
    """Limb columns survive gather/compact (the batch-op surface)."""
    batch, tbl, ra, rb = _setup(VALS_A, VALS_B)
    import jax.numpy as jnp
    keep = jnp.asarray([True, False, True, True, False, True]
                       + [False] * (batch.capacity - 6))
    filtered = compact(batch, keep)
    got = filtered.columns[0].to_arrow().to_pylist()
    want = [v for v, k in zip(VALS_A, [True, False, True, True, False, True]) if k]
    assert got == want
    idx = jnp.asarray([5, 0, 3] + [0] * (batch.capacity - 3))
    g = gather(batch, idx, 3, out_capacity=batch.capacity)
    assert g.columns[0].to_arrow().to_pylist() == [VALS_A[5], VALS_A[0],
                                                   VALS_A[3]]


def test_decimal128_registered_for_arithmetic():
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import spark_rapids_tpu.plan.overrides  # noqa: F401
    from spark_rapids_tpu.plan.typechecks import expr_sig_for
    sig = expr_sig_for(Add)
    assert sig.supports(DecimalType(38, 8))
    assert sig.supports(DecimalType(18, 2))
