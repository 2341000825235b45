"""Cast expression — Spark's cast matrix subset with ANSI support.

Reference: /root/reference/sql-plugin/.../GpuCast.scala (1903 LoC) + CastChecks in
TypeChecks.scala. Implemented pairs (grown over rounds, gated by CastChecks in
plan/typechecks.py): numeric↔numeric (with Spark's overflow wrap / ANSI raise),
bool↔numeric, numeric↔string, string→numeric (host-assisted), date/timestamp↔long,
anything→string per Spark formatting for fixed-width types.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..types import (BooleanType, BooleanT, ByteType, DataType, DateType,
                     DecimalType, DoubleType, FloatType, FractionalType, IntegerType,
                     IntegralType, LongType, NumericType, ShortType, StringType,
                     StringT, TimestampType)
from ..columnar.vector import TpuColumnVector, TpuScalar, row_mask
from .base import (EvalContext, Expression, ExpressionError, UnaryExpression,
                   _DEFAULT_CTX, combine_validity, device_parts, make_column)

_INT_BOUNDS = {np.dtype(np.int8): (-128, 127),
               np.dtype(np.int16): (-32768, 32767),
               np.dtype(np.int32): (-2**31, 2**31 - 1),
               np.dtype(np.int64): (-2**63, 2**63 - 1)}


class Cast(UnaryExpression):
    def __init__(self, child: Expression, to_type: DataType, ansi: Optional[bool] = None):
        super().__init__(child)
        self._to = to_type
        self._ansi = ansi

    @property
    def dtype(self) -> DataType:
        return self._to

    @property
    def nullable(self) -> bool:
        return True

    def pretty(self) -> str:
        return f"cast({self.child.pretty()} AS {self._to.simple_string()})"

    def eval_tpu(self, batch, ctx=_DEFAULT_CTX):
        src = self.child.dtype
        dst = self._to
        c = self.child.eval_tpu(batch, ctx)
        ansi = self._ansi if self._ansi is not None else ctx.ansi
        if isinstance(c, TpuScalar):
            return TpuScalar(dst, _cast_scalar(c.value, src, dst, ansi))
        if src == dst:
            return c
        if isinstance(src, StringType) or isinstance(dst, StringType):
            return _cast_via_host(c, src, dst, batch, ansi)
        cap = batch.capacity
        d, v = device_parts(c, cap)
        valid = combine_validity(cap, v, row_mask(batch.num_rows, cap))
        data, extra_null = _device_numeric_cast(d, src, dst, ansi, valid)
        if extra_null is not None:
            valid = combine_validity(cap, valid, ~extra_null)
        return make_column(dst, data, valid, batch.num_rows)

    def eval_cpu(self, table, ctx=_DEFAULT_CTX):
        import pyarrow as pa
        import pyarrow.compute as pc
        from ..types import to_arrow
        c = self.child.eval_cpu(table, ctx)
        src, dst = self.child.dtype, self._to
        ansi = self._ansi if self._ansi is not None else ctx.ansi
        if not isinstance(c, (pa.Array, pa.ChunkedArray)):
            return _cast_scalar(c, src, dst, ansi)
        if isinstance(dst, StringType):
            return _format_to_string_arrow(c, src)
        if isinstance(src, StringType):
            return _parse_string_arrow(c, dst, ansi)
        at = to_arrow(dst)
        if isinstance(src, FractionalType) and isinstance(dst, IntegralType):
            # Spark float→int truncates toward zero, out-of-range wraps (non-ANSI)
            ln, lm = _np_of(c)
            return pa.array(_float_to_int_np(ln, at.to_pandas_dtype(), ansi, ~lm),
                            mask=lm)
        if isinstance(src, TimestampType) and isinstance(dst, IntegralType):
            # Spark timestampToLong = floorDiv(micros, 1e6), not raw micros;
            # narrower targets wrap like java narrowing (ANSI raises)
            micros, lm = _np_of(pc.cast(c, pa.int64()))
            secs = np.floor_divide(micros, 1_000_000)
            np_t = np.dtype(dst.np_dtype)
            if np_t.itemsize < 8:
                lo, hi = _INT_BOUNDS[np_t]
                if ansi and bool((((secs < lo) | (secs > hi)) & ~lm).any()):
                    raise ExpressionError("cast overflow")
                secs = secs.astype(np_t)  # two's-complement wrap
            return pa.array(secs, mask=lm).cast(at, safe=False)
        if isinstance(src, IntegralType) and isinstance(dst, TimestampType):
            secs, lm = _np_of(c)
            return pa.array(secs.astype(np.int64) * 1_000_000,
                            mask=lm).cast(at)
        try:
            return pc.cast(c, at, safe=ansi)
        except pa.ArrowInvalid as e:
            if ansi:
                raise ExpressionError(str(e)) from e
            return pc.cast(c, at, safe=False)


def _np_of(arr):
    import pyarrow as pa
    import pyarrow.compute as pc
    a = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    mask = np.asarray(pc.is_null(a).to_numpy(zero_copy_only=False)).astype(bool)
    vals = np.asarray(a.fill_null(0).to_numpy(zero_copy_only=False))
    return vals, mask


def _float_to_int_np(vals, np_int, ansi, valid):
    lo, hi = _INT_BOUNDS[np.dtype(np_int)]
    finite = np.isfinite(vals)
    if ansi and bool(((~finite | (vals < lo) | (vals > hi)) & valid).any()):
        raise ExpressionError("cast overflow")
    v = np.trunc(np.where(np.isnan(vals), 0.0, vals))
    # 2**63-1 is not float-representable: use exact power-of-two range tests
    hi_f = np.float64(float(hi) if np.dtype(np_int).itemsize < 8 else 2.0**63)
    lo_f = np.float64(lo)
    in_range = (v >= lo_f) & (v < hi_f) if np.dtype(np_int).itemsize == 8 \
        else (v >= lo_f) & (v <= hi_f)
    safe = np.where(in_range, v, 0.0).astype(np_int)
    return np.where(v >= hi_f, np_int(hi), np.where(v < lo_f, np_int(lo), safe))


def _device_numeric_cast(d, src: DataType, dst: DataType, ansi: bool, valid):
    """Fixed-width device cast. Returns (data, extra_null_mask_or_None)."""
    carrier = dst.np_dtype
    if isinstance(src, BooleanType) and isinstance(dst, NumericType):
        return d.astype(carrier), None
    if isinstance(dst, BooleanType):
        return (d != 0), None
    if isinstance(src, FractionalType) and isinstance(dst, IntegralType):
        lo, hi = _INT_BOUNDS[np.dtype(carrier)]
        nan = jnp.isnan(d)
        if ansi:
            bad = nan | (d < lo) | (d > hi)
            if valid is not None:
                bad = bad & valid
            if bool(jnp.any(bad)):
                raise ExpressionError("cast overflow")
        # Java (int)/(long) conversion: NaN→0, out-of-range clamps to MIN/MAX.
        # For int64 the upper bound 2**63-1 is not float-representable; use exact
        # power-of-two range tests instead of clip.
        v = jnp.trunc(jnp.where(nan, 0.0, d))
        hi_f = 2.0 ** 63 if np.dtype(carrier).itemsize == 8 else float(hi)
        in_range = (v >= float(lo)) & (v < hi_f) if np.dtype(carrier).itemsize == 8 \
            else (v >= float(lo)) & (v <= hi_f)
        safe = jnp.where(in_range, v, 0.0).astype(carrier)
        data = jnp.where(v >= hi_f, jnp.asarray(hi, carrier),
                         jnp.where(v < float(lo), jnp.asarray(lo, carrier), safe))
        return data, None
    if isinstance(src, IntegralType) and isinstance(dst, IntegralType):
        if np.dtype(carrier).itemsize < np.dtype(src.np_dtype).itemsize and ansi:
            lo, hi = _INT_BOUNDS[np.dtype(carrier)]
            bad = (d < lo) | (d > hi)
            if valid is not None:
                bad = bad & valid
            if bool(jnp.any(bad)):
                raise ExpressionError("cast overflow")
        return d.astype(carrier), None  # wraps like java narrowing (non-ANSI)
    if isinstance(src, (DateType,)) and isinstance(dst, IntegralType):
        return d.astype(carrier), None
    if isinstance(src, TimestampType) and isinstance(dst, LongType):
        return _trunc_div_seconds(d), None
    if isinstance(src, IntegralType) and isinstance(dst, TimestampType):
        return (d.astype(jnp.int64) * 1_000_000), None
    if isinstance(src, TimestampType) and isinstance(dst, DoubleType):
        return d.astype(jnp.float64) / 1e6, None
    if isinstance(src, NumericType) and isinstance(dst, NumericType):
        return d.astype(carrier), None
    raise NotImplementedError(f"device cast {src} -> {dst}")


def _trunc_div_seconds(d):
    # Spark timestampToLong = Math.floorDiv(micros, 1e6): -0.5s -> -1
    # (jnp integer // is floor division already)
    return d // 1_000_000


def _cast_via_host(col: TpuColumnVector, src, dst, batch, ansi):
    import pyarrow as pa
    arr = col.to_arrow()
    if isinstance(dst, StringType):
        out = _format_to_string_arrow(arr, src)
    else:
        out = _parse_string_arrow(arr, dst, ansi)
    res = TpuColumnVector.from_arrow(out)
    if res.capacity != batch.capacity:
        from ..columnar.batch import _repad
        res = _repad(res, batch.capacity)
    return res


def _format_to_string_arrow(arr, src: DataType):
    """Spark-exact value formatting (Ryu-style shortest repr for floats, 'true'/'false',
    decimal trailing-zero rules) — reference GpuCast castToString."""
    import pyarrow as pa
    vals = arr.to_pylist()
    out = []
    for v in vals:
        if v is None:
            out.append(None)
        elif isinstance(src, BooleanType):
            out.append("true" if v else "false")
        elif isinstance(src, (FloatType, DoubleType)):
            out.append(_spark_float_str(v, isinstance(src, FloatType)))
        elif isinstance(src, TimestampType):
            out.append(v.strftime("%Y-%m-%d %H:%M:%S") +
                       (f".{v.microsecond:06d}".rstrip("0") if v.microsecond else ""))
        elif isinstance(src, DateType):
            out.append(v.isoformat())
        else:
            out.append(str(v))
    return pa.array(out, type=pa.string())


def _spark_float_str(v: float, is_float32: bool) -> str:
    """Java Double.toString / Float.toString semantics exactly: shortest
    round-trip digits; plain decimal form when 1e-3 <= |v| < 1e7, otherwise
    scientific `d.dddEexp` with one digit before the point (reference
    GpuCast castToString float path / castFloatingTypesToString; the 'Ryu
    quirks' — python repr switches notation at different
    thresholds, so the digits are re-laid-out here)."""
    if np.isnan(v):
        return "NaN"
    if np.isinf(v):
        return "Infinity" if v > 0 else "-Infinity"
    f = float(np.float32(v)) if is_float32 else float(v)
    if f == 0.0:
        return "-0.0" if np.signbit(f) else "0.0"
    # shortest round-trip digits (str() is shortest for the type; known
    # divergence: ties between equally-short reprs can pick a different
    # digit than Java's Ryu, e.g. Double.MIN_VALUE 5e-324 vs Java 4.9E-324)
    s = str(np.float32(v)) if is_float32 else repr(f)
    neg = s.startswith("-")
    if neg:
        s = s[1:]
    if "e" in s or "E" in s:
        mant, _, exp = s.replace("E", "e").partition("e")
        exp10 = int(exp)
    else:
        mant, exp10 = s, 0
    # normalize mantissa to pure digit string + exponent of leading digit
    if "." in mant:
        int_part, frac = mant.split(".")
    else:
        int_part, frac = mant, ""
    digits = (int_part + frac).lstrip("0")
    lead_exp = exp10 + len(int_part.lstrip("0")) - 1 if int_part.strip("0") \
        else exp10 - (len(frac) - len(frac.lstrip("0"))) - 1
    digits = digits.rstrip("0") or "0"
    sign = "-" if neg else ""
    if -3 <= lead_exp < 7:
        if lead_exp >= 0:
            ip = digits[:lead_exp + 1].ljust(lead_exp + 1, "0")
            fp = digits[lead_exp + 1:] or "0"
        else:
            ip = "0"
            fp = "0" * (-lead_exp - 1) + digits
        return f"{sign}{ip}.{fp}"
    fp = digits[1:] or "0"
    return f"{sign}{digits[0]}.{fp}E{lead_exp}"


def _parse_string_arrow(arr, dst: DataType, ansi: bool):
    import pyarrow as pa
    import pyarrow.compute as pc
    from ..types import to_arrow
    trimmed = pc.utf8_trim_whitespace(arr)
    at = to_arrow(dst)
    if isinstance(dst, BooleanType):
        lowered = pc.utf8_lower(trimmed)
        true_set = pa.array(["t", "true", "y", "yes", "1"])
        false_set = pa.array(["f", "false", "n", "no", "0"])
        is_t = pc.is_in(lowered, value_set=true_set)
        is_f = pc.is_in(lowered, value_set=false_set)
        bad = pc.and_(pc.invert(is_t), pc.invert(is_f))
        if ansi and bool(pc.any(pc.fill_null(bad, False)).as_py()):
            raise ExpressionError("invalid input for cast to boolean")
        return pc.if_else(bad, pa.scalar(None, pa.bool_()), is_t)
    if isinstance(dst, IntegralType):
        # Spark accepts trailing .xxx for int casts? Only via decimal path; keep strict
        vals = trimmed.to_pylist() if isinstance(trimmed, pa.Array) else trimmed.combine_chunks().to_pylist()
        out = []
        lo, hi = _INT_BOUNDS[np.dtype(dst.np_dtype)]
        for s in vals:
            if s is None:
                out.append(None)
                continue
            try:
                v = int(s)
                if v < lo or v > hi:
                    raise ValueError("overflow")
                out.append(v)
            except ValueError:
                if ansi:
                    raise ExpressionError(f"invalid input for cast to {dst}: {s!r}")
                out.append(None)
        return pa.array(out, type=at)
    if isinstance(dst, (FloatType, DoubleType)):
        vals = trimmed.to_pylist() if isinstance(trimmed, pa.Array) else trimmed.combine_chunks().to_pylist()
        out = []
        for s in vals:
            if s is None:
                out.append(None)
                continue
            try:
                sl = s.lower()
                if sl in ("nan",):
                    out.append(float("nan"))
                elif sl in ("inf", "infinity", "+inf", "+infinity"):
                    out.append(float("inf"))
                elif sl in ("-inf", "-infinity"):
                    out.append(float("-inf"))
                else:
                    # Java Double.parseDouble accepts a trailing d/D/f/F
                    # type suffix ("1d" == 1.0); Spark inherits it
                    if sl and sl[-1] in "df" and len(sl) > 1 \
                            and (sl[-2].isdigit() or sl[-2] == "."):
                        s = s[:-1]
                    out.append(float(s))
            except ValueError:
                if ansi:
                    raise ExpressionError(f"invalid input for cast to {dst}: {s!r}")
                out.append(None)
        return pa.array(out, type=at)
    if isinstance(dst, DateType):
        vals = trimmed.to_pylist() if isinstance(trimmed, pa.Array) \
            else trimmed.combine_chunks().to_pylist()
        out = []
        for s in vals:
            d = None if s is None else _parse_spark_date(s)
            if s is not None and d is None and ansi:
                raise ExpressionError(f"invalid input for cast to date: {s!r}")
            out.append(d)
        return pa.array(out, type=pa.date32())
    if isinstance(dst, TimestampType):
        vals = trimmed.to_pylist() if isinstance(trimmed, pa.Array) \
            else trimmed.combine_chunks().to_pylist()
        out = []
        for s in vals:
            us = None if s is None else _parse_spark_timestamp(s)
            if s is not None and us is None and ansi:
                raise ExpressionError(
                    f"invalid input for cast to timestamp: {s!r}")
            out.append(us)
        return pa.array(out, type=pa.timestamp("us")).cast(at)
    if isinstance(dst, DecimalType):
        vals = trimmed.to_pylist() if isinstance(trimmed, pa.Array) \
            else trimmed.combine_chunks().to_pylist()
        out = []
        for s in vals:
            d = None if s is None else _parse_spark_decimal(
                s, dst.precision, dst.scale)
            if s is not None and d is None and ansi:
                raise ExpressionError(
                    f"invalid input for cast to {dst.simple_string()}: {s!r}")
            out.append(d)
        return pa.array(out, type=pa.decimal128(dst.precision, dst.scale))
    raise NotImplementedError(f"string cast to {dst}")


_DATE_RE = None
_TIME_RE = None


def _parse_spark_date(s: str):
    """Spark stringToDate: `[+-]y{1,7}[-m[-d]]`, anything after the day
    allowed when separated by ' ' or 'T' (reference GpuCast castStringToDate;
    org.apache.spark.sql.catalyst.util.DateTimeUtils.stringToDate).
    Returns datetime.date or None."""
    import datetime
    import re as _re2
    global _DATE_RE
    if _DATE_RE is None:
        _DATE_RE = _re2.compile(
            r"^([+-]?\d{1,7})(?:-(\d{1,2})(?:-(\d{1,2})(?:[ T].*)?)?)?$")
    m = _DATE_RE.match(s.strip())
    if not m:
        return None
    y = int(m.group(1))
    mo = int(m.group(2)) if m.group(2) else 1
    d = int(m.group(3)) if m.group(3) else 1
    try:
        return datetime.date(y, mo, d)  # proleptic Gregorian, 1..9999
    except ValueError:
        return None


def _parse_spark_timestamp(s: str):
    """Spark stringToTimestamp (UTC session zone): date part as in
    stringToDate, optional `[h]h[:[m]m[:[s]s[.f{1,9}]]]` after ' ' or 'T',
    optional zone `Z` / `UTC` / `GMT` / `[+-]h[h][:mm]`. Returns epoch
    microseconds (int) or None. 'epoch' special literal supported."""
    import datetime
    import re as _re2
    s = s.strip()
    if s.lower() == "epoch":
        return 0
    global _TIME_RE
    if _TIME_RE is None:
        _TIME_RE = _re2.compile(
            r"^([+-]?\d{1,7})(?:-(\d{1,2})(?:-(\d{1,2})"
            r"(?:[ T](\d{1,2})(?::(\d{1,2})(?::(\d{1,2})"
            r"(?:\.(\d{1,9}))?)?)?\s*(.*))?)?)?$")
    m = _TIME_RE.match(s)
    if not m:
        return None
    y = int(m.group(1))
    mo = int(m.group(2)) if m.group(2) else 1
    d = int(m.group(3)) if m.group(3) else 1
    hh = int(m.group(4)) if m.group(4) else 0
    mi = int(m.group(5)) if m.group(5) else 0
    ss = int(m.group(6)) if m.group(6) else 0
    frac = m.group(7) or ""
    us = int(frac[:6].ljust(6, "0")) if frac else 0
    zone = (m.group(8) or "").strip()
    off_us = 0
    if zone:
        zm = _re2.match(r"^(?:Z|z|UTC|GMT)$", zone)
        if zm:
            off_us = 0
        else:
            zm = _re2.match(r"^([+-])(\d{1,2})(?::(\d{1,2}))?$", zone)
            if not zm:
                return None
            sign = 1 if zm.group(1) == "+" else -1
            off_us = sign * ((int(zm.group(2)) * 60
                              + int(zm.group(3) or 0)) * 60 * 1_000_000)
    if hh > 23 or mi > 59 or ss > 59:
        return None
    try:
        day = datetime.date(y, mo, d)
    except ValueError:
        return None
    epoch_days = (day - datetime.date(1970, 1, 1)).days
    local = (epoch_days * 86_400_000_000
             + (hh * 3600 + mi * 60 + ss) * 1_000_000 + us)
    return local - off_us


def _parse_spark_decimal(s: str, precision: int, scale: int):
    """Spark string→decimal: parse, HALF_UP round to scale, null on
    overflow/garbage (reference GpuCast castStringToDecimal)."""
    import decimal
    try:
        d = decimal.Decimal(s.strip())
    except decimal.InvalidOperation:
        return None
    if not d.is_finite():
        return None
    # default context precision (28) would raise on wide-but-valid
    # decimal(38) inputs; Spark's Decimal holds 38 digits + rounding room
    with decimal.localcontext() as dctx:
        dctx.prec = 60
        q = d.quantize(decimal.Decimal(1).scaleb(-scale),
                       rounding=decimal.ROUND_HALF_UP)
    if len(q.as_tuple().digits) - scale > precision - scale and q != 0:
        return None  # integral part too wide
    return q


def _cast_scalar(v, src, dst, ansi):
    if v is None:
        return None
    import pyarrow as pa
    arr = pa.array([v], type=None if not isinstance(src, DataType) else None)
    # simple python-level conversion mirroring the array paths
    if isinstance(dst, StringType):
        return _format_to_string_arrow(pa.array([v]), src)[0].as_py()
    if isinstance(dst, BooleanType):
        return bool(v)
    if isinstance(dst, IntegralType):
        lo, hi = _INT_BOUNDS[np.dtype(dst.np_dtype)]
        if isinstance(v, str):
            v = int(v.strip())
        iv = int(v)
        if iv < lo or iv > hi:
            if ansi:
                raise ExpressionError("cast overflow")
            iv = ((iv - lo) % (hi - lo + 1)) + lo  # java wrap
        return iv
    if isinstance(dst, (FloatType, DoubleType)):
        return float(v)
    raise NotImplementedError(f"scalar cast {src} -> {dst}")
