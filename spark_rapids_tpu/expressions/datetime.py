"""Date/time expressions.

Reference: org/apache/spark/sql/rapids/datetimeExpressions.scala (1266) +
spark-rapids-jni DateTimeRebase/GpuTimeZoneDB. Carriers: DateType = int32 days
since epoch, TimestampType = int64 micros since epoch UTC (Spark internal
representation). Device field extraction uses Howard Hinnant's civil-calendar
integer algorithms — pure elementwise integer math, ideal for the VPU (the
reference calls cuDF datetime kernels). Session-timezone math runs on device
for any zone with a TZif table: tzdb.TimeZoneDB loads transition tables and
the conversion is a searchsorted + gather before the civil-calendar math
(reference GpuTimeZoneDB); zones without a table fall back to the host arrow
path inside the op.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..types import DataType, DateT, DateType, IntegerT, LongT, TimestampT, TimestampType
from ..columnar.vector import row_mask
from .base import (EvalContext, Expression, UnaryExpression, _DEFAULT_CTX,
                   combine_validity, device_parts, make_column)

MICROS_PER_DAY = 86_400_000_000
MICROS_PER_SECOND = 1_000_000


def _floor_div(a, b):
    return a // b  # python/jax floor semantics match Spark's floorDiv here


def civil_from_days(z):
    """days-since-epoch → (year, month, day); Hinnant's algorithm."""
    z = z.astype(jnp.int64) + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)


def days_from_civil(y, m, d):
    y = y.astype(jnp.int64) - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = (m.astype(jnp.int64) + jnp.where(m > 2, -3, 9))
    doy = (153 * mp + 2) // 5 + d.astype(jnp.int64) - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return (era * 146097 + doe - 719468).astype(jnp.int32)


def _days_of(d, dtype):
    if isinstance(dtype, TimestampType):
        return _floor_div(d.astype(jnp.int64), MICROS_PER_DAY).astype(jnp.int32)
    return d.astype(jnp.int32)


def _localize_micros(d, dtype, ctx):
    """Timestamp micros → session-timezone wall-clock micros (device TZ DB
    binary search; reference GpuTimeZoneDB). Non-timestamp inputs and UTC
    sessions pass through. Returns None when the zone has no TZif table —
    callers fall back to the host arrow path."""
    from ..tzdb import TimeZoneDB, is_utc
    if not isinstance(dtype, TimestampType) or is_utc(getattr(ctx, "tz", None)):
        return d
    db = TimeZoneDB.get(ctx.tz)
    if db is None:
        return None
    return db.utc_to_local(d.astype(jnp.int64))


def _cpu_session_ts(arr, ctx):
    """Arrow timestamp column re-flagged to the session timezone so arrow's
    temporal kernels extract LOCAL fields (instant unchanged)."""
    import pyarrow as pa
    if pa.types.is_timestamp(arr.type):
        tz = getattr(ctx, "tz", None) or "UTC"
        return arr.cast(pa.timestamp(arr.type.unit, tz=tz))
    return arr


class _DateField(UnaryExpression):
    """Extract an integer field from date/timestamp (session-timezone aware
    for timestamps)."""

    @property
    def dtype(self) -> DataType:
        return IntegerT

    _arrow_fn = ""

    def eval_tpu(self, batch, ctx=_DEFAULT_CTX):
        c = self.child.eval_tpu(batch, ctx)
        cap = batch.capacity
        d, v = device_parts(c, cap)
        d = jnp.broadcast_to(d, (cap,))
        local = _localize_micros(d, self.child.dtype, ctx)
        if local is None:  # zone has no TZif table → host oracle path
            from .base import to_column
            from .collections import _result_from_pylist
            col = to_column(c, batch, self.child.dtype)
            arr = _cpu_session_ts(col.to_arrow(), ctx)
            return _result_from_pylist(self._arrow_field(arr).to_pylist(),
                                       IntegerT, batch)
        days = _days_of(local, self.child.dtype)
        data = self._field(days, local)
        valid = combine_validity(cap, v, row_mask(batch.num_rows, cap))
        return make_column(IntegerT, data, valid, batch.num_rows)

    def _arrow_field(self, arr):
        import pyarrow as pa
        import pyarrow.compute as pc
        return pc.cast(getattr(pc, self._arrow_fn)(arr), pa.int32())

    def eval_cpu(self, table, ctx=_DEFAULT_CTX):
        c = _cpu_session_ts(self.child.eval_cpu(table, ctx), ctx)
        return self._arrow_field(c)

    def pretty(self) -> str:
        return f"{type(self).__name__.lower()}({self.child.pretty()})"


class Year(_DateField):
    _arrow_fn = "year"

    def _field(self, days, raw):
        y, m, d = civil_from_days(days)
        return y


class Month(_DateField):
    _arrow_fn = "month"

    def _field(self, days, raw):
        y, m, d = civil_from_days(days)
        return m


class DayOfMonth(_DateField):
    _arrow_fn = "day"

    def _field(self, days, raw):
        y, m, d = civil_from_days(days)
        return d


class Quarter(_DateField):
    _arrow_fn = "quarter"

    def _field(self, days, raw):
        y, m, d = civil_from_days(days)
        return (m - 1) // 3 + 1


class DayOfWeek(_DateField):
    """Spark: 1 = Sunday … 7 = Saturday. 1970-01-01 was a Thursday."""

    def _field(self, days, raw):
        return ((days.astype(jnp.int64) + 4) % 7 + 1).astype(jnp.int32)

    def _arrow_field(self, arr):
        import pyarrow as pa
        import pyarrow.compute as pc
        # Spark: 1=Sunday..7=Saturday == arrow week_start=7, count_from_zero=False
        dow = pc.day_of_week(arr, week_start=7, count_from_zero=False)
        return pc.cast(dow, pa.int32())


class WeekDay(_DateField):
    """Spark weekday(): 0 = Monday … 6 = Sunday."""

    def _field(self, days, raw):
        return ((days.astype(jnp.int64) + 3) % 7).astype(jnp.int32)

    def _arrow_field(self, arr):
        import pyarrow as pa
        import pyarrow.compute as pc
        return pc.cast(pc.day_of_week(arr), pa.int32())


class DayOfYear(_DateField):
    _arrow_fn = "day_of_year"

    def _field(self, days, raw):
        y, m, d = civil_from_days(days)
        jan1 = days_from_civil(y, jnp.ones_like(m), jnp.ones_like(d))
        return (days - jan1 + 1).astype(jnp.int32)


class WeekOfYear(_DateField):
    """ISO 8601 week number (Spark weekofyear)."""

    def _field(self, days, raw):
        d64 = days.astype(jnp.int64)
        # ISO: week of the Thursday of this week
        dow_mon0 = (d64 + 3) % 7  # 0=Monday
        thursday = d64 + (3 - dow_mon0)
        y, m, d = civil_from_days(thursday.astype(jnp.int32))
        jan1 = days_from_civil(y, jnp.ones_like(m), jnp.ones_like(d)).astype(jnp.int64)
        return ((thursday - jan1) // 7 + 1).astype(jnp.int32)

    def _arrow_field(self, arr):
        import pyarrow as pa
        import pyarrow.compute as pc
        return pc.cast(pc.iso_week(arr), pa.int32())


class _TimeField(_DateField):
    def _tod_micros(self, raw):
        micros = raw.astype(jnp.int64)
        days = _floor_div(micros, MICROS_PER_DAY)
        return micros - days * MICROS_PER_DAY


class Hour(_TimeField):
    _arrow_fn = "hour"

    def _field(self, days, raw):
        return (self._tod_micros(raw) // 3_600_000_000).astype(jnp.int32)


class Minute(_TimeField):
    _arrow_fn = "minute"

    def _field(self, days, raw):
        return ((self._tod_micros(raw) // 60_000_000) % 60).astype(jnp.int32)


class Second(_TimeField):
    _arrow_fn = "second"

    def _field(self, days, raw):
        return ((self._tod_micros(raw) // MICROS_PER_SECOND) % 60).astype(jnp.int32)


class LastDay(UnaryExpression):
    """Last day of the month of the given date."""

    @property
    def dtype(self) -> DataType:
        return DateT

    def eval_tpu(self, batch, ctx=_DEFAULT_CTX):
        c = self.child.eval_tpu(batch, ctx)
        cap = batch.capacity
        d, v = device_parts(c, cap)
        days = _days_of(jnp.broadcast_to(d, (cap,)), self.child.dtype)
        y, m, _ = civil_from_days(days)
        ny = jnp.where(m == 12, y + 1, y)
        nm = jnp.where(m == 12, 1, m + 1)
        first_next = days_from_civil(ny, nm, jnp.ones_like(nm))
        valid = combine_validity(cap, v, row_mask(batch.num_rows, cap))
        return make_column(DateT, first_next - 1, valid, batch.num_rows)

    def eval_cpu(self, table, ctx=_DEFAULT_CTX):
        import datetime
        import pyarrow as pa
        vals = self.child.eval_cpu(table, ctx).to_pylist()
        out = []
        for v in vals:
            if v is None:
                out.append(None)
            else:
                nxt = datetime.date(v.year + (v.month == 12),
                                    1 if v.month == 12 else v.month + 1, 1)
                out.append(nxt - datetime.timedelta(days=1))
        return pa.array(out, pa.date32())


class DateAdd(Expression):
    """date_add(date, days)."""

    def __init__(self, date: Expression, days: Expression, negate: bool = False):
        self.children = (date, days)
        self.negate = negate

    @property
    def dtype(self) -> DataType:
        return DateT

    def eval_tpu(self, batch, ctx=_DEFAULT_CTX):
        cap = batch.capacity
        l = self.children[0].eval_tpu(batch, ctx)
        r = self.children[1].eval_tpu(batch, ctx)
        ld, lv = device_parts(l, cap)
        rd, rv = device_parts(r, cap)
        delta = jnp.broadcast_to(rd, (cap,)).astype(jnp.int32)
        if self.negate:
            delta = -delta
        data = jnp.broadcast_to(ld, (cap,)).astype(jnp.int32) + delta
        valid = combine_validity(cap, lv, rv, row_mask(batch.num_rows, cap))
        return make_column(DateT, data, valid, batch.num_rows)

    def eval_cpu(self, table, ctx=_DEFAULT_CTX):
        import pyarrow as pa
        import pyarrow.compute as pc
        l = self.children[0].eval_cpu(table, ctx)
        r = self.children[1].eval_cpu(table, ctx)
        days32 = pc.cast(l, pa.int32())
        delta = pc.cast(r, pa.int32())
        if self.negate:
            delta = pc.negate(delta)
        return pc.cast(pc.add(days32, delta), pa.date32())

    def pretty(self) -> str:
        op = "date_sub" if self.negate else "date_add"
        return f"{op}({self.children[0].pretty()}, {self.children[1].pretty()})"


class DateDiff(Expression):
    """datediff(end, start) in days."""

    def __init__(self, end: Expression, start: Expression):
        self.children = (end, start)

    @property
    def dtype(self) -> DataType:
        return IntegerT

    def eval_tpu(self, batch, ctx=_DEFAULT_CTX):
        cap = batch.capacity
        l = self.children[0].eval_tpu(batch, ctx)
        r = self.children[1].eval_tpu(batch, ctx)
        ld, lv = device_parts(l, cap)
        rd, rv = device_parts(r, cap)
        data = (jnp.broadcast_to(ld, (cap,)).astype(jnp.int32)
                - jnp.broadcast_to(rd, (cap,)).astype(jnp.int32))
        valid = combine_validity(cap, lv, rv, row_mask(batch.num_rows, cap))
        return make_column(IntegerT, data, valid, batch.num_rows)

    def eval_cpu(self, table, ctx=_DEFAULT_CTX):
        import pyarrow as pa
        import pyarrow.compute as pc
        l = pc.cast(self.children[0].eval_cpu(table, ctx), pa.int32())
        r = pc.cast(self.children[1].eval_cpu(table, ctx), pa.int32())
        return pc.subtract(l, r)


class AddMonths(Expression):
    def __init__(self, date: Expression, months: Expression):
        self.children = (date, months)

    @property
    def dtype(self) -> DataType:
        return DateT

    def eval_tpu(self, batch, ctx=_DEFAULT_CTX):
        cap = batch.capacity
        l = self.children[0].eval_tpu(batch, ctx)
        r = self.children[1].eval_tpu(batch, ctx)
        ld, lv = device_parts(l, cap)
        rd, rv = device_parts(r, cap)
        days = jnp.broadcast_to(ld, (cap,)).astype(jnp.int32)
        y, m, d = civil_from_days(days)
        total = (y.astype(jnp.int64) * 12 + (m - 1)
                 + jnp.broadcast_to(rd, (cap,)).astype(jnp.int64))
        ny = (total // 12).astype(jnp.int32)
        nm = (total % 12 + 1).astype(jnp.int32)
        # clamp day to last day of target month (Spark semantics)
        nny = jnp.where(nm == 12, ny + 1, ny)
        nnm = jnp.where(nm == 12, 1, nm + 1)
        last = days_from_civil(nny, nnm, jnp.ones_like(nnm)) - 1
        _, _, last_d = civil_from_days(last)
        nd = jnp.minimum(d, last_d)
        data = days_from_civil(ny, nm, nd)
        valid = combine_validity(cap, lv, rv, row_mask(batch.num_rows, cap))
        return make_column(DateT, data, valid, batch.num_rows)

    def eval_cpu(self, table, ctx=_DEFAULT_CTX):
        import calendar
        import datetime
        import pyarrow as pa
        dates = self.children[0].eval_cpu(table, ctx).to_pylist()
        months = self.children[1].eval_cpu(table, ctx)
        months = months.to_pylist() if hasattr(months, "to_pylist") \
            else [months] * len(dates)
        out = []
        for v, mo in zip(dates, months):
            if v is None or mo is None:
                out.append(None)
                continue
            total = v.year * 12 + (v.month - 1) + int(mo)
            y, m = total // 12, total % 12 + 1
            d = min(v.day, calendar.monthrange(y, m)[1])
            out.append(datetime.date(y, m, d))
        return pa.array(out, pa.date32())


class UnixTimestampFromTs(UnaryExpression):
    """unix_timestamp(ts): seconds since epoch (floor)."""

    @property
    def dtype(self) -> DataType:
        return LongT

    def _compute(self, d, ctx, valid):
        return _floor_div(d.astype(jnp.int64), MICROS_PER_SECOND)

    def eval_cpu(self, table, ctx=_DEFAULT_CTX):
        import pyarrow as pa
        import pyarrow.compute as pc
        c = self.child.eval_cpu(table, ctx)
        micros = pc.cast(c, pa.int64())
        # floor division for negative timestamps
        import numpy as np
        vals, mask = _np_mask(micros)
        return pa.array(np.floor_divide(vals, MICROS_PER_SECOND), mask=mask)


class ToUnixMicros(UnaryExpression):
    @property
    def dtype(self) -> DataType:
        return LongT

    def _compute(self, d, ctx, valid):
        return d.astype(jnp.int64)

    def eval_cpu(self, table, ctx=_DEFAULT_CTX):
        import pyarrow as pa
        import pyarrow.compute as pc
        return pc.cast(self.child.eval_cpu(table, ctx), pa.int64())


def _np_mask(arr):
    import pyarrow as pa
    import pyarrow.compute as pc
    a = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    mask = np.asarray(pc.is_null(a).to_numpy(zero_copy_only=False)).astype(bool)
    vals = np.asarray(a.fill_null(0).to_numpy(zero_copy_only=False))
    return vals, mask


class DateSub(DateAdd):
    """date_sub(date, days) (reference GpuDateSub)."""

    def __init__(self, date: Expression, days: Expression):
        super().__init__(date, days, negate=True)

    def pretty(self) -> str:
        return f"date_sub({self.children[0].pretty()}, {self.children[1].pretty()})"


class _EpochToTimestamp(UnaryExpression):
    """seconds/millis/micros → timestamp (reference GpuSecondsToTimestamp
    family): integer scaling on device."""

    _scale = MICROS_PER_SECOND  # micros per input unit

    @property
    def dtype(self) -> DataType:
        return TimestampT

    def _compute(self, d, ctx, valid):
        return (d.astype(jnp.int64) * self._scale).astype(jnp.int64)

    def eval_cpu(self, table, ctx=_DEFAULT_CTX):
        import pyarrow as pa
        import pyarrow.compute as pc
        c = self.child.eval_cpu(table, ctx)
        micros = pc.multiply(pc.cast(c, pa.int64()), self._scale)
        return pc.cast(micros, pa.timestamp("us", tz="UTC"))

    def pretty(self) -> str:
        return f"{type(self).__name__.lower()}({self.child.pretty()})"


class SecondsToTimestamp(_EpochToTimestamp):
    _scale = MICROS_PER_SECOND


class MillisToTimestamp(_EpochToTimestamp):
    _scale = 1000


class MicrosToTimestamp(_EpochToTimestamp):
    _scale = 1


def _java_to_strftime(pattern: str) -> str:
    """Java SimpleDateFormat subset → strftime. Quoted literals ('T', '')
    copy through; unknown directives (incl. SSS/DD, which have no exact
    strftime width) raise ValueError — callers set tpu_supported=False at
    construction so tagging rejects the expression instead of crashing
    mid-query (mirroring GpuToTimestamp.COMPATIBLE_FORMATS)."""
    out = []
    i = 0
    mapping = {"yyyy": "%Y", "yy": "%y", "MMM": "%b", "MM": "%m", "dd": "%d",
               "HH": "%H", "mm": "%M", "ss": "%S", "EEEE": "%A", "EEE": "%a",
               "a": "%p"}
    toks = ("yyyy", "EEEE", "MMM", "EEE", "yy", "MM", "dd", "HH", "mm", "ss",
            "a")
    while i < len(pattern):
        if pattern[i] == "'":
            # Java quoted literal; '' inside quotes is a literal quote
            if pattern.startswith("''", i):
                out.append("'")
                i += 2
                continue
            j = pattern.find("'", i + 1)
            if j < 0:
                raise ValueError("unterminated quote in datetime pattern")
            lit = pattern[i + 1: j]
            out.append(lit.replace("%", "%%") if lit else "'")
            i = j + 1
            continue
        matched = False
        for tok in toks:
            if pattern.startswith(tok, i):
                out.append(mapping[tok])
                i += len(tok)
                matched = True
                break
        if matched:
            continue
        ch = pattern[i]
        if ch.isalpha():
            raise ValueError(f"unsupported datetime pattern token: {ch}")
        out.append("%%" if ch == "%" else ch)
        i += 1
    return "".join(out)


def _session_zone(ctx):
    """tzinfo of the session timezone (UTC default; unknown zones fall back
    to UTC rather than crashing the host formatting path)."""
    import datetime as _dt
    from ..tzdb import is_utc
    tz = getattr(ctx, "tz", None)
    if is_utc(tz):
        return _dt.timezone.utc
    try:
        from zoneinfo import ZoneInfo
        return ZoneInfo(tz)
    except Exception:  # noqa: BLE001 — unknown zone name
        return _dt.timezone.utc


_SF_CACHE: dict = {}


def _strftime_cached(fmt):
    """fmt → strftime string (memoized); None for null/unsupported fmt."""
    if fmt is None:
        return None
    if fmt not in _SF_CACHE:
        try:
            _SF_CACHE[fmt] = _java_to_strftime(fmt)
        except ValueError:
            _SF_CACHE[fmt] = None
    return _SF_CACHE[fmt]


def _fmt_supported(fmt) -> bool:
    """Constructor-time pattern validation (the tagging gate)."""
    if fmt is None:
        return True
    try:
        _java_to_strftime(fmt)
        return True
    except ValueError:
        return False


def _tz_local_micros(micros, ctx):
    """Epoch micros → session-local wall-clock micros regardless of the
    input dtype (device TZ table binary search; None = no TZif table)."""
    from ..tzdb import TimeZoneDB, is_utc
    if is_utc(getattr(ctx, "tz", None)):
        return micros
    db = TimeZoneDB.get(ctx.tz)
    if db is None:
        return None
    return db.utc_to_local(micros.astype(jnp.int64))


def _device_fmt_plan(fmt):
    """Tokenize a Java datetime pattern into [(kind, value)] when every
    token is fixed-width numeric (yyyy/MM/dd/HH/mm/ss/SSS) or a literal
    byte — the set a device byte-assembly can format. None otherwise."""
    if fmt is None:
        return None
    toks = []
    i = 0
    letters = "GyYMLdHhmsSaEuwWDFkKzZXQqecV'"
    while i < len(fmt):
        ch = fmt[i]
        if ch in letters:
            j = i
            while j < len(fmt) and fmt[j] == ch:
                j += 1
            run = fmt[i:j]
            # SSS deliberately absent: the construction-time gate
            # (_java_to_strftime) rejects it, and strftime's %f (micros)
            # cannot mirror Java millis on the host-fallback path
            if run not in ("yyyy", "MM", "dd", "HH", "mm", "ss"):
                return None
            toks.append(("f", run))
            i = j
        else:
            b = ch.encode("utf-8")
            if len(b) != 1:
                return None
            toks.append(("l", b[0]))
            i += 1
    return toks or None


_FMT_WIDTH = {"yyyy": 4, "MM": 2, "dd": 2, "HH": 2, "mm": 2, "ss": 2}


def _format_micros_device(micros, valid, n, cap, toks):
    """Local-wall-clock micros → formatted string column, fully on device:
    civil fields + per-token zero-padded digit bytes assembled into a
    (cap, W) byte matrix. Returns None when a year falls outside 1..9999
    (Java widens yyyy there — variable width, host path)."""
    from ..columnar.vector import TpuColumnVector
    from ..types import StringT
    micros = micros.astype(jnp.int64)
    days = _floor_div(micros, MICROS_PER_DAY)
    intra = micros - days * MICROS_PER_DAY
    y, mo, d = civil_from_days(days)
    if n:
        sel = valid[:n] if valid is not None else None
        ys = jnp.where(sel, y[:n], 2000) if sel is not None else y[:n]
        # one transfer for both bounds (each eager D→H sync blocks the
        # host on the device)
        ymin, ymax = map(int, jax.device_get(
            jnp.stack([jnp.min(ys), jnp.max(ys)])))
        if ymin < 1 or ymax > 9999:
            return None
    secs = intra // 1_000_000
    fields = {"yyyy": y, "MM": mo, "dd": d,
              "HH": (secs // 3600).astype(jnp.int32),
              "mm": ((secs // 60) % 60).astype(jnp.int32),
              "ss": (secs % 60).astype(jnp.int32),
              "SSS": ((intra // 1000) % 1000).astype(jnp.int32)}
    cols = []
    for kind, v in toks:
        if kind == "l":
            cols.append(jnp.full((cap,), np.uint8(v), jnp.uint8))
        else:
            val = fields[v].astype(jnp.int32)
            w = _FMT_WIDTH[v]
            for k in range(w):
                digit = (val // (10 ** (w - 1 - k))) % 10
                cols.append((digit + 48).astype(jnp.uint8))
    chars = jnp.stack(cols, axis=1).reshape(-1)
    width = len(cols)
    lens = jnp.where(jnp.arange(cap) < n, width, 0).astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(lens, dtype=jnp.int32)])
    return TpuColumnVector(StringT, chars, valid, n, offsets=offs)


class FromUnixTime(Expression):
    """from_unixtime(seconds, fmt) → string, UTC session timezone
    (reference GpuFromUnixTime). Host-assisted formatting."""

    def __init__(self, sec: Expression, fmt: Expression = None):
        from .base import Literal
        self.children = (sec, fmt if fmt is not None
                         else Literal("yyyy-MM-dd HH:mm:ss"))
        f = self.children[1]
        self.tpu_supported = _fmt_supported(
            f.value if isinstance(f, Literal) else None)

    @property
    def dtype(self) -> DataType:
        from ..types import StringT
        return StringT

    def _fmt(self):
        from .base import Literal
        f = self.children[1]
        return f.value if isinstance(f, Literal) else None

    def _format_list(self, secs, ctx, fmts=None):
        import datetime as _dt
        tz = _session_zone(ctx)
        out = []
        for i, s in enumerate(secs):
            fmt = fmts[i] if fmts is not None else self._fmt()
            sf = _strftime_cached(fmt)
            if s is None or sf is None:
                out.append(None)
            else:
                t = _dt.datetime.fromtimestamp(int(s), tz)
                out.append(t.strftime(sf))
        return out

    def _fmts_of(self, batch_or_table, ctx, n, is_tpu):
        """Per-row formats when the fmt child is not a literal."""
        from .base import Literal
        f = self.children[1]
        if isinstance(f, Literal):
            return None
        v = f.eval_tpu(batch_or_table, ctx) if is_tpu \
            else f.eval_cpu(batch_or_table, ctx)
        from ..columnar.vector import TpuScalar
        if isinstance(v, TpuScalar):
            return [v.value] * n
        return v.to_pylist()[:n] if hasattr(v, "to_pylist") else [v] * n

    def eval_tpu(self, batch, ctx=_DEFAULT_CTX):
        from ..columnar.vector import TpuScalar
        from .collections import _result_from_pylist
        c = self.children[0].eval_tpu(batch, ctx)
        if isinstance(c, TpuScalar):
            v = self._format_list([c.value], ctx,
                                  self._fmts_of(batch, ctx, 1, True))[0]
            return TpuScalar(self.dtype, v)
        toks = _device_fmt_plan(self._fmt())
        if toks is not None and not isinstance(c, TpuScalar) \
                and getattr(c, "host_data", None) is None:
            micros = c.data.astype(jnp.int64) * 1_000_000
            local = _tz_local_micros(micros, ctx)
            if local is not None:
                out = _format_micros_device(
                    local, combine_validity(batch.capacity, c.validity,
                                            row_mask(batch.num_rows,
                                                     batch.capacity)),
                    batch.num_rows, batch.capacity, toks)
                if out is not None:
                    return out
        vals = c.to_pylist()
        fmts = self._fmts_of(batch, ctx, len(vals), True)
        return _result_from_pylist(self._format_list(vals, ctx, fmts),
                                   self.dtype, batch)

    def eval_cpu(self, table, ctx=_DEFAULT_CTX):
        import pyarrow as pa
        vals = self.children[0].eval_cpu(table, ctx).to_pylist()
        fmts = self._fmts_of(table, ctx, len(vals), False)
        return pa.array(self._format_list(vals, ctx, fmts), pa.string())

    def pretty(self) -> str:
        return f"from_unixtime({self.children[0].pretty()}, {self.children[1].pretty()})"


class DateFormatClass(Expression):
    """date_format(ts, fmt) → string (reference GpuDateFormatClass). UTC only;
    host-assisted formatting over the civil fields."""

    def __init__(self, ts: Expression, fmt: Expression):
        from .base import Literal
        self.children = (ts, fmt)
        self.tpu_supported = _fmt_supported(
            fmt.value if isinstance(fmt, Literal) else None)

    @property
    def dtype(self) -> DataType:
        from ..types import StringT
        return StringT

    def _format_list(self, vals, ctx, fmts=None):
        from .base import Literal
        import datetime as _dt
        f = self.children[1]
        lit_fmt = f.value if isinstance(f, Literal) else None
        tz = _session_zone(ctx)
        out = []
        for i, v in enumerate(vals):
            fmt = fmts[i] if fmts is not None else lit_fmt
            sf = _strftime_cached(fmt)
            if v is None or sf is None:
                out.append(None)
                continue
            if isinstance(v, _dt.datetime):
                # naive values are UTC instants (the _DateField convention:
                # stored micros are instants, fields display session-local)
                t = (v if v.tzinfo is not None
                     else v.replace(tzinfo=_dt.timezone.utc)).astimezone(tz)
            elif isinstance(v, _dt.date):
                t = _dt.datetime(v.year, v.month, v.day)
            else:
                t = _dt.datetime.fromtimestamp(int(v) / 1e6, tz)
            out.append(t.strftime(sf))
        return out

    def _fmts_of(self, batch_or_table, ctx, n, is_tpu):
        from .base import Literal
        f = self.children[1]
        if isinstance(f, Literal):
            return None
        v = f.eval_tpu(batch_or_table, ctx) if is_tpu \
            else f.eval_cpu(batch_or_table, ctx)
        from ..columnar.vector import TpuScalar
        if isinstance(v, TpuScalar):
            return [v.value] * n
        return v.to_pylist()[:n] if hasattr(v, "to_pylist") else [v] * n

    def eval_tpu(self, batch, ctx=_DEFAULT_CTX):
        from .base import Literal
        from ..columnar.vector import TpuScalar
        from .collections import _result_from_pylist
        c = self.children[0].eval_tpu(batch, ctx)
        if isinstance(c, TpuScalar):
            return TpuScalar(self.dtype, self._format_list([c.value], ctx)[0])
        f = self.children[1]
        toks = _device_fmt_plan(f.value if isinstance(f, Literal) else None)
        if toks is not None and getattr(c, "host_data", None) is None:
            dt = self.children[0].dtype
            if isinstance(dt, TimestampType):
                local = _tz_local_micros(c.data.astype(jnp.int64), ctx)
            elif isinstance(dt, DateType):
                local = c.data.astype(jnp.int64) * MICROS_PER_DAY
            else:
                local = None
            if local is not None:
                out = _format_micros_device(
                    local, combine_validity(batch.capacity, c.validity,
                                            row_mask(batch.num_rows,
                                                     batch.capacity)),
                    batch.num_rows, batch.capacity, toks)
                if out is not None:
                    return out
        return _result_from_pylist(self._format_list(c.to_pylist(), ctx),
                                   self.dtype, batch)

    def eval_cpu(self, table, ctx=_DEFAULT_CTX):
        import pyarrow as pa
        vals = self.children[0].eval_cpu(table, ctx).to_pylist()
        return pa.array(self._format_list(vals, ctx), pa.string())

    def pretty(self) -> str:
        return f"date_format({self.children[0].pretty()}, {self.children[1].pretty()})"


class ToUnixTimestamp(Expression):
    """to_unix_timestamp(str|ts|date, fmt) → bigint seconds (reference
    GpuToUnixTimestamp). String inputs parse host-side (UTC); timestamp/date
    inputs scale on device."""

    def __init__(self, child: Expression, fmt: Expression = None):
        from .base import Literal
        self.children = (child, fmt if fmt is not None
                         else Literal("yyyy-MM-dd HH:mm:ss"))
        f = self.children[1]
        self.tpu_supported = _fmt_supported(
            f.value if isinstance(f, Literal) else None)

    @property
    def dtype(self) -> DataType:
        return LongT

    def _fmt(self):
        from .base import Literal
        f = self.children[1]
        return f.value if isinstance(f, Literal) else None

    def _parse_list(self, vals, ctx, fmts=None):
        import datetime as _dt
        tz = _session_zone(ctx)
        out = []
        for i, v in enumerate(vals):
            fmt = fmts[i] if fmts is not None else self._fmt()
            sf = _strftime_cached(fmt)
            if v is None or sf is None:
                out.append(None)
                continue
            try:
                # fold=0: ambiguous wall times take the earlier offset,
                # matching java.time (and the device TZ-DB kernel)
                t = _dt.datetime.strptime(v, sf).replace(tzinfo=tz, fold=0)
                out.append(int(t.timestamp()))
            except ValueError:
                out.append(None)  # Spark: unparseable → null
        return out

    def _fmts_of(self, batch_or_table, ctx, n, is_tpu):
        from .base import Literal
        f = self.children[1]
        if isinstance(f, Literal):
            return None
        v = f.eval_tpu(batch_or_table, ctx) if is_tpu \
            else f.eval_cpu(batch_or_table, ctx)
        from ..columnar.vector import TpuScalar
        if isinstance(v, TpuScalar):
            return [v.value] * n
        return v.to_pylist()[:n] if hasattr(v, "to_pylist") else [v] * n

    def eval_tpu(self, batch, ctx=_DEFAULT_CTX):
        import pyarrow as pa
        from ..columnar.batch import _repad
        from ..columnar.vector import TpuColumnVector, TpuScalar
        from ..types import DateType, StringType, TimestampType
        src = self.children[0]
        c = src.eval_tpu(batch, ctx)
        if isinstance(src.dtype, TimestampType) and isinstance(c, TpuColumnVector):
            data = _floor_div(c.data.astype(jnp.int64), MICROS_PER_SECOND)
            valid = combine_validity(batch.capacity, c.validity,
                                     row_mask(batch.num_rows, batch.capacity))
            return make_column(LongT, data, valid, batch.num_rows)
        if isinstance(src.dtype, DateType) and isinstance(c, TpuColumnVector):
            from ..tzdb import TimeZoneDB, is_utc
            local_midnight = c.data.astype(jnp.int64) * MICROS_PER_DAY
            if is_utc(getattr(ctx, "tz", None)):
                utc = local_midnight
            else:
                db = TimeZoneDB.get(ctx.tz)
                if db is None:
                    raise ValueError(f"unknown session timezone {ctx.tz}")
                utc = db.local_to_utc(local_midnight)
            data = _floor_div(utc, MICROS_PER_SECOND)
            valid = combine_validity(batch.capacity, c.validity,
                                     row_mask(batch.num_rows, batch.capacity))
            return make_column(LongT, data, valid, batch.num_rows)
        from .collections import _result_from_pylist
        vals = [c.value] * batch.num_rows if isinstance(c, TpuScalar) \
            else c.to_pylist()
        fmts = self._fmts_of(batch, ctx, len(vals), True)
        return _result_from_pylist(self._parse_list(vals, ctx, fmts),
                                   LongT, batch)

    def eval_cpu(self, table, ctx=_DEFAULT_CTX):
        import datetime as _dt
        import pyarrow as pa
        from ..types import DateType, StringType, TimestampType
        src = self.children[0]
        vals = src.eval_cpu(table, ctx).to_pylist()
        if isinstance(src.dtype, TimestampType):
            out = [None if v is None else
                   int(v.timestamp() // 1) if isinstance(v, _dt.datetime)
                   else int(v) // 1000000 for v in vals]
            return pa.array(out, pa.int64())
        if isinstance(src.dtype, DateType):
            tz = _session_zone(ctx)
            out = [None if v is None else
                   int(_dt.datetime(v.year, v.month, v.day,
                                    tzinfo=tz, fold=0).timestamp())
                   for v in vals]
            return pa.array(out, pa.int64())
        fmts = self._fmts_of(table, ctx, len(vals), False)
        return pa.array(self._parse_list(vals, ctx, fmts), pa.int64())

    def pretty(self) -> str:
        return f"to_unix_timestamp({self.children[0].pretty()}, {self.children[1].pretty()})"


class UnixTimestamp(ToUnixTimestamp):
    """unix_timestamp(...) — same semantics as to_unix_timestamp
    (reference GpuUnixTimestamp)."""

    def pretty(self) -> str:
        return f"unix_timestamp({self.children[0].pretty()}, {self.children[1].pretty()})"
