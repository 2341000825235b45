"""User-facing session + DataFrame API (the PySpark-shaped front door).

The reference is a plugin inside Spark; a standalone framework needs its own
entry point. The API mirrors pyspark.sql so a spark-rapids user finds the same
surface: TpuSession.builder, createDataFrame/range/read, DataFrame
select/filter/groupBy/join/sort/limit/union/collect, conf get/set, explain.
Execution: logical plan → planner (CPU physical) → TpuOverrides (retarget to
TPU + transitions) → partition-parallel execution.
"""

from __future__ import annotations

import concurrent.futures as _fut
import itertools as _itertools
from typing import Any, Dict, Iterable, List, Optional, Sequence, Union

from .config import RapidsConf
from .expressions.base import (Alias, AttributeReference, Expression, Literal,
                               UnresolvedAttribute, output_name)
from .plan import logical as L
from .plan.overrides import TpuOverrides, plan_cpu, plan_query


class Column:
    """Expression wrapper with pyspark.sql.Column operator surface."""

    def __init__(self, expr: Expression):
        self._expr = expr

    # arithmetic
    def __add__(self, other):
        from .expressions.arithmetic import Add
        return Column(Add(self._expr, _expr(other)))

    def __radd__(self, other):
        from .expressions.arithmetic import Add
        return Column(Add(_expr(other), self._expr))

    def __sub__(self, other):
        from .expressions.arithmetic import Subtract
        return Column(Subtract(self._expr, _expr(other)))

    def __rsub__(self, other):
        from .expressions.arithmetic import Subtract
        return Column(Subtract(_expr(other), self._expr))

    def __mul__(self, other):
        from .expressions.arithmetic import Multiply
        return Column(Multiply(self._expr, _expr(other)))

    def __rmul__(self, other):
        from .expressions.arithmetic import Multiply
        return Column(Multiply(_expr(other), self._expr))

    def __truediv__(self, other):
        from .expressions.arithmetic import Divide
        return Column(Divide(self._expr, _expr(other)))

    def __rtruediv__(self, other):
        from .expressions.arithmetic import Divide
        return Column(Divide(_expr(other), self._expr))

    def __mod__(self, other):
        from .expressions.arithmetic import Remainder
        return Column(Remainder(self._expr, _expr(other)))

    def __neg__(self):
        from .expressions.arithmetic import UnaryMinus
        return Column(UnaryMinus(self._expr))

    # comparisons
    def __eq__(self, other):  # type: ignore[override]
        from .expressions.predicates import EqualTo
        return Column(EqualTo(self._expr, _expr(other)))

    def __ne__(self, other):  # type: ignore[override]
        from .expressions.predicates import EqualTo, Not
        return Column(Not(EqualTo(self._expr, _expr(other))))

    def __lt__(self, other):
        from .expressions.predicates import LessThan
        return Column(LessThan(self._expr, _expr(other)))

    def __le__(self, other):
        from .expressions.predicates import LessThanOrEqual
        return Column(LessThanOrEqual(self._expr, _expr(other)))

    def __gt__(self, other):
        from .expressions.predicates import GreaterThan
        return Column(GreaterThan(self._expr, _expr(other)))

    def __ge__(self, other):
        from .expressions.predicates import GreaterThanOrEqual
        return Column(GreaterThanOrEqual(self._expr, _expr(other)))

    def eqNullSafe(self, other):
        from .expressions.predicates import EqualNullSafe
        return Column(EqualNullSafe(self._expr, _expr(other)))

    # boolean
    def __and__(self, other):
        from .expressions.predicates import And
        return Column(And(self._expr, _expr(other)))

    def __or__(self, other):
        from .expressions.predicates import Or
        return Column(Or(self._expr, _expr(other)))

    def __invert__(self):
        from .expressions.predicates import Not
        return Column(Not(self._expr))

    # methods
    def alias(self, *names: str) -> "Column":
        from .expressions.generators import Generator, MultiAlias
        if len(names) > 1:
            if not isinstance(self._expr, Generator):
                raise ValueError("multi-name alias requires a generator column")
            return Column(MultiAlias(self._expr, list(names)))
        return Column(Alias(self._expr, names[0]))

    name = alias

    def cast(self, to) -> "Column":
        from .expressions.cast import Cast
        from . import types as T
        if isinstance(to, str):
            to = _type_from_string(to)
        return Column(Cast(self._expr, to))

    def isNull(self) -> "Column":
        from .expressions.nullexprs import IsNull
        return Column(IsNull(self._expr))

    def isNotNull(self) -> "Column":
        from .expressions.nullexprs import IsNotNull
        return Column(IsNotNull(self._expr))

    def isin(self, *values) -> "Column":
        from .expressions.predicates import In
        items = values[0] if len(values) == 1 and isinstance(values[0], (list, tuple)) \
            else values
        return Column(In(self._expr, [_expr(v) for v in items]))

    def like(self, pattern: str) -> "Column":
        from .expressions.regex import Like
        return Column(Like(self._expr, pattern))

    def rlike(self, pattern: str) -> "Column":
        from .expressions.regex import RLike
        return Column(RLike(self._expr, pattern))

    def between(self, lower, upper) -> "Column":
        from .expressions.predicates import And, GreaterThanOrEqual, \
            LessThanOrEqual
        return Column(And(GreaterThanOrEqual(self._expr, _expr(lower)),
                          LessThanOrEqual(self._expr, _expr(upper))))

    def startswith(self, other) -> "Column":
        from .expressions.strings import StartsWith
        return Column(StartsWith(self._expr, _expr(other)))

    def endswith(self, other) -> "Column":
        from .expressions.strings import EndsWith
        return Column(EndsWith(self._expr, _expr(other)))

    def contains(self, other) -> "Column":
        from .expressions.strings import Contains
        return Column(Contains(self._expr, _expr(other)))

    def getItem(self, key) -> "Column":
        """array[i] (0-based), map[key], or struct.field access (reference
        GpuGetArrayItem / GpuGetMapValue / GpuGetStructField)."""
        from .expressions import collections as _CL
        from .types import ArrayType, MapType, StructType
        e = self._expr
        try:
            dt = e.dtype
        except Exception:  # unresolved — assume array; others resolve later
            dt = None
        if isinstance(dt, MapType):
            return Column(_CL.GetMapValue(e, _expr(key)))
        if isinstance(dt, StructType) and isinstance(key, str):
            return Column(_CL.GetStructField(e, key))
        if isinstance(dt, ArrayType) and isinstance(dt.element_type,
                                                    StructType) \
                and isinstance(key, str):
            return Column(_CL.GetArrayStructFields(e, key))
        return Column(_CL.GetArrayItem(e, _expr(key)))

    def getField(self, name: str) -> "Column":
        """struct.field access (pyspark Column.getField)."""
        from .expressions import collections as _CL
        return Column(_CL.GetStructField(self._expr, name))

    def substr(self, start: int, length: int) -> "Column":
        from .expressions.strings import Substring
        return Column(Substring(self._expr, Literal(start), Literal(length)))

    def over(self, spec) -> "Column":
        from .window import WindowExpression
        return Column(WindowExpression(self._expr, spec))

    def asc(self) -> "L.SortOrder":
        return L.SortOrder(self._expr, True)

    def desc(self) -> "L.SortOrder":
        return L.SortOrder(self._expr, False)

    def asc_nulls_last(self) -> "L.SortOrder":
        return L.SortOrder(self._expr, True, nulls_first=False)

    def desc_nulls_first(self) -> "L.SortOrder":
        return L.SortOrder(self._expr, False, nulls_first=True)

    def __repr__(self) -> str:
        return f"Column<{self._expr.pretty()}>"


def _expr(x) -> Expression:
    if isinstance(x, Column):
        return x._expr
    if isinstance(x, Expression):
        return x
    return Literal(x)


def _type_from_string(s: str):
    from . import types as T
    m = {"boolean": T.BooleanT, "byte": T.ByteT, "tinyint": T.ByteT,
         "short": T.ShortT, "smallint": T.ShortT, "int": T.IntegerT,
         "integer": T.IntegerT, "long": T.LongT, "bigint": T.LongT,
         "float": T.FloatT, "double": T.DoubleT, "string": T.StringT,
         "binary": T.BinaryT, "date": T.DateT, "timestamp": T.TimestampT}
    key = s.strip().lower()
    if key in m:
        return m[key]
    if key.startswith("decimal"):
        import re
        mt = re.match(r"decimal\((\d+),\s*(\d+)\)", key)
        if mt:
            return T.DecimalType(int(mt.group(1)), int(mt.group(2)))
        return T.DecimalType(10, 0)
    raise ValueError(f"unknown type string {s!r}")


class DataFrame:
    def __init__(self, plan: L.LogicalPlan, session: "TpuSession"):
        self._plan = plan
        self.session = session

    # --- column access ----------------------------------------------------
    def __getitem__(self, name: str) -> Column:
        return Column(self._plan.resolve_name(name))

    def col(self, name: str) -> Column:
        return self[name]

    @property
    def columns(self) -> List[str]:
        return [a.name for a in self._plan.output]

    @property
    def schema(self):
        return self._plan.schema()

    # --- transformations --------------------------------------------------
    def select(self, *cols) -> "DataFrame":
        exprs = [self._to_named(c) for c in cols]
        if _has_generator(exprs):
            return _project_with_generator(exprs, self)
        if _has_window(exprs):
            return _project_with_windows(exprs, self)
        return DataFrame(L.Project(exprs, self._plan), self.session)

    def _to_named(self, c) -> Expression:
        if isinstance(c, str):
            if c == "*":
                raise ValueError("use select('*') via df.select(*df.columns)")
            return UnresolvedAttribute(c)
        return _expr(c)

    def selectExpr(self, *exprs):  # minimal: attribute names only for now
        return self.select(*exprs)

    def filter(self, condition) -> "DataFrame":
        return DataFrame(L.Filter(_expr(condition), self._plan), self.session)

    where = filter

    def withColumn(self, name: str, col) -> "DataFrame":
        exprs: List[Expression] = []
        replaced = False
        for a in self._plan.output:
            if a.name == name:
                exprs.append(Alias(_expr(col), name))
                replaced = True
            else:
                exprs.append(a)
        if not replaced:
            exprs.append(Alias(_expr(col), name))
        if _has_generator(exprs):
            return _project_with_generator(exprs, self)
        if _has_window(exprs):
            return _project_with_windows(exprs, self)
        return DataFrame(L.Project(exprs, self._plan), self.session)

    def withColumnRenamed(self, old: str, new: str) -> "DataFrame":
        exprs = [Alias(a, new) if a.name == old else a for a in self._plan.output]
        return DataFrame(L.Project(exprs, self._plan), self.session)

    def drop(self, *names: str) -> "DataFrame":
        keep = [a for a in self._plan.output if a.name not in names]
        return DataFrame(L.Project(keep, self._plan), self.session)

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(L.Limit(n, self._plan), self.session)

    def distinct(self) -> "DataFrame":
        """SELECT DISTINCT — lowered to a keys-only hash aggregate (Spark
        ReplaceDeduplicateWithAggregate; reference GpuHashAggregateExec)."""
        keys = list(self._plan.output)
        return DataFrame(L.Aggregate(keys, [], self._plan), self.session)

    def dropDuplicates(self, subset: Optional[List[str]] = None) -> "DataFrame":
        """Deduplicate on `subset` (default: all columns), keeping the first
        row per key (Spark Dataset.dropDuplicates via first() aggregates)."""
        if not subset:
            return self.distinct()
        from .expressions.aggregates import First
        from .expressions.base import Alias
        keys = [self._plan.resolve_name(c) for c in subset]
        key_ids = {k.expr_id for k in keys}
        rest = [a for a in self._plan.output if a.expr_id not in key_ids]
        aggs = [Alias(First(a, ignore_nulls=False), a.name) for a in rest]
        node = L.Aggregate(keys, aggs, self._plan)
        # restore original column order by expr id (names may be duplicated
        # in join outputs, so a name-based select would be ambiguous)
        node_out = node.output
        by_orig = {}
        for out_attr, orig in zip(node_out[:len(keys)], keys):
            by_orig[orig.expr_id] = out_attr
        for out_attr, orig in zip(node_out[len(keys):], rest):
            by_orig[orig.expr_id] = out_attr
        ordered = [by_orig[a.expr_id] for a in self._plan.output]
        return DataFrame(L.Project(ordered, node), self.session)

    def sample(self, withReplacement=None, fraction=None, seed=None
               ) -> "DataFrame":
        """pyspark-style sample: sample(fraction), sample(fraction, seed),
        sample(withReplacement, fraction[, seed])."""
        if not isinstance(withReplacement, bool) and withReplacement is not None:
            # positional sample(fraction[, seed]) form
            withReplacement, fraction, seed = False, withReplacement, fraction
        if fraction is None:
            raise ValueError("sample() requires a fraction")
        return DataFrame(L.Sample(self._plan, fraction,
                                  bool(withReplacement), seed), self.session)

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(L.Union([self._plan, other._plan]), self.session)

    unionAll = union

    def _set_op(self, other: "DataFrame", keep_right: bool) -> "DataFrame":
        """INTERSECT / EXCEPT (distinct set semantics). Where Spark rewrites
        to null-aware semi/anti joins (ReplaceIntersectWithSemiJoin), the
        TPU lowering rides the aggregate engine instead: union both sides
        tagged, GROUP BY every column (grouping already treats NULL keys as
        equal — exactly the null-safe equality set ops need), then filter on
        which sides contributed. One shuffle, no join, device-typed
        throughout (joins here can't hash null string keys as equal)."""
        from .expressions.aggregates import Max
        from .expressions.base import Alias
        if len(self._plan.output) != len(other._plan.output):
            raise ValueError("set op requires equal column counts")
        names = [a.name for a in self._plan.output]
        Fn = _functions()
        tag = lambda df, l, r: df.select(  # noqa: E731
            *[Column(a).alias(n) for a, n in zip(df._plan.output, names)],
            Fn.lit(l).alias("__setop_l"), Fn.lit(r).alias("__setop_r"))
        u = tag(self, 1, 0).union(tag(other, 0, 1))
        keys = list(u._plan.output[:len(names)])
        aggs = [Alias(Max(u._plan.output[len(names)]), "__l"),
                Alias(Max(u._plan.output[len(names) + 1]), "__r")]
        g = DataFrame(L.Aggregate(keys, aggs, u._plan), self.session)
        cond = (Fn.col("__l") == 1) & ((Fn.col("__r") == 1) if keep_right
                                       else (Fn.col("__r") == 0))
        return g.filter(cond).select(*names)

    def intersect(self, other: "DataFrame") -> "DataFrame":
        return self._set_op(other, keep_right=True)

    def exceptDistinct(self, other: "DataFrame") -> "DataFrame":
        """EXCEPT DISTINCT — pyspark exposes this as `subtract`. (pyspark's
        `exceptAll` is duplicate-PRESERVING and is deliberately not aliased
        to this; it is not implemented.)"""
        return self._set_op(other, keep_right=False)

    subtract = exceptDistinct

    def sort(self, *cols, ascending: Union[bool, List[bool], None] = None) -> "DataFrame":
        order = []
        for i, c in enumerate(cols):
            if isinstance(c, L.SortOrder):
                order.append(c)
            else:
                e = UnresolvedAttribute(c) if isinstance(c, str) else _expr(c)
                asc = ascending[i] if isinstance(ascending, list) else (
                    ascending if ascending is not None else True)
                order.append(L.SortOrder(e, asc))
        return DataFrame(L.Sort(order, True, self._plan), self.session)

    orderBy = sort

    def sortWithinPartitions(self, *cols) -> "DataFrame":
        order = [c if isinstance(c, L.SortOrder)
                 else L.SortOrder(UnresolvedAttribute(c) if isinstance(c, str) else _expr(c), True)
                 for c in cols]
        return DataFrame(L.Sort(order, False, self._plan), self.session)

    def repartition(self, num: int, *cols) -> "DataFrame":
        if cols:
            keys = [UnresolvedAttribute(c) if isinstance(c, str) else _expr(c)
                    for c in cols]
            node = L.Repartition(self._plan, num, "hash", keys)
        else:
            node = L.Repartition(self._plan, num, "roundrobin")
        return DataFrame(node, self.session)

    def coalesce(self, num: int) -> "DataFrame":
        return DataFrame(L.Repartition(self._plan, num, "coalesce"), self.session)

    def groupBy(self, *cols) -> "GroupedData":
        keys = [UnresolvedAttribute(c) if isinstance(c, str) else _expr(c)
                for c in cols]
        return GroupedData(self, keys)

    groupby = groupBy

    def rollup(self, *cols) -> "GroupedData":
        """GROUP BY ROLLUP: grouping sets (all), (all-1), ..., () (Spark
        Dataset.rollup; lowered via Expand — reference GpuExpandExec)."""
        keys = [UnresolvedAttribute(c) if isinstance(c, str) else _expr(c)
                for c in cols]
        sets = [list(range(i)) for i in range(len(keys), -1, -1)]
        return GroupedData(self, keys, grouping_sets=sets)

    def cube(self, *cols) -> "GroupedData":
        """GROUP BY CUBE: all 2^n grouping sets."""
        keys = [UnresolvedAttribute(c) if isinstance(c, str) else _expr(c)
                for c in cols]
        n = len(keys)
        sets = [[i for i in range(n) if (mask >> i) & 1 == 0]
                for mask in range(1 << n)]
        sets.sort(key=lambda s: (len(s) * -1, s))
        return GroupedData(self, keys, grouping_sets=sets)

    def groupingSets(self, sets, *cols) -> "GroupedData":
        """Explicit GROUPING SETS: `sets` is a list of lists of column names
        (each a subset of `cols`)."""
        keys = [UnresolvedAttribute(c) if isinstance(c, str) else _expr(c)
                for c in cols]
        names = [c if isinstance(c, str) else None for c in cols]
        idx_sets = []
        for s in sets:
            idxs = []
            for item in s:
                if isinstance(item, int):
                    idxs.append(item)
                else:
                    idxs.append(names.index(item))
            idx_sets.append(idxs)
        return GroupedData(self, keys, grouping_sets=idx_sets)

    def agg(self, *aggs) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs)

    def join(self, other: "DataFrame", on=None, how: str = "inner") -> "DataFrame":
        left, right = self._plan, other._plan
        if on is None:
            raise ValueError("join requires `on`")
        if isinstance(on, str):
            on = [on]
        if isinstance(on, (list, tuple)) and on and isinstance(on[0], str):
            lk0 = [left.resolve_name(c) for c in on]
            rk0 = [right.resolve_name(c) for c in on]
            lk, rk = _coerce_join_keys(lk0, rk0)
            node = L.Join(left, right, how, lk, rk)
            df = DataFrame(node, self.session)
            # pyspark drops the duplicate USING columns from the right side
            # (dedup against the raw attrs — coercion may wrap rk in Casts)
            if node.join_type not in ("leftsemi", "semi", "leftanti", "anti"):
                keep = [a for a in node.output
                        if not any(a.expr_id == r.expr_id for r in rk0)]
                return DataFrame(L.Project(keep, node), self.session)
            return df
        # join on a Column condition: extract equi-keys when possible
        cond = _expr(on)
        lk, rk, residual = _extract_equi_keys(cond, left, right)
        lk, rk = _coerce_join_keys(lk, rk)
        node = L.Join(left, right, how, lk, rk, residual)
        return DataFrame(node, self.session)

    def crossJoin(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(L.Join(self._plan, other._plan, "cross"), self.session)

    @property
    def write(self):
        from .io.writer import DataFrameWriter
        return DataFrameWriter(self)

    def cache(self) -> "DataFrame":
        """Materialize once and replace the plan with the cached result
        (reference ParquetCachedBatchSerializer: df.cache() stores compressed
        parquet-encoded batches on host). Host storage is Arrow here; the
        compressed-at-rest variant is the cache serializer in io/cache.py."""
        from .io.cache import CachedRelation
        table = self.to_arrow()
        return DataFrame(CachedRelation(table), self.session)

    persist = cache

    def device_cache(self) -> "DataFrame":
        """Materialize once into device-resident batches (HBM) and replace
        the plan with a device scan — repeated queries skip the host→device
        upload entirely (reference GpuInMemoryTableScanExec over the cached
        batch serializer). Column objects are stable across runs, so
        per-column memoized statistics (group-by dictionaries, key ranges)
        and the compiled-stage program cache stay warm."""
        from .io.cache import DeviceCachedRelation, shard_over_chips
        from .parallel.mesh import session_chips
        conf = self.session._rapids_conf()
        chips = session_chips(conf)
        if chips is None:
            batches = self.to_device_batches()
        else:
            # mesh session: a quarter of the rows on each chip
            # (docs/distributed.md "Placement and the task model")
            from .config import BATCH_SIZE_ROWS
            batches = shard_over_chips(self.to_arrow(), chips,
                                       int(conf.get(BATCH_SIZE_ROWS)))
        return DataFrame(DeviceCachedRelation(batches, self._plan.output),
                         self.session)

    # --- actions ----------------------------------------------------------
    def to_arrow(self, timeout: Optional[float] = None,
                 priority: Optional[str] = None):
        return self.session._execute(self._plan, timeout=timeout,
                                     priority=priority)

    toArrow = to_arrow

    def collect(self, timeout: Optional[float] = None,
                priority: Optional[str] = None):
        """Execute and fetch all rows. `timeout` (seconds) sets a deadline
        for THIS query (overriding spark.rapids.tpu.query.timeoutMs): past
        it the query is cancelled at the next cooperative checkpoint and
        raises QueryDeadlineExceeded with every resource released
        (docs/robustness.md "Query lifecycle"). `priority` overrides the
        session's SLO class (spark.rapids.tpu.query.priority) for this
        call. Under sustained overload the scheduler may SHED the query —
        the return value is then a typed ``QueryShed`` result carrying a
        retry-after hint instead of the row list (docs/serving.md)."""
        out = self.to_arrow(timeout=timeout, priority=priority)
        from .serving.query_context import QueryShed
        if isinstance(out, QueryShed):
            return out
        return out.to_pylist()

    def toPandas(self):
        return self.to_arrow().to_pandas()

    def to_device_batches(self) -> List:
        """ML interop (reference ColumnarRdd, README.md:47-56: zero-copy
        handoff of the internal Table RDD to XGBoost etc.): execute the plan
        and hand back the device-resident TpuColumnarBatch per partition —
        columns are jax Arrays usable directly in a jax ML pipeline, no
        host round trip for device-resident stages."""
        from .execs.base import TaskContext
        from .execs.transitions import DeviceToHostExec
        from .columnar.batch import TpuColumnarBatch
        if self.session._stopped:
            # same contract as _execute: a stopped session must not
            # silently resurrect the shared shuffle manager (the ML
            # interop path materializes exchanges too)
            raise RuntimeError(
                f"TpuSession {self.session._session_id} is stopped")
        conf = self.session._rapids_conf()
        final, _, _ = plan_query(self._plan, conf)
        # strip the final device→host transition: the caller wants device data
        while isinstance(final, DeviceToHostExec):
            final = final.children[0]
        out: List = []
        # mesh session: the caller gets one device's arrays (it may
        # concatenate them), so each partition is computed on its chip and
        # handed over on the mesh's first (`TpuExec._placed`)
        from .parallel.mesh import on_chip, session_chips
        chips = session_chips(conf)
        try:
            with on_chip(chips[0] if chips else None):
                for p in range(final.num_partitions()):
                    ctx = TaskContext(p, conf)
                    try:
                        for b in final.execute_partition(p, ctx):
                            if isinstance(b, TpuColumnarBatch):
                                out.append(b)
                            else:  # CPU-resident plan: upload (reference
                                # InternalColumnarRddConverter host→device
                                # path)
                                out.append(TpuColumnarBatch.from_arrow(b))
                    finally:
                        ctx.complete()
        finally:
            # same end-of-query shuffle release as _execute; the returned
            # batches keep their arrays alive independently of the catalog
            for node in final.collect_nodes():
                if hasattr(node, "cleanup_shuffle"):
                    node.cleanup_shuffle(conf)
        return out

    def to_device_arrays(self) -> dict:
        """Column-name → jax Array of the whole result (single concatenated
        batch) — the convenient form for feeding jax/flax training steps.
        Nullable columns come back zero-filled at null positions with a
        companion boolean mask under ``<name>__valid`` (a raw device buffer
        cannot express SQL nulls; training on unmasked lanes would be
        silent garbage)."""
        import jax.numpy as jnp
        from .columnar.batch import concat_batches
        batches = self.to_device_batches()
        if not batches:
            out = {}
            for a in self._plan.output:
                npdt = getattr(a.dtype, "np_dtype", None)
                if npdt is not None:
                    out[a.name] = jnp.zeros((0,), npdt)
                else:
                    import pyarrow as pa
                    from .types import to_arrow as t2a
                    out[a.name] = pa.array([], type=t2a(a.dtype))
            return out
        whole = batches[0] if len(batches) == 1 else concat_batches(batches)
        names = [a.name for a in self._plan.output]
        out = {}
        for name, col in zip(names, whole.columns):
            data = col.data
            if data is not None and col.offsets is None \
                    and col.host_data is None:
                n = whole.num_rows
                if col.validity is not None:
                    v = col.validity[:n]
                    out[name] = jnp.where(v, data[:n],
                                          jnp.zeros((), data.dtype))
                    out[f"{name}__valid"] = v
                else:
                    out[name] = data[:n]
            else:  # strings/nested stay host-side
                out[name] = col.to_arrow()
        return out

    def count(self) -> int:
        return self.to_arrow().num_rows

    def show(self, n: int = 20) -> None:
        print(self.limit(n).to_arrow().to_pandas().to_string())

    def explain(self, mode: str = "formatted") -> str:
        if str(mode) == "metrics":
            # the executed-plan annotation lives on the session (it renders
            # the LAST collected query's snapshots — run a collect() first)
            return self.session.explain("metrics")
        conf = self.session._rapids_conf()
        from .config import PLAN_CACHE_ENABLED
        from .plan.optimizer import explain_logical
        from .serving.plan_cache import fingerprint
        from .serving.scheduler import QueryScheduler
        status = "off"
        if conf.get(PLAN_CACHE_ENABLED):
            fp = fingerprint(self._plan, conf)
            if fp is None:
                status = "uncacheable"
            else:
                inst = QueryScheduler.peek()
                status = ("hit" if inst is not None
                          and inst.plan_cache.peek(fp.key) else "miss")
        final, optimized, rules = plan_query(self._plan, conf)
        lines = [f"planCache={status}"]
        if rules:
            lines.append(f"appliedRules={', '.join(rules)}")
            lines.append("== Optimized Logical Plan ==")
            lines.append(explain_logical(optimized))
            lines.append("== Physical Plan ==")
        lines.append(final.tree_string())
        s = "\n".join(lines)
        print(s)
        return s

    def explain_fallback(self) -> str:
        """reference ExplainPlan: report what would not run on TPU."""
        conf = self.session._rapids_conf()
        cpu_plan, _, _ = plan_cpu(self._plan, conf)
        return TpuOverrides.explain_plan(cpu_plan, conf)


def _has_generator(exprs) -> bool:
    from .expressions.generators import Generator
    return any(e.collect(lambda x: isinstance(x, Generator)) for e in exprs)


def _project_with_generator(exprs, df: "DataFrame") -> "DataFrame":
    """Extract the (single) generator into a Generate node, then project the
    selected columns with the generator replaced by its output attributes
    (Spark's ExtractGenerator rule; reference GpuGenerateExec)."""
    from .expressions.generators import Generator, MultiAlias
    gens = []
    for e in exprs:
        for g in e.collect(lambda x: isinstance(x, Generator)):
            if not any(g is x for x in gens):
                gens.append(g)
    if len(gens) != 1:
        raise ValueError("only one generator allowed per select clause")
    gen = gens[0]
    # names: from Alias / MultiAlias wrapper if present
    gen_names = None
    for e in exprs:
        if isinstance(e, MultiAlias) and e.child is gen:
            gen_names = e.names
        elif isinstance(e, Alias) and e.child is gen:
            n_out = len(gen.element_schema()) if all(
                c.resolved for c in gen.children) else 1
            if n_out != 1:
                raise ValueError(
                    f"generator produces {n_out} columns; use "
                    f".alias({', '.join(repr(f'n{i}') for i in range(n_out))})")
            gen_names = [e.name]
    # resolve generator children against the child plan first so names work
    node = L.Generate(gen, df._plan, gen_names)
    attrs = node.generator_output

    new_exprs: List[Expression] = []
    for e in exprs:
        if (isinstance(e, (Alias, MultiAlias)) and e.child is gen) or e is gen:
            new_exprs.extend(attrs)
        elif e.collect(lambda x: isinstance(x, Generator)):
            raise ValueError(
                f"generators are not supported when nested in expressions: "
                f"{e.pretty()}")
        else:
            new_exprs.append(e)
    return DataFrame(L.Project(new_exprs, node), df.session)


def _has_window(exprs) -> bool:
    from .window import WindowExpression
    return any(e.collect(lambda x: isinstance(x, WindowExpression))
               for e in exprs)


def _project_with_windows(exprs, df: "DataFrame") -> "DataFrame":
    """Extract WindowExpressions into a WindowOp node, replace their occurrences
    with references to the window output columns, then project
    (Spark's ExtractWindowExpressions rule)."""
    from .window import WindowExpression
    windows: List = []
    for e in exprs:
        for w in e.collect(lambda x: isinstance(x, WindowExpression)):
            if not any(w is x for x in windows):
                windows.append(w)
    node = L.WindowOp(windows, df._plan)
    attrs = node.window_attrs

    def replace(e: Expression) -> Expression:
        def rule(x: Expression):
            for i, w in enumerate(windows):
                if x is w:
                    return attrs[i]
            return None
        return e.transform(rule)

    new_exprs = [replace(e) for e in exprs]
    return DataFrame(L.Project(new_exprs, node), df.session)


def _coerce_join_keys(lk: List[Expression], rk: List[Expression]):
    """Widen mismatched equi-join key types to a common type (Spark's
    analyzer findWiderTypeForTwo). Without this, the two co-partitioned
    exchange sides hash DIFFERENT byte widths (murmur3 hashes int32 and
    int64 differently, by Spark spec) and silently route matching keys to
    different partitions — an int32 FK ⋈ int64 PK join then drops ~(1-1/N)
    of its matches."""
    from .expressions.cast import Cast
    from .types import (ByteType, DecimalType, DoubleT, DoubleType,
                        FloatType, IntegerType, LongType, ShortType)
    order = {ByteType: 0, ShortType: 1, IntegerType: 2, LongType: 3,
             FloatType: 4, DoubleType: 5}
    out_l, out_r = [], []
    for a, b in zip(lk, rk):
        try:
            ta, tb = a.dtype, b.dtype
        except ValueError:
            # unresolved keys (MERGE builds joins pre-resolution): types are
            # unified later by the resolver; pass through untouched
            out_l.append(a)
            out_r.append(b)
            continue
        if isinstance(ta, DecimalType) or isinstance(tb, DecimalType):
            # decimal keys: only exact precision/scale matches hash alike
            if repr(ta) != repr(tb):
                raise ValueError(
                    f"join key type mismatch {ta} vs {tb}: cast one side "
                    "explicitly (silently hashing different decimal layouts "
                    "would mis-route rows across partitions)")
            out_l.append(a)
            out_r.append(b)
            continue
        if type(ta) is type(tb):
            out_l.append(a)
            out_r.append(b)
            continue
        ra, rb = order.get(type(ta)), order.get(type(tb))
        if ra is None or rb is None:
            # no known widening: equality would need engine-specific
            # casts AND the two sides would hash different layouts — fail
            # loudly (Spark's analyzer would insert a cast or reject too)
            raise ValueError(
                f"join key type mismatch {ta} vs {tb}: cast one side "
                "explicitly")
        if (ra <= 3) != (rb <= 3):
            common = DoubleT  # integral vs fractional → double
        else:
            common = ta if ra >= rb else tb
        out_l.append(a if type(ta) is type(common) else Cast(a, common))
        out_r.append(b if type(tb) is type(common) else Cast(b, common))
    return out_l, out_r


def _functions():
    from . import functions as F
    return F


def _extract_equi_keys(cond: Expression, left, right):
    """Split an AND-tree of EqualTo(left_attr, right_attr) into key lists +
    residual condition (reference GpuHashJoin key extraction)."""
    from .expressions.predicates import And, EqualTo
    left_ids = {a.expr_id for a in left.output}
    right_ids = {a.expr_id for a in right.output}
    conjuncts: List[Expression] = []

    def flatten(e):
        if isinstance(e, And):
            flatten(e.children[0])
            flatten(e.children[1])
        else:
            conjuncts.append(e)

    flatten(cond)
    lk, rk, residual = [], [], []
    for c in conjuncts:
        if isinstance(c, EqualTo):
            a, b = c.children
            ids_a = {x.expr_id for x in a.collect(lambda e: isinstance(e, AttributeReference))}
            ids_b = {x.expr_id for x in b.collect(lambda e: isinstance(e, AttributeReference))}
            if ids_a <= left_ids and ids_b <= right_ids:
                lk.append(a)
                rk.append(b)
                continue
            if ids_a <= right_ids and ids_b <= left_ids:
                lk.append(b)
                rk.append(a)
                continue
        residual.append(c)
    res = None
    if residual:
        from .expressions.predicates import And as _And
        res = residual[0]
        for c in residual[1:]:
            res = _And(res, c)
    return lk, rk, res


class GroupedData:
    def __init__(self, df: DataFrame, keys: List[Expression],
                 grouping_sets: Optional[List[List[int]]] = None):
        self._df = df
        self._keys = keys
        self._grouping_sets = grouping_sets

    def agg(self, *aggs) -> DataFrame:
        exprs = [_expr(a) for a in aggs]
        if self._grouping_sets is not None:
            return self._agg_grouping_sets(exprs)
        node = L.Aggregate(self._keys, exprs, self._df._plan)
        return DataFrame(node, self._df.session)

    def _agg_grouping_sets(self, agg_exprs: List[Expression]) -> DataFrame:
        """Lower grouping sets to Expand + Aggregate + Project (Spark's
        ResolveGroupingAnalytics; reference GpuExpandExec.scala). The Expand
        output keeps all child columns (aggregates see real values — Spark
        semantics), adds one nulled-or-real column per grouping expr (renamed
        _gset_i to avoid ambiguity) plus the _gid bitmask, all of which become
        the hash-agg keys."""
        from .expressions.base import Literal
        from .expressions.generators import GroupingExpr, GroupingID
        from .types import LongT
        child = self._df._plan
        keys = [L.resolve_expression(k, child) for k in self._keys]
        n = len(keys)
        gset_attrs = [AttributeReference(f"_gset_{i}", k.dtype, True)
                      for i, k in enumerate(keys)]
        gid_attr = AttributeReference("_gid", LongT, False)
        out_attrs = list(child.output) + gset_attrs + [gid_attr]
        projections: List[List[Expression]] = []
        for s in self._grouping_sets:
            included = set(s)
            # Spark gid: bit (n-1-i) set when grouping expr i is NOT in the set
            gid = 0
            proj: List[Expression] = list(child.output)
            for i, k in enumerate(keys):
                if i in included:
                    proj.append(k)
                else:
                    proj.append(Literal(None, k.dtype))
                    gid |= 1 << (n - 1 - i)
            proj.append(Literal(gid, LongT))
            projections.append(proj)
        expand = L.Expand(projections, out_attrs, child, resolve=False)

        def lower_markers(e: Expression) -> Expression:
            def rule(x: Expression):
                from .expressions import arithmetic as A_
                if isinstance(x, GroupingID):
                    return gid_attr
                if isinstance(x, GroupingExpr):
                    inner = L.resolve_expression(x.child, child)
                    for i, k in enumerate(keys):
                        if (isinstance(inner, AttributeReference)
                                and isinstance(k, AttributeReference)
                                and inner.expr_id == k.expr_id):
                            from .expressions.bitwise import ShiftRight, BitwiseAnd
                            from .expressions.cast import Cast as _Cast
                            from .types import ByteT
                            return _Cast(BitwiseAnd(
                                ShiftRight(gid_attr, Literal(n - 1 - i)),
                                Literal(1, LongT)), ByteT)
                    raise ValueError(
                        f"grouping() argument {inner.pretty()} is not a grouping column")
                return None
            return e.transform(rule)

        lowered = []
        for e in agg_exprs:
            low = lower_markers(e)
            # preserve the user-visible name when the marker was not aliased
            # (Spark names these "grouping_id()"/"grouping(k)")
            if low is not e and not isinstance(e, Alias):
                low = Alias(low, L.resolve_expression(e, child).pretty())
            lowered.append(low)
        agg_exprs = lowered
        grouping = list(gset_attrs) + [gid_attr]
        node = L.Aggregate(grouping, agg_exprs, expand)
        # final projection: grouping cols under their original names + aggs,
        # dropping the internal _gid
        out_exprs: List[Expression] = []
        for i, k in enumerate(keys):
            out_exprs.append(Alias(node.output[i], output_name(k)))
        for j in range(len(agg_exprs)):
            out_exprs.append(node.output[n + 1 + j])
        return DataFrame(L.Project(out_exprs, node), self._df.session)

    def count(self) -> DataFrame:
        from .expressions.aggregates import Count
        from .expressions.base import Alias, Literal
        return self.agg(Column(Alias(Count(Literal(1)), "count")))

    def sum(self, *names: str) -> DataFrame:
        from .expressions.aggregates import Sum
        return self.agg(*[Column(Alias(Sum(UnresolvedAttribute(n)), f"sum({n})"))
                          for n in names])

    def avg(self, *names: str) -> DataFrame:
        from .expressions.aggregates import Average
        return self.agg(*[Column(Alias(Average(UnresolvedAttribute(n)), f"avg({n})"))
                          for n in names])

    mean = avg

    def min(self, *names: str) -> DataFrame:
        from .expressions.aggregates import Min
        return self.agg(*[Column(Alias(Min(UnresolvedAttribute(n)), f"min({n})"))
                          for n in names])

    def max(self, *names: str) -> DataFrame:
        from .expressions.aggregates import Max
        return self.agg(*[Column(Alias(Max(UnresolvedAttribute(n)), f"max({n})"))
                          for n in names])


class TpuSessionBuilder:
    def __init__(self):
        self._conf: Dict[str, str] = {}

    def config(self, key: str, value: Any) -> "TpuSessionBuilder":
        self._conf[key] = str(value)
        return self

    def appName(self, name: str) -> "TpuSessionBuilder":
        self._conf["spark.app.name"] = name
        return self

    def master(self, m: str) -> "TpuSessionBuilder":
        return self

    def getOrCreate(self) -> "TpuSession":
        return TpuSession(self._conf)


class TpuSession:
    """The SparkSession analogue. `spark.plugins=com.nvidia.spark.SQLPlugin` ≙
    constructing this session: it installs the override rules, device manager,
    and shuffle env (reference Plugin.scala driver/executor init, SURVEY §3.1)."""

    builder = property(lambda self: TpuSessionBuilder())

    #: session-id mint (itertools.count.__next__ is atomic in CPython)
    _session_ids = _itertools.count(1)

    def __init__(self, conf: Optional[Dict[str, str]] = None):
        self._settings: Dict[str, str] = dict(conf or {})
        from .config import LEAK_TRACKING_DEBUG
        from .memory.cleaner import MemoryCleaner
        from .memory.device import TpuDeviceManager
        rc = self._rapids_conf()
        TpuDeviceManager.initialize(rc)
        if rc.get(LEAK_TRACKING_DEBUG):
            MemoryCleaner.get().set_debug(True)
        # chaos harness (docs/robustness.md): arm/disarm the process-wide
        # fault injector from spark.rapids.tpu.test.chaos.* when mentioned
        from .chaos import FaultInjector
        FaultInjector.maybe_configure(rc)
        # observability plane (docs/observability.md): apply the always-on
        # metrics-registry switch and arm the crash flight recorder's
        # postmortem dir / ring size (same arm-once pattern as chaos)
        from .config import OBS_METRICS_ENABLED
        from .obs import flight as _flight
        from .obs import mesh_profile as _mesh_profile
        from .obs import metrics as _obs_metrics
        _obs_metrics.set_enabled(rc.get(OBS_METRICS_ENABLED))
        _flight.maybe_configure(rc)
        # mesh efficiency profiler: collective watchdog thresholds +
        # straggler factor (docs/observability.md "Mesh profiling")
        _mesh_profile.maybe_configure(rc)
        self._pool: Optional[_fut.ThreadPoolExecutor] = None
        # query lifecycle (docs/robustness.md): this session is one
        # frontend of the process-wide scheduler — queries submit under
        # its id (session.cancel()/stop() target exactly its queries),
        # and the LAST frontend to stop() releases shared state
        from .serving import scheduler as _sched
        # itertools.count: concurrent constructors must not mint duplicate
        # ids — a shared id would merge two tenants' admission queues and
        # make one session's cancel()/stop() drain the other's queries
        self._session_id = f"sess-{next(TpuSession._session_ids)}"
        self._stopped = False
        _sched.register_session(self)
        _sched.QueryScheduler.get(rc)

    # conf API
    class _Conf:
        def __init__(self, session: "TpuSession"):
            self._s = session

        def set(self, key: str, value: Any) -> None:
            self._s._settings[key] = str(value)
            _invalidate_cached_plans(key, str(value))

        def get(self, key: str, default: Optional[str] = None) -> Optional[str]:
            return self._s._settings.get(key, default)

        def unset(self, key: str) -> None:
            self._s._settings.pop(key, None)
            _invalidate_cached_plans(key, None)

    @property
    def conf(self) -> "_Conf":
        return TpuSession._Conf(self)

    def _rapids_conf(self) -> RapidsConf:
        return RapidsConf(self._settings)

    # --- data sources -----------------------------------------------------
    def createDataFrame(self, data, schema=None, num_partitions: int = 1) -> DataFrame:
        import pyarrow as pa
        if isinstance(data, pa.Table):
            table = data
        elif hasattr(data, "to_records") or str(type(data).__module__).startswith("pandas"):
            table = pa.Table.from_pandas(data, preserve_index=False)
        elif isinstance(data, dict):
            table = pa.table(data)
        elif isinstance(data, list) and data and isinstance(data[0], dict):
            table = pa.Table.from_pylist(data)
            # Spark maps python dict VALUES to MapType, not StructType (pyarrow
            # default); re-cast any struct-typed column whose row values were
            # plain dicts of uniform value type
            casts = []
            for i, f in enumerate(table.schema):
                if pa.types.is_struct(f.type) \
                        and any(isinstance(r.get(f.name), dict) for r in data):
                    vt = {ft.type for ft in f.type}
                    if len(vt) == 1:
                        mt = pa.map_(pa.string(), vt.pop())
                        vals = [r.get(f.name) for r in data]
                        casts.append((i, f.name,
                                      pa.array([None if v is None else list(v.items())
                                                for v in vals], type=mt)))
            for i, name, arr in casts:
                table = table.set_column(i, name, arr)
        elif isinstance(data, list) and schema is not None:
            names = schema if isinstance(schema, list) else schema.field_names
            cols = list(zip(*data)) if data else [[] for _ in names]
            table = pa.table({n: list(c) for n, c in zip(names, cols)})
        else:
            raise TypeError(f"cannot create DataFrame from {type(data)}")
        return DataFrame(L.LocalRelation(table, num_partitions), self)

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              numPartitions: int = 1) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(L.Range(start, end, step, numPartitions), self)

    @property
    def read(self):
        from .io.reader import DataFrameReader
        return DataFrameReader(self)

    # --- execution --------------------------------------------------------
    def _execute(self, plan: L.LogicalPlan,
                 timeout: Optional[float] = None,
                 priority: Optional[str] = None):
        """Submit one query through the scheduler/executor service
        (serving/scheduler.py — docs/robustness.md "Query lifecycle"):
        admission control (bounded queue per SLO class, HBM watermark +
        per-tenant quota, per-class round-robin fairness across
        sessions), a per-query cancel token + optional deadline, and the
        per-partition driving loop. The session keeps only query STATE
        (the _last_* snapshots the executor writes back); the
        device-owning loop lives in the service."""
        if self._stopped:
            # a stopped session already released (or ceded) the shared
            # state; executing would silently resurrect the shuffle
            # manager with no owner left to ever shut it down
            raise RuntimeError(
                f"TpuSession {self._session_id} is stopped")
        from .serving.scheduler import execute_plan
        return execute_plan(self, plan, timeout=timeout,
                            priority=priority)

    def last_admit_wait_ms(self) -> Optional[float]:
        """Admission-queue wait of this session's last executed query in
        milliseconds (None before any query, or when the last query was
        rejected/shed while still queued). The benchmark's tenant cell reads
        this per query; the process-wide distribution is the
        sched.class_admit_wait_ms histogram."""
        return getattr(self, "_last_admit_wait_ms", None)

    def last_query_phases(self):
        """The always-on phase summary of this session's last executed
        query (docs/observability.md "Span model"): its clocks
        (``t_begin_ns``/``t_end_ns`` on ``time.perf_counter_ns()``,
        ``t_begin_unix_ns`` on the realtime clock a device trace can be
        laid over), wall and CPU time of the query thread, XLA compiles,
        and ``phases``: per layer boundary ``{count, wall_ns, cpu_ns,
        child_wall_ns, cat}`` — self time is wall - child, wall - cpu the
        time the thread was off the CPU. Needs no tracing. None before
        any query."""
        return getattr(self, "_last_query_phases", None)

    def last_query_metrics(self, level: Optional[str] = None):
        """Per-operator metrics of the last executed query (the reference
        surfaces these as SQLMetrics in the Spark SQL UI)."""
        from .config import METRICS_LEVEL
        snap = getattr(self, "_last_metrics_snapshot", None)
        if snap is None:
            return {}
        lvl = str(level or self._rapids_conf().get(METRICS_LEVEL)).upper()
        from .profiling import metric_level_filter
        return metric_level_filter(snap, lvl)

    def last_task_metrics(self):
        """Task-accumulator deltas for the last query alone (reference
        GpuTaskMetrics shown per SQL execution): semaphore wait, retry
        counts/time, spill bytes, read-spill time."""
        return dict(getattr(self, "_last_task_metrics", {}))

    def last_sync_ledger(self):
        """Per-operator blocking device→host transfer counts for the last
        query alone ({operator: {kind: count}}; docs/configs.md "Dispatch &
        sync accounting"). Healthy general-path plans show counts bounded
        by O(exchanges); a per-(operator×batch) `rows` count is the
        regression signature the ledger exists to catch."""
        return {op: dict(kinds)
                for op, kinds in getattr(self, "_last_sync_ledger",
                                         {}).items()}

    def last_query_profile(self):
        """The diagnostics bundle of the last TRACED query
        (spark.rapids.tpu.trace.enabled; docs/observability.md "Bundle
        schema"): span tree, per-operator dispatch+sync counts reconciled
        against calls_by_kind and the sync ledger, chaos/retry event
        correlation, and — when spark.rapids.tpu.trace.dir is set — the
        paths of the written Chrome trace and bundle JSON under
        ['artifacts']. None when the last query ran untraced."""
        return getattr(self, "_last_query_profile", None)

    def metrics_snapshot(self):
        """The always-on process-wide metrics registry readout
        (docs/observability.md "Metrics registry"): counters, gauges and
        log2-bucket histograms — query latency p50/p95/p99 and rows/s per
        session, active queries, HBM high-water/pressure, spill bytes,
        retry and chaos counts — plus the engine's other process-wide
        counters folded in at read time (opjit cache stats incl. hit
        rate, mesh collective_stats, SyncLedger totals, task metrics,
        shuffle bytes, HBM state). Same payload as
        ``python -m tools.obs_report``. Needs no tracing."""
        from .obs import metrics as _metrics
        return _metrics.full_snapshot()

    def explain(self, mode: str = "metrics", level: Optional[str] = None
                ) -> str:
        """session-level explain over the LAST EXECUTED query. Mode
        "metrics" (the Spark SQL UI plan-graph analogue, reference GpuExec
        SQLMetrics): the executed physical plan annotated per node with its
        actual metric values, opjit dispatch counts (hits/misses) and
        blocking-sync counts. Works with tracing off — the inputs are the
        session's always-captured per-query snapshots."""
        if str(mode) != "metrics":
            raise ValueError(
                f"TpuSession.explain supports mode='metrics'; for plan "
                f"shape use DataFrame.explain() (got {mode!r})")
        from .config import METRICS_LEVEL
        from .obs import render_explain_metrics
        lvl = str(level or self._rapids_conf().get(METRICS_LEVEL))
        s = render_explain_metrics(
            getattr(self, "_last_plan_tree", []),
            getattr(self, "_last_metrics_snapshot", {}) or {},
            self.last_sync_ledger(), level=lvl)
        print(s)
        return s

    def profiler(self):
        """Context manager capturing an xprof trace of the enclosed queries
        (reference ProfilerOnExecutor; requires
        spark.rapids.profile.pathPrefix)."""
        from .config import PROFILE_PATH_PREFIX
        from .profiling import TpuProfiler
        prefix = self._rapids_conf().get(PROFILE_PATH_PREFIX)
        if not prefix or prefix == "None":
            raise ValueError("set spark.rapids.profile.pathPrefix to profile")
        return TpuProfiler(prefix)

    def cancel(self) -> int:
        """Cancel this session's in-flight (queued or running) queries:
        each observes its cancel token at the next cooperative checkpoint
        and unwinds through the audited release paths — permits, HBM,
        spill files and its tracer return to baseline. Returns how many
        queries were flagged (docs/robustness.md "Query lifecycle")."""
        from .serving.scheduler import QueryScheduler
        return QueryScheduler.get().cancel_session(self._session_id)

    def stop(self) -> None:
        """Shut this session frontend down (idempotent): cancel + drain
        its in-flight queries, shut down its thread pool, drop the
        per-query snapshot state (which can pin plan trees), and — when
        this was the LAST live session with nothing running anywhere —
        release the process-wide shuffle manager (pools + block store,
        the TpuShuffleManager.shutdown() contract)."""
        if self._stopped:
            return
        self._stopped = True
        from .obs import flight as _flight
        from .serving import scheduler as _sched
        sched = _sched.QueryScheduler.get()
        n = sched.cancel_session(self._session_id, reason="session.stop")
        drained = sched.drain_session(self._session_id, timeout_s=30.0)
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        # release tracer/flight-adjacent bindings: the snapshot state the
        # executor parked on this session (bundles reference plan trees
        # and, through them, device buffers)
        for attr in ("_last_query_profile", "_last_plan_tree",
                     "_last_metrics_snapshot", "_last_sync_ledger",
                     "_last_task_metrics", "_last_mesh_profiles",
                     "_last_mesh_fallbacks", "_last_plan_cache",
                     "_last_opt_rules", "_last_query_phases"):
            if hasattr(self, attr):
                setattr(self, attr, None)
        _flight.note("session.stop", session=self._session_id,
                     cancelled=n, drained=drained)
        _sched.release_session(self)
        if not _sched.other_live_sessions(self):
            # last frontend gone: the shuffle manager's pools/block store
            # have no remaining owner (a later session lazily recreates
            # the singleton). Released now when the device pool is idle;
            # if a straggler query outlived the drain timeout, the
            # release stays PENDING and fires when that query ends
            # (scheduler.maybe_release_shared in execute_plan's finally).
            _sched.request_shared_release()

    # with-style lifetime (TL020 owner-class rule: a class parking
    # resources on self exposes __exit__/stop)
    def __enter__(self) -> "TpuSession":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.stop()


def _invalidate_cached_plans(key: str, value: Optional[str]) -> None:
    """Conf-change invalidation hook for the scheduler-owned plan cache: a
    plan-relevant key changing drops entries planned under another value
    (session.conf.set/unset; no-op before the scheduler exists)."""
    from .serving.scheduler import QueryScheduler
    inst = QueryScheduler.peek()
    if inst is not None:
        inst.plan_cache.invalidate_conf(key, value)


def get_session(**conf) -> TpuSession:
    return TpuSession({k.replace("__", "."): str(v) for k, v in conf.items()})
