"""Hash-aggregate execs: CPU (arrow group_by oracle) and TPU (sort-based
segmented reduction on device).

Reference: GpuHashAggregateExec (GpuAggregateExec.scala:1711) with the
update/merge decomposition of aggregateFunctions.scala. TPU algorithm choice:
cuDF has a device hash-groupby; on TPU, data-dependent hash tables fight XLA's
static shapes, while sort+segment-reduce maps cleanly onto MXU/VPU-friendly
primitives (argsort, segment-sum via scatter-add), so the *primary* path here is
what the reference uses as its fallback (sort-based aggregation,
GpuAggregateExec.scala:757) — deliberately inverted for the hardware.

Modes mirror the reference: Partial (update → state columns), Final (merge
states → results), Complete (both, single partition). The planner emits
Partial → [exchange] → Final once the shuffle lands; Complete otherwise.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..columnar.batch import TpuColumnarBatch, concat_batches, gather
from ..columnar.vector import TpuColumnVector, bucket_capacity, row_mask
from ..expressions.aggregates import (AggregateFunction, Average, Count, First,
                                      Last, Max, Min, StddevBase, StddevPop,
                                      StddevSamp, Sum, VariancePop, VarianceSamp)
from ..expressions.base import (Alias, AttributeReference, Expression, to_column)
from ..types import (DataType, DecimalType, DoubleT, FloatType, DoubleType,
                     LongT, StringType)
from .base import (CpuExec, PhysicalPlan, TaskContext, TpuExec, bind_all,
                   bind_references)


def split_result_exprs(aggregates: Sequence[Expression]):
    """Split each output expression into its AggregateFunction leaves + a result
    projection over them (reference resultExpressions handling)."""
    agg_fns: List[AggregateFunction] = []
    result_exprs: List[Expression] = []
    for e in aggregates:
        def rule(x: Expression):
            if isinstance(x, AggregateFunction):
                for i, existing in enumerate(agg_fns):
                    if existing is x:
                        idx = i
                        break
                else:
                    agg_fns.append(x)
                    idx = len(agg_fns) - 1
                return AttributeReference(f"__agg_{idx}", x.dtype, x.nullable,
                                          expr_id=-(idx + 1))
            return None
        result_exprs.append(e.transform(rule))
    return agg_fns, result_exprs


class CpuHashAggregateExec(CpuExec):
    """Arrow group_by based aggregate (the CPU oracle / fallback target)."""

    def __init__(self, grouping: Sequence[Expression],
                 aggregates: Sequence[Expression], child: PhysicalPlan,
                 output: List[AttributeReference], per_partition: bool = False):
        super().__init__([child])
        self.grouping = bind_all(list(grouping), child.output)
        self.aggregates = [bind_references(a, child.output) for a in aggregates]
        self._output = output
        # per_partition: child is hash-distributed by the grouping keys (an
        # exchange below us) so each partition aggregates independently
        self.per_partition = per_partition

    @property
    def output(self):
        return self._output

    def num_partitions(self) -> int:
        return self.children[0].num_partitions() if self.per_partition else 1

    def node_desc(self) -> str:
        return f"CpuHashAggregate[keys={len(self.grouping)}]"

    def execute_partition(self, idx: int, ctx: TaskContext) -> Iterator:
        import pyarrow as pa
        import pyarrow.compute as pc
        child = self.children[0]
        tables = []
        if self.per_partition:
            tables.extend(child.execute_partition(idx, ctx))
        else:
            for p in range(child.num_partitions()):
                tables.extend(child.execute_partition(p, ctx))
        if not tables:
            base = None
        else:
            base = pa.concat_tables(tables)
        agg_fns, result_exprs = split_result_exprs(self.aggregates)
        if base is None or base.num_rows == 0:
            from ..types import to_arrow
            if self.grouping:
                yield pa.schema([(a.name, to_arrow(a.dtype))
                                 for a in self._output]).empty_table()
                return
            base = pa.schema([(a.name, to_arrow(a.dtype))
                              for a in self.children[0].output]).empty_table()
        # pre-project: key cols + agg input cols
        proj: Dict[str, object] = {}
        key_names = []
        for i, g in enumerate(self.grouping):
            arr = g.eval_cpu(base, ctx.eval_ctx)
            arr = _normalize_fp_key_arrow(arr)
            name = f"__key_{i}"
            proj[name] = arr
            key_names.append(name)
        agg_specs = []

        def eval_input(inp):
            r = inp.eval_cpu(base, ctx.eval_ctx)
            if not isinstance(r, (pa.Array, pa.ChunkedArray)):
                from ..types import to_arrow
                r = pa.array([r] * base.num_rows, type=to_arrow(inp.dtype))
            return r

        for i, fn in enumerate(agg_fns):
            inp = fn.children[0] if fn.children else None
            name = f"__in_{i}"
            if inp is None:
                proj[name] = pa.array(np.ones(base.num_rows, np.int64))
            else:
                proj[name] = eval_input(inp)
            if len(fn.children) >= 2:
                proj[f"__in2_{i}"] = eval_input(fn.children[1])
            agg_specs.append((name, fn))
        if base.num_rows == 0 and not self.grouping:
            flat = pa.table({k: pa.array([], type=getattr(v, "type", pa.int64()))
                             for k, v in proj.items()})
        else:
            flat = pa.table(proj)
        agg_table = _arrow_aggregate(flat, key_names, agg_specs, self.grouping)
        # result projection over (keys + __agg_i) — bind the special refs
        out_cols = []
        ng = len(self.grouping)
        for ri, (expr, attr) in enumerate(zip(result_exprs, self._output[ng:])):
            bound = _bind_agg_refs(expr, agg_table, ng, self.grouping)
            r = bound.eval_cpu(agg_table, ctx.eval_ctx)
            if not isinstance(r, (pa.Array, pa.ChunkedArray)):
                from ..types import to_arrow
                r = pa.array([r] * agg_table.num_rows, type=to_arrow(attr.dtype))
            out_cols.append(r)
        names = [a.name for a in self._output]
        key_arrays = [agg_table.column(i) for i in range(ng)]
        yield pa.table(dict(zip(names, key_arrays + out_cols)))


def _normalize_fp_key_arrow(arr):
    import pyarrow as pa
    import pyarrow.compute as pc
    if isinstance(arr, (pa.Array, pa.ChunkedArray)) and pa.types.is_floating(arr.type):
        # -0.0 → 0.0 (NaNs group together in arrow hashing already)
        zero = pa.scalar(0.0, arr.type)
        return pc.if_else(pc.equal(arr, zero), zero, arr)
    return arr


_ARROW_AGG = {"sum": "sum", "count": "count", "min": "min", "max": "max",
              "avg": "mean", "first": "first", "last": "last",
              "stddev_samp": "stddev", "stddev_pop": "stddev",
              "var_samp": "variance", "var_pop": "variance",
              "collect_list": "list", "collect_set": "distinct"}

#: aggregates with no Arrow group_by kernel — python-grouped on the oracle
_CUSTOM_CPU_AGGS = {"percentile", "approx_percentile",
                    "covar_samp", "covar_pop", "corr", "bloom_filter"}


def _dedup_key(v):
    """Hashable identity key for set dedup matching the device semantics
    (_dedup_bits): all NaNs equal; -0.0 and 0.0 distinct; nested values by
    structure."""
    import struct as _struct
    if isinstance(v, float):
        if v != v:
            return ("__nan__",)
        return ("__f__", _struct.pack(">d", v))
    if isinstance(v, list):
        return ("__l__", tuple(_dedup_key(x) for x in v))
    if isinstance(v, dict):
        return ("__m__", tuple(sorted((k, _dedup_key(x))
                                      for k, x in v.items())))
    return v


def _dedup_values(items):
    seen, uniq = set(), []
    for v in items:
        k = _dedup_key(v)
        if k not in seen:
            seen.add(k)
            uniq.append(v)
    return uniq


def _cast_percentile_value(v: float, fn):
    """t-digest quantiles interpolate in doubles; approx_percentile answers
    in the INPUT type like Spark (round-half-even back to integral carriers —
    decimals carry scaled ints, so they round too)."""
    from ..types import DecimalType, FloatType, DoubleType
    if isinstance(fn.children[0].dtype, (FloatType, DoubleType)):
        return float(v)
    import math as _math
    if v != v or _math.isinf(v):
        return float(v)
    return int(np.round(v))


def _custom_cpu_agg(fn, cols_py: List[list], rows: List[int]):
    """One group's value for a python-grouped aggregate (oracle path)."""
    import math
    op = fn.update_op
    if op == "bloom_filter":
        vals = [v for v in (cols_py[0][r] for r in rows) if v is not None]
        return fn.build(np.asarray(vals, np.int64)) if vals else None
    if op in ("first", "last"):
        ignore_nulls = getattr(fn, "ignore_nulls", False)
        seq = rows if op == "first" else list(reversed(rows))
        for r in seq:
            v = cols_py[0][r]
            if v is not None or not ignore_nulls:
                return v
        return None
    if op in ("collect_list", "collect_set"):
        items = [v for v in (cols_py[0][r] for r in rows) if v is not None]
        if op == "collect_list":
            return items
        uniq = _dedup_values(items)
        try:
            uniq = sorted(uniq)  # match the device's value-sorted sets
        except TypeError:
            pass
        return uniq
    if op in ("percentile", "approx_percentile"):
        vals, nans = [], []
        for r in rows:
            v = cols_py[0][r]
            if v is None:
                continue
            if isinstance(v, float) and v != v:
                nans.append(v)
            else:
                vals.append(v)
        vals.sort()
        if op == "approx_percentile":
            # t-digest (same construction as the device bucketing, so the
            # two engines agree exactly; NaNs excluded from the sketch,
            # all-NaN groups answer NaN)
            from ..kernels.tdigest import (build_digest_np, compression_for,
                                           quantile)
            from ..types import DecimalType as _Dec
            if not vals and not nans:
                return None
            dt = fn.children[0].dtype
            dec_scale = dt.scale if isinstance(dt, _Dec) else None
            if dec_scale is not None:
                # digest over the scaled-int carrier domain, exactly like
                # the device path
                from decimal import Decimal as _D
                work = [int(_D(v).scaleb(dec_scale)) for v in vals]
            else:
                work = vals
            comp = compression_for(getattr(fn, "accuracy", 10000))
            means, weights = build_digest_np(np.asarray(work, np.float64),
                                             comp)
            outs = []
            for p in fn.percentages:
                if not vals:
                    outs.append(float("nan"))
                    continue
                q = _cast_percentile_value(quantile(means, weights, p), fn)
                if dec_scale is not None:
                    from decimal import Decimal as _D
                    q = _D(int(q)).scaleb(-dec_scale)
                outs.append(q)
            return outs if fn.is_array else outs[0]
        vals.extend(nans)  # NaN greatest, like the device bit encoding
        if not vals:
            return None
        n = len(vals)
        outs = []
        for p in fn.percentages:
            t = p * (n - 1)
            lo, hi = math.floor(t), math.ceil(t)
            outs.append(float(vals[lo])
                        + (float(vals[hi]) - float(vals[lo])) * (t - lo))
        return outs if fn.is_array else outs[0]
    # covariance family
    xs, ys = [], []
    for r in rows:
        x, y = cols_py[0][r], cols_py[1][r]
        if x is None or y is None:
            continue
        xs.append(float(x))
        ys.append(float(y))
    n = len(xs)
    if n == 0 or (op != "covar_pop" and n < 2):
        return None
    sx, sy = sum(xs), sum(ys)
    sxy = sum(x * y for x, y in zip(xs, ys))
    cov = sxy - sx * sy / n
    if op == "covar_pop":
        return cov / n
    if op == "covar_samp":
        return cov / (n - 1)
    sx2 = sum(x * x for x in xs)
    sy2 = sum(y * y for y in ys)
    mx2 = max(sx2 - sx * sx / n, 0.0)
    my2 = max(sy2 - sy * sy / n, 0.0)
    denom = math.sqrt(mx2 * my2)
    if denom == 0:
        return None
    return cov / denom


def _arrow_aggregate(flat, key_names: List[str], agg_specs, grouping):
    """Grouped aggregation with Spark semantics layered over arrow group_by.
    Spark orders NaN greater than all doubles: fp min skips NaN unless the whole
    group is NaN; fp max is NaN when any NaN is present — arrow propagates NaN
    instead, so fp min/max decompose into clean-min/any-nan/all-nan parts."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.compute as pc

    work = {k: flat.column(k) for k in key_names}
    plans = []  # per output agg: (mode, [work col names], fn)
    for i, (name, fn) in enumerate(agg_specs):
        col = flat.column(name)
        is_fp = pa.types.is_floating(col.type)
        if fn.update_op in _CUSTOM_CPU_AGGS or (
                fn.update_op in ("collect_set", "collect_list", "first",
                                 "last")
                and pa.types.is_nested(col.type)):
            # nested inputs: Arrow's hash_* kernels lack struct/list
            # support → python-grouped path
            names = [f"__c_{i}"]
            work[f"__c_{i}"] = col
            if f"__in2_{i}" in flat.column_names:
                work[f"__c2_{i}"] = flat.column(f"__in2_{i}")
                names.append(f"__c2_{i}")
            plans.append(("custom", names, fn))
        elif is_fp and fn.update_op in ("min", "max"):
            nan = pc.is_nan(col)
            neutral = pa.scalar(np.inf if fn.update_op == "min" else -np.inf,
                                col.type)
            clean = pc.if_else(pc.fill_null(nan, False), neutral, col)
            work[f"__c_{i}"] = clean
            work[f"__n_{i}"] = pc.cast(nan, pa.int8())  # null-preserving
            plans.append(("fp_minmax", [f"__c_{i}", f"__n_{i}"], fn))
        else:
            work[f"__c_{i}"] = col
            plans.append(("plain", [f"__c_{i}"], fn))

    agg_calls = []
    for mode, names, fn in plans:
        if mode == "custom":
            continue
        op = _ARROW_AGG[fn.update_op]
        if fn.update_op in ("stddev_samp", "var_samp"):
            agg_calls.append((names[0], op, pc.VarianceOptions(ddof=1)))
        elif fn.update_op in ("stddev_pop", "var_pop"):
            agg_calls.append((names[0], op, pc.VarianceOptions(ddof=0)))
        elif fn.update_op in ("first", "last"):
            agg_calls.append((names[0], op, pc.ScalarAggregateOptions(
                skip_nulls=getattr(fn, "ignore_nulls", False))))
        elif mode == "fp_minmax":
            agg_calls.append((names[0], op, None))
            agg_calls.append((names[1], "min", None))  # all-nan flag
            agg_calls.append((names[1], "max", None))  # any-nan flag
        else:
            agg_calls.append((names[0], op, None))

    work_table = pa.table(work)
    have_custom = any(m == "custom" for m, _, _ in plans)
    if key_names:
        if not agg_calls:
            # keys only (all aggs custom): still need the distinct-key rows
            work_table = work_table.append_column(
                "__dummy", pa.array(np.ones(work_table.num_rows, np.int8)))
            agg_calls.append(("__dummy", "count", None))
        # first/last are ordered aggregators: arrow only supports them in
        # single-threaded execution (and row order matters for them anyway)
        ordered = any(op in ("first", "last") for _, op, _ in agg_calls)
        gb = pa.TableGroupBy(work_table, key_names, use_threads=not ordered)
        res = gb.aggregate([(n, op) if o is None else (n, op, o)
                            for n, op, o in agg_calls])
        get = lambda n, op: res.column(f"{n}_{op}")
        n_out = res.num_rows
    else:
        scalar_fns = {"sum": pc.sum, "count": pc.count, "min": pc.min,
                      "max": pc.max, "mean": pc.mean, "first": pc.first,
                      "last": pc.last, "stddev": pc.stddev,
                      "variance": pc.variance}
        results = {}
        for n, op, o in agg_calls:
            col = work_table.column(n)
            if op in ("list", "distinct"):
                # raw collect; null-drop/dedup happens in the shared cleanup
                results[f"{n}_{op}"] = pa.array([col.to_pylist()],
                                                type=pa.list_(col.type))
                continue
            f = scalar_fns[op]
            v = f(col, options=o) if o is not None else f(col)
            results[f"{n}_{op}"] = pa.array(
                [v.as_py()], type=v.type if v.type != pa.null() else pa.int64())
        get = lambda n, op: results[f"{n}_{op}"]
        n_out = 1

    # custom (python-grouped) aggregates, aligned to the output key rows
    custom_vals = {}
    if have_custom:
        def canon(t):
            return tuple("__nan__" if isinstance(v, float) and v != v else v
                         for v in t)
        if key_names:
            in_keys = list(zip(*[work_table.column(k).to_pylist()
                                 for k in key_names]))
            groups: Dict[tuple, list] = {}
            for ri, kt in enumerate(in_keys):
                groups.setdefault(canon(kt), []).append(ri)
            out_keys = [canon(t) for t in zip(*[res.column(k).to_pylist()
                                               for k in key_names])]
        else:
            groups = {(): list(range(work_table.num_rows))}
            out_keys = [()]
        for i, (mode, names, fn) in enumerate(plans):
            if mode != "custom":
                continue
            cols_py = [work_table.column(nm).to_pylist() for nm in names]
            vals = [_custom_cpu_agg(fn, [c for c in cols_py],
                                    groups.get(k, [])) for k in out_keys]
            from ..types import to_arrow as type_to_arrow
            custom_vals[i] = pa.array(vals, type=type_to_arrow(fn.dtype))

    out_cols = {}
    for i, (mode, names, fn) in enumerate(plans):
        if mode == "custom":
            out_cols[f"__out_{i}"] = custom_vals[i]
            continue
        op = _ARROW_AGG[fn.update_op]
        if op in ("list", "distinct"):
            raw = get(names[0], op)
            cleaned = []
            for lst in raw.to_pylist():
                items = [v for v in (lst or []) if v is not None]
                if op == "distinct":
                    items = _dedup_values(items)
                cleaned.append(items)
            from ..types import to_arrow as type_to_arrow
            out_cols[f"__out_{i}"] = pa.array(cleaned,
                                              type=type_to_arrow(fn.dtype))
            continue
        if mode == "fp_minmax":
            red = get(names[0], op)
            all_nan = get(names[1], "min")
            any_nan = get(names[1], "max")
            nan_scalar = pa.scalar(float("nan"), red.type if hasattr(red, 'type') else pa.float64())
            if fn.update_op == "min":
                flag = pc.equal(pc.fill_null(all_nan, 0), 1)
            else:
                flag = pc.equal(pc.fill_null(any_nan, 0), 1)
            out = pc.if_else(flag, nan_scalar, red)
        else:
            out = get(names[0], op)
        out_cols[f"__out_{i}"] = out

    if key_names:
        key_arrays = [res.column(k) for k in key_names]
    else:
        key_arrays = []
    arrays = key_arrays + [out_cols[f"__out_{i}"] for i in range(len(plans))]
    names_out = key_names + [f"__out_{i}" for i in range(len(plans))]
    return pa.table(dict(zip(names_out, arrays)))


def _bind_agg_refs(expr: Expression, agg_table, num_keys: int,
                   grouping: Sequence[Expression] = ()) -> Expression:
    """Rewrite __agg_i refs (expr_id=-(i+1)) to ordinals in the aggregated
    table; references to grouping attributes rebind to their key slot (so
    result projections over keys — e.g. grouping_id() — evaluate against the
    aggregated layout, not the child's)."""
    key_slot = {g.expr_id: j for j, g in enumerate(grouping)
                if isinstance(g, AttributeReference)}

    def rule(e: Expression):
        if isinstance(e, AttributeReference) and e.expr_id < 0:
            i = -e.expr_id - 1
            return AttributeReference(e.name, e.dtype, e.nullable,
                                      ordinal=num_keys + i, expr_id=e.expr_id)
        if isinstance(e, AttributeReference) and e.expr_id in key_slot:
            return AttributeReference(e.name, e.dtype, e.nullable,
                                      ordinal=key_slot[e.expr_id],
                                      expr_id=e.expr_id)
        return None

    return expr.transform(rule)


# ---------------------------------------------------------------------------
# TPU path
# ---------------------------------------------------------------------------

def _sortable_bits(col: TpuColumnVector):
    """Order/equality-preserving integer encoding of a fixed-width column
    (floats: sign-flipped IEEE bits with NaN canonicalized and -0→0 — the same
    trick radix sorts use; cuDF does this inside its sort kernels)."""
    d = col.data
    if getattr(d, "ndim", 1) != 1:
        # decimal128 limb pairs have no single-int64 order encoding; the
        # tagging layer keeps such columns off device sorts/joins — raising
        # here turns a would-be silent mis-sort into a loud error
        raise NotImplementedError(
            f"no sortable encoding for {col.dtype.simple_string()} "
            f"(two-limb carrier)")
    if jnp.issubdtype(d.dtype, jnp.floating):
        d = jnp.where(d == 0.0, jnp.zeros((), d.dtype), d)
        canon = jnp.asarray(np.array(np.nan, d.dtype))
        d = jnp.where(jnp.isnan(d), canon, d)
        # a DOUBLE key stays a DOUBLE on every backend: utils/hw picks the
        # bit view or, on TPU, the exact f32-pair encoding
        from ..utils.hw import f32_order_bits, f64_order_bits
        return f64_order_bits(d) if d.dtype == jnp.float64 \
            else f32_order_bits(d)
    if d.dtype == jnp.bool_:
        return d.astype(jnp.int32)
    return d


def encode_group_keys(cols: List[TpuColumnVector], num_rows: int, capacity: int):
    """Per-key (sortable_value, validity) pairs. Strings carrying a device
    `dict_encoding` (parquet dictionary pages, the dictionary exchange's
    decode-on-read) use their codes DIRECTLY — equality-preserving int32,
    zero host work; strings without one dictionary-encode host-side (codes
    preserve equality; order not needed for grouping)."""
    out = []
    for c in cols:
        if isinstance(c.dtype, StringType):
            de = getattr(c, "dict_encoding", None)
            if de is not None:
                out.append((de[0], c.validity))
                continue
            import pyarrow as pa
            import pyarrow.compute as pc
            arr = c.to_arrow()
            enc = pc.dictionary_encode(arr)
            if isinstance(enc, pa.ChunkedArray):
                enc = enc.combine_chunks()
            codes = enc.indices
            vals = np.asarray(codes.fill_null(-1).to_numpy(zero_copy_only=False)).astype(np.int32)
            buf = np.zeros(capacity, np.int32)
            buf[:num_rows] = vals
            out.append((jnp.asarray(buf), c.validity))
        else:
            out.append((_sortable_bits(c), c.validity))
    return out


def segment_boundaries(enc, perm, rowmask):
    """Group boundaries over key-sorted rows: (is_new, seg_ids, n_groups).
    Shared by the eager sort phase and the opjit traced sort phase — the two
    paths MUST agree bit-for-bit, so there is exactly one copy. `n_groups`
    is returned as a device scalar (callers sync when they need the int)."""
    cap = perm.shape[0]
    is_new = jnp.zeros((cap,), jnp.bool_).at[0].set(True)
    for vals, validity in enc:
        sv = jnp.take(vals, perm)
        neq = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                               sv[1:] != sv[:-1]])
        if validity is not None:
            nv = jnp.take(validity, perm)
            vneq = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                    nv[1:] != nv[:-1]])
            neq = neq | vneq
        is_new = is_new | neq
    pad = jnp.take(rowmask, perm)
    is_new = is_new & pad
    seg_ids = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    ng = jnp.max(jnp.where(pad, seg_ids, -1)) + 1
    return is_new, seg_ids, ng


def lex_sort_permutation(keys, num_rows: int, capacity: int,
                         orders: Optional[List[Tuple[bool, bool]]] = None):
    """Stable lexicographic sort permutation over encoded keys.
    keys: list of (values, validity_or_None); orders: per-key (ascending,
    nulls_first); padding rows always sort last."""
    perm = jnp.arange(capacity, dtype=jnp.int32)
    if orders is None:
        orders = [(True, True)] * len(keys)
    # least-significant key first; each pass is a stable argsort. Within one
    # key the order is (null group, value): a value pass then a null-flag
    # pass — sentinel encodings would collide with real extreme values
    # (e.g. a null vs an actual INT32_MIN).
    for (vals, validity), (asc, nulls_first) in list(zip(keys, orders))[::-1]:
        v = jnp.take(vals, perm)
        if validity is not None:
            # null lanes hold garbage payloads — pin them to a constant so
            # the value pass keeps prior-pass (secondary-key) order for ties
            nv0 = jnp.take(validity, perm)
            v = jnp.where(nv0, v, jnp.zeros((), v.dtype))
        if not asc:
            v = _invert_order(v)
        order = jnp.argsort(v, stable=True)
        perm = jnp.take(perm, order)
        if validity is not None:
            nv = jnp.take(validity, perm)
            flag = jnp.where(nv, 1, 0) if nulls_first else jnp.where(nv, 0, 1)
            order = jnp.argsort(flag, stable=True)
            perm = jnp.take(perm, order)
    # padding last: single extra pass on is_padding
    pad = (perm >= num_rows).astype(jnp.int32)
    order = jnp.argsort(pad, stable=True)
    return jnp.take(perm, order)


def _invert_order(v):
    if v.dtype == jnp.int64:
        return jnp.int64(-1) ^ v
    return (-1 ^ v.astype(jnp.int32))


class AggState:
    """Per-group device state columns for one aggregate fn."""

    def __init__(self, arrays: Dict[str, jnp.ndarray]):
        self.arrays = arrays


def _segment_update(fn: AggregateFunction, col: Optional[TpuColumnVector],
                    seg_ids, n_groups_cap: int, capacity: int, num_rows: int,
                    sorted_perm) -> Dict[str, jnp.ndarray]:
    """Compute partial state per group via scatter reductions over sorted rows.
    `col` is the evaluated input column (a tuple of columns for two-input
    aggregates like covar/corr)."""
    if fn.update_op in ("collect_list", "collect_set",
                        "percentile", "approx_percentile"):
        return _segment_collect(fn, col, seg_ids, n_groups_cap, capacity,
                                num_rows, sorted_perm)
    if fn.update_op in ("covar_samp", "covar_pop", "corr"):
        return _segment_covar(fn, col, seg_ids, n_groups_cap, capacity,
                              num_rows, sorted_perm)
    if fn.update_op == "bloom_filter":
        return _segment_bloom(fn, col, seg_ids, n_groups_cap, capacity,
                              num_rows, sorted_perm)
    if fn.update_op in ("min", "max", "first", "last") and col is not None \
            and not isinstance(col, tuple) \
            and (col.offsets is not None or col.host_data is not None
                 or col.children is not None):
        # variable-width input (strings/binary/nested): host-assisted segment
        # min/max/first/last over the arrow values (the reference does these
        # in cuDF device kernels; no TPU ragged reduce yet)
        return _host_segment_minmax(fn, col, seg_ids, n_groups_cap, capacity,
                                    num_rows, sorted_perm)
    mask = row_mask(num_rows, capacity)
    if col is not None:
        data = jnp.take(col.data, sorted_perm)
        valid = jnp.take(col.validity, sorted_perm) if col.validity is not None else mask
        valid = valid & jnp.take(mask, sorted_perm)
    else:
        data = jnp.ones((capacity,), jnp.int64)
        valid = jnp.take(mask, sorted_perm)
    op = fn.update_op
    if op == "count":
        cnt = jnp.zeros((n_groups_cap,), jnp.int64).at[seg_ids].add(
            valid.astype(jnp.int64), mode="drop")
        return {"count": cnt}
    if op == "sum":
        acc_dtype = fn.dtype.np_dtype
        contrib = jnp.where(valid, data, jnp.zeros((), data.dtype)).astype(acc_dtype)
        s = jnp.zeros((n_groups_cap,), acc_dtype).at[seg_ids].add(contrib, mode="drop")
        nn = jnp.zeros((n_groups_cap,), jnp.int64).at[seg_ids].add(
            valid.astype(jnp.int64), mode="drop")
        return {"sum": s, "nonnull": nn}
    if op == "avg":
        contrib = jnp.where(valid, data, jnp.zeros((), data.dtype)).astype(jnp.float64)
        s = jnp.zeros((n_groups_cap,), jnp.float64).at[seg_ids].add(contrib, mode="drop")
        n = jnp.zeros((n_groups_cap,), jnp.int64).at[seg_ids].add(
            valid.astype(jnp.int64), mode="drop")
        return {"sum": s, "count": n}
    if op in ("min", "max"):
        is_fp = jnp.issubdtype(data.dtype, jnp.floating)
        nn = jnp.zeros((n_groups_cap,), jnp.int64).at[seg_ids].add(
            valid.astype(jnp.int64), mode="drop")
        if is_fp:
            # Spark orders NaN greater than everything: min skips NaN unless the
            # whole group is NaN; max returns NaN if any NaN present.
            neutral = jnp.asarray(np.inf if op == "min" else -np.inf, data.dtype)
            nan_in = jnp.isnan(data) & valid
            clean = jnp.where(valid & ~jnp.isnan(data), data, neutral)
            init = jnp.full((n_groups_cap,), neutral, data.dtype)
            red = init.at[seg_ids].min(clean, mode="drop") if op == "min" \
                else init.at[seg_ids].max(clean, mode="drop")
            nan_any = jnp.zeros((n_groups_cap,), jnp.bool_).at[seg_ids].max(
                nan_in, mode="drop")
            nonnan = jnp.zeros((n_groups_cap,), jnp.int64).at[seg_ids].add(
                (valid & ~jnp.isnan(data)).astype(jnp.int64), mode="drop")
            if op == "min":
                red = jnp.where((nonnan == 0) & (nn > 0),
                                jnp.asarray(np.nan, data.dtype), red)
            else:
                red = jnp.where(nan_any, jnp.asarray(np.nan, data.dtype), red)
            return {op: red, "nonnull": nn}
        info = np.iinfo(np.asarray(jnp.zeros((), data.dtype)).dtype)
        neutral = jnp.asarray(info.max if op == "min" else info.min, data.dtype)
        contrib = jnp.where(valid, data, neutral)
        init = jnp.full((n_groups_cap,), neutral, data.dtype)
        red = init.at[seg_ids].min(contrib, mode="drop") if op == "min" \
            else init.at[seg_ids].max(contrib, mode="drop")
        return {op: red, "nonnull": nn}
    if op in ("first", "last"):
        pos = jnp.arange(capacity, dtype=jnp.int32)
        ignore = getattr(fn, "ignore_nulls", False)
        eligible = valid if ignore else jnp.take(mask, sorted_perm)
        bad = jnp.asarray(np.int32(2**31 - 1))
        cand = jnp.where(eligible, pos, bad if op == "first" else jnp.int32(-1))
        init = jnp.full((n_groups_cap,), bad if op == "first" else jnp.int32(-1), jnp.int32)
        sel = init.at[seg_ids].min(cand, mode="drop") if op == "first" \
            else init.at[seg_ids].max(cand, mode="drop")
        has = (sel != (bad if op == "first" else -1))
        safe = jnp.clip(sel, 0, capacity - 1)
        vals = jnp.take(data, safe)
        vvalid = jnp.take(valid, safe) & has
        return {op: jnp.where(vvalid, vals, jnp.zeros((), vals.dtype)),
                "has": has, f"{op}_valid": vvalid}
    if op in ("stddev_samp", "stddev_pop", "var_samp", "var_pop"):
        x = jnp.where(valid, data, jnp.zeros((), data.dtype)).astype(jnp.float64)
        n = jnp.zeros((n_groups_cap,), jnp.int64).at[seg_ids].add(
            valid.astype(jnp.int64), mode="drop")
        s = jnp.zeros((n_groups_cap,), jnp.float64).at[seg_ids].add(x, mode="drop")
        s2 = jnp.zeros((n_groups_cap,), jnp.float64).at[seg_ids].add(x * x, mode="drop")
        return {"n": n, "sum": s, "sumsq": s2}
    raise NotImplementedError(f"update op {op}")


def _dedup_bits(col_data):
    """Equality-preserving bit view for set dedup: NaNs canonicalized (Java
    HashSet merges NaNs) but -0.0 and 0.0 kept distinct (Double.equals)."""
    d = col_data
    if jnp.issubdtype(d.dtype, jnp.floating):
        canon = jnp.asarray(np.array(np.nan, d.dtype))
        d = jnp.where(jnp.isnan(d), canon, d)
        if d.dtype == jnp.float64:
            from ..utils.hw import f64_order_bits
            return f64_order_bits(d)  # injective: -0.0 != 0.0 survives
        return d.view(jnp.int32)
    if d.dtype == jnp.bool_:
        return d.astype(jnp.int32)
    return d


def _compact_to_indices(keep, perm, capacity: int):
    """Sorted-domain keep mask → (orig-row index array, total, elem_cap).
    Groups are contiguous in sorted order, so a global stable compact keeps
    per-group element runs contiguous — exactly the list-column child layout."""
    pos_out = jnp.cumsum(keep.astype(jnp.int32)) - 1
    total = int(jnp.sum(keep))
    elem_cap = bucket_capacity(max(total, 1))
    idx = jnp.full((elem_cap,), capacity, jnp.int32).at[
        jnp.where(keep, pos_out, elem_cap)].set(
        perm.astype(jnp.int32), mode="drop")
    return idx, total, elem_cap


def _segment_collect(fn, col: TpuColumnVector, seg_ids, g_cap: int,
                     capacity: int, num_rows: int, perm):
    """collect_list / collect_set / percentile / approx_percentile.

    The input is already key-sorted (groups contiguous), so collect_list is a
    null-compact + offsets-from-counts; collect_set and the percentiles add a
    value sort within each segment (lexsort on (segment, value bits)) — the
    same segmented-sort shape cuDF's groupby collect/percentile kernels use.
    """
    mask = row_mask(num_rows, capacity)
    valid_orig = (col.validity & mask) if col.validity is not None else mask
    valid = jnp.take(valid_orig, perm)  # sorted domain
    op = fn.update_op
    device_layout = col.offsets is None and col.host_data is None

    if op == "collect_list":
        counts = jnp.zeros((g_cap,), jnp.int32).at[seg_ids].add(
            valid.astype(jnp.int32), mode="drop")
        idx, total, elem_cap = _compact_to_indices(valid, perm, capacity)
        from ..columnar.batch import _gather_column
        child = _gather_column(col, jnp.where(idx < capacity, idx, 0),
                               row_mask(total, elem_cap), total, elem_cap)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(counts).astype(jnp.int32)])
        return {"__list_child": child, "__list_offsets": offsets}

    if not device_layout:
        return _host_collect(fn, col, seg_ids, g_cap, capacity, num_rows, perm)

    data = jnp.take(col.data, perm)  # sorted domain values
    # secondary sort by value within segment; invalid rows to a trailing bucket
    bits = _dedup_bits(data) if op == "collect_set" else _sortable_bits(
        TpuColumnVector(col.dtype, data, None, num_rows))
    seg_key = jnp.where(valid, seg_ids, g_cap)
    perm2 = jnp.lexsort((bits, seg_key))  # value-sorted within each segment
    seg2 = jnp.take(seg_key, perm2)
    valid2 = jnp.take(valid, perm2)
    bits2 = jnp.take(bits, perm2)

    if op == "collect_set":
        first = valid2 & jnp.concatenate([
            jnp.ones((1,), jnp.bool_),
            (seg2[1:] != seg2[:-1]) | (bits2[1:] != bits2[:-1])])
        counts = jnp.zeros((g_cap,), jnp.int32).at[
            jnp.where(valid2, seg2, g_cap)].add(
            first.astype(jnp.int32), mode="drop")
        orig_idx = jnp.take(perm, perm2)
        idx, total, elem_cap = _compact_to_indices(first, orig_idx, capacity)
        from ..columnar.batch import _gather_column
        child = _gather_column(col, jnp.where(idx < capacity, idx, 0),
                               row_mask(total, elem_cap), total, elem_cap)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(counts).astype(jnp.int32)])
        return {"__list_child": child, "__list_offsets": offsets}

    # percentiles: per-group sorted run [start, start+n_g)
    pos = jnp.arange(capacity, dtype=jnp.int32)
    n_g = jnp.zeros((g_cap,), jnp.int64).at[
        jnp.where(valid2, seg2, g_cap)].add(
        valid2.astype(jnp.int64), mode="drop")
    starts = jnp.full((g_cap,), capacity, jnp.int32).at[
        jnp.where(valid2, seg2, g_cap)].min(pos, mode="drop")
    vals2 = jnp.take(data, perm2)
    if op == "approx_percentile":
        # mergeable t-digest, built by device bucketing over the segment-
        # sorted run (kernels/tdigest.py; reference
        # GpuApproximatePercentile.scala). NaNs are excluded from the
        # sketch; an all-NaN group answers NaN.
        from ..kernels.tdigest import (compression_for,
                                       grouped_digest_quantiles_device)
        is_fp = jnp.issubdtype(vals2.dtype, jnp.floating)
        nonnan2 = valid2 & (~jnp.isnan(vals2) if is_fp
                            else jnp.ones_like(valid2))
        n_nn = jnp.zeros((g_cap,), jnp.int64).at[
            jnp.where(nonnan2, seg2, g_cap)].add(
            nonnan2.astype(jnp.int64), mode="drop")
        starts_nn = jnp.full((g_cap,), capacity, jnp.int32).at[
            jnp.where(nonnan2, seg2, g_cap)].min(pos, mode="drop")
        comp = compression_for(getattr(fn, "accuracy", 10000))
        qs = grouped_digest_quantiles_device(
            vals2.astype(jnp.float64), seg2, nonnan2, starts_nn, n_nn,
            g_cap, fn.percentages, comp)
        out = {"n": n_g}
        int_out = not jnp.issubdtype(
            np.dtype(fn.dtype.np_dtype) if not fn.is_array
            else np.dtype(fn.dtype.element_type.np_dtype), np.floating)
        for k in range(len(fn.percentages)):
            v = qs[k]
            v = jnp.where(n_nn > 0, v, jnp.float64(np.nan))
            if int_out:
                v = jnp.round(v).astype(
                    np.dtype(fn.dtype.np_dtype) if not fn.is_array
                    else np.dtype(fn.dtype.element_type.np_dtype))
            out[f"p{k}"] = v
        return out
    # exact percentile: rank interpolation over the sorted run.
    # decimal columns carry scaled ints; interpolate in doubles, unscaled
    unscale = (10.0 ** -col.dtype.scale) \
        if isinstance(col.dtype, DecimalType) else 1.0
    out = {"n": n_g}
    for k, p in enumerate(fn.percentages):
        t = p * jnp.maximum(n_g.astype(jnp.float64) - 1.0, 0.0)
        lo = jnp.floor(t).astype(jnp.int64)
        hi = jnp.ceil(t).astype(jnp.int64)
        frac = t - lo.astype(jnp.float64)
        v_lo = jnp.take(vals2, jnp.clip(starts.astype(jnp.int64) + lo,
                                        0, capacity - 1)).astype(jnp.float64) * unscale
        v_hi = jnp.take(vals2, jnp.clip(starts.astype(jnp.int64) + hi,
                                        0, capacity - 1)).astype(jnp.float64) * unscale
        out[f"p{k}"] = v_lo + (v_hi - v_lo) * frac
    return out


def _host_collect(fn, col, seg_ids, g_cap, capacity, num_rows, perm):
    """Arrow-assisted collect_set for string/nested inputs (value bits don't
    exist on device); produces the same value-sorted-set layout.

    Vectorized for arrow-sortable element types (strings/binary/numerics):
    one arrow take + one (segment, value) sort + a numpy consecutive-dedup —
    no per-row python loop. Nested elements (arrow cannot sort them) keep
    the pylist path with first-seen order."""
    import pyarrow as pa
    import pyarrow.compute as pc
    arr = col.to_arrow()  # original row domain
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    from ..columnar.vector import audited_sync
    perm_np = audited_sync(perm, "fetch")[:capacity]
    seg_np = audited_sync(seg_ids, "fetch")[:capacity].astype(np.int64)
    from ..types import to_arrow as type_to_arrow
    in_range = perm_np < min(num_rows, len(arr))
    rows = perm_np[in_range].astype(np.int64)
    segs = seg_np[in_range]
    vals = arr.take(pa.array(rows))
    valid_np = np.asarray(vals.is_valid()) & (segs < g_cap)
    vals = vals.filter(pa.array(valid_np))
    segs = segs[valid_np]
    try:
        order = pc.sort_indices(
            pa.table({"s": pa.array(segs), "v": vals}),
            sort_keys=[("s", "ascending"), ("v", "ascending")])
    except (pa.ArrowNotImplementedError, pa.ArrowInvalid, TypeError):
        return _host_collect_pylist(fn, arr, perm_np, seg_np, g_cap,
                                    capacity, num_rows)
    order_np = np.asarray(order).astype(np.int64)
    segs_sorted = segs[order_np]
    vals_sorted = vals.take(order)
    # consecutive dedup on (segment, dictionary code): equal strings share a
    # code, so a code change == a value change within the segment run
    enc = pc.dictionary_encode(vals_sorted)
    if isinstance(enc, pa.ChunkedArray):
        enc = enc.combine_chunks()
    codes = np.asarray(enc.indices.to_numpy(zero_copy_only=False)
                       ).astype(np.int64)
    n = len(segs_sorted)
    first = np.ones(n, dtype=bool)
    if n > 1:
        first[1:] = (segs_sorted[1:] != segs_sorted[:-1]) | \
            (codes[1:] != codes[:-1])
    counts = np.bincount(segs_sorted[first], minlength=g_cap)
    offsets = np.zeros(g_cap + 1, dtype=np.int32)
    np.cumsum(counts, out=offsets[1:])
    child = vals_sorted.filter(pa.array(first))
    elem_t = type_to_arrow(fn.dtype).value_type
    if child.type != elem_t:
        child = child.cast(elem_t)
    list_arr = pa.ListArray.from_arrays(pa.array(offsets, pa.int32()), child)
    if list_arr.type != type_to_arrow(fn.dtype):
        list_arr = list_arr.cast(type_to_arrow(fn.dtype))
    final = TpuColumnVector.from_arrow(list_arr)
    return {"__final": final}


def _host_collect_pylist(fn, arr, perm_np, seg_np, g_cap, capacity,
                         num_rows):
    """Per-row fallback for element types arrow cannot sort (nested):
    first-seen order, python-level dedup — the pre-vectorization path."""
    import pyarrow as pa
    vals = arr.to_pylist()
    sets: Dict[int, list] = {}
    for i in range(capacity):
        row = int(perm_np[i])
        if row >= num_rows:
            continue
        v = vals[row] if row < len(vals) else None
        if v is None:
            continue
        sets.setdefault(int(seg_np[i]), []).append(v)
    out_lists = []
    for g in range(g_cap):
        uniq = _dedup_values(sets.get(g, []))
        try:
            uniq = sorted(uniq)  # device parity: value-sorted sets
        except TypeError:
            pass  # nested elements: keep first-seen order
        out_lists.append(uniq)
    from ..types import to_arrow as type_to_arrow
    list_arr = pa.array(out_lists, type=type_to_arrow(fn.dtype))
    final = TpuColumnVector.from_arrow(list_arr)
    return {"__final": final}


def _host_segment_minmax(fn, col, seg_ids, g_cap: int, capacity: int,
                         num_rows: int, perm):
    """min/max/first/last for variable-width columns, host-side over sorted
    segments (groups are contiguous after the key sort).

    Vectorized: first/last reduce to one numpy segment min/max over sorted
    POSITIONS (any element type — the value is fetched with one arrow take
    of the chosen row per group); min/max over VALUES use numpy minimum/
    maximum.at for numeric carriers and an arrow (segment, value) sort for
    other orderable types (strings/binary). Only element types arrow cannot
    order fall back to the per-row pylist loop."""
    import pyarrow as pa
    import pyarrow.compute as pc
    arr = col.to_arrow()  # original row domain
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    from ..columnar.vector import audited_sync
    perm_np = audited_sync(perm, "fetch")[:num_rows].astype(np.int64)
    seg_np = audited_sync(seg_ids, "fetch")[:num_rows].astype(np.int64)
    op = fn.update_op
    ignore_nulls = getattr(fn, "ignore_nulls", False)
    n_groups = int(seg_np.max()) + 1 if num_rows else 0
    from ..types import to_arrow as type_to_arrow
    atype = type_to_arrow(fn.dtype)

    def result_from_rows(sel_rows: np.ndarray, has: np.ndarray):
        """One arrow take of the chosen source row per group; groups without
        a chosen row take a null index → null output."""
        idx = pa.array(np.where(has, sel_rows, 0), mask=~has)
        out = arr.take(idx)
        return {"__final": TpuColumnVector.from_arrow(
            out if out.type == atype else out.cast(atype))}

    if op in ("first", "last"):
        pos = np.arange(num_rows, dtype=np.int64)
        if ignore_nulls:
            valid = np.asarray(arr.is_valid())
            eligible = valid[perm_np] if len(valid) else \
                np.zeros(num_rows, dtype=bool)
        else:
            eligible = np.ones(num_rows, dtype=bool)
        sent = np.int64(num_rows if op == "first" else -1)
        sel = np.full(n_groups, sent, dtype=np.int64)
        if op == "first":
            np.minimum.at(sel, seg_np[eligible], pos[eligible])
        else:
            np.maximum.at(sel, seg_np[eligible], pos[eligible])
        has = sel != sent
        rows = perm_np[np.clip(sel, 0, max(num_rows - 1, 0))] \
            if num_rows else sel
        return result_from_rows(rows, has)

    # min/max over values: nulls never participate
    valid = np.asarray(arr.is_valid()) if arr.null_count else \
        np.ones(len(arr), dtype=bool)
    row_valid = valid[perm_np] if len(valid) else \
        np.zeros(num_rows, dtype=bool)
    rows = perm_np[row_valid]
    segs = seg_np[row_valid]
    if pa.types.is_integer(arr.type) or pa.types.is_floating(arr.type):
        # numeric carrier: numpy segment reduce, no sort needed
        vals_np = np.asarray(arr.take(pa.array(rows)).to_numpy(
            zero_copy_only=False))
        if pa.types.is_floating(arr.type):
            sent_v = np.inf if op == "min" else -np.inf
        else:
            info = np.iinfo(vals_np.dtype)
            sent_v = info.max if op == "min" else info.min
        acc = np.full(n_groups, sent_v, dtype=vals_np.dtype)
        if op == "min":
            np.minimum.at(acc, segs, vals_np)
        else:
            np.maximum.at(acc, segs, vals_np)
        has = np.zeros(n_groups, dtype=bool)
        has[segs] = True
        out = pa.array(acc, mask=~has)
        return {"__final": TpuColumnVector.from_arrow(
            out if out.type == atype else out.cast(atype))}
    vals = arr.take(pa.array(rows))
    try:
        order = pc.sort_indices(
            pa.table({"s": pa.array(segs), "v": vals}),
            sort_keys=[("s", "ascending"), ("v", "ascending")])
    except (pa.ArrowNotImplementedError, pa.ArrowInvalid, TypeError):
        return _host_segment_minmax_pylist(fn, arr, perm_np, seg_np,
                                           num_rows, n_groups, op)
    order_np = np.asarray(order).astype(np.int64)
    segs_sorted = segs[order_np]
    # per-group run boundaries in the (seg, value)-sorted order: min == run
    # start, max == run end
    if op == "min":
        sel_pos = np.full(n_groups, len(segs_sorted), dtype=np.int64)
        np.minimum.at(sel_pos, segs_sorted, np.arange(len(segs_sorted)))
        has = sel_pos != len(segs_sorted)
    else:
        sel_pos = np.full(n_groups, -1, dtype=np.int64)
        np.maximum.at(sel_pos, segs_sorted, np.arange(len(segs_sorted)))
        has = sel_pos != -1
    chosen = rows[order_np[np.clip(sel_pos, 0, max(len(order_np) - 1, 0))]] \
        if len(order_np) else sel_pos
    return result_from_rows(chosen, has)


def _host_segment_minmax_pylist(fn, arr, perm_np, seg_np, num_rows: int,
                                n_groups: int, op: str):
    """Per-row fallback for element types arrow cannot order (nested)."""
    import pyarrow as pa
    from ..types import to_arrow as type_to_arrow
    vals = arr.to_pylist()
    out: List = [None] * n_groups
    for pos in range(num_rows):
        g = int(seg_np[pos])
        v = vals[int(perm_np[pos])]
        if v is not None:
            if out[g] is None or (op == "min" and v < out[g]) or \
                    (op == "max" and v > out[g]):
                out[g] = v
    final = TpuColumnVector.from_arrow(
        pa.array(out, type=type_to_arrow(fn.dtype)))
    return {"__final": final}


def _segment_bloom(fn, col, seg_ids, g_cap, capacity, num_rows, perm):
    """Per-group bloom blobs (host bit math over device-hashed longs; the
    reference's JNI BloomFilter kernel analogue). Empty group → null blob."""
    import pyarrow as pa
    from ..columnar.vector import audited_sync
    mask_np = np.zeros(capacity, dtype=bool)
    mask_np[:num_rows] = True
    perm_np = audited_sync(perm, "fetch")[:capacity]
    seg_np = audited_sync(seg_ids, "fetch")[:capacity]
    valid = mask_np[perm_np]
    if col.validity is not None:
        valid &= audited_sync(col.validity, "fetch")[perm_np]
    vals = audited_sync(col.data, "fetch")[perm_np].astype(np.int64)
    # group rows once via a segment sort instead of one full scan per group
    vv = vals[valid]
    ss = seg_np[valid]
    order = np.argsort(ss, kind="stable")
    ss, vv = ss[order], vv[order]
    bounds = np.searchsorted(ss, np.arange(g_cap + 1))
    blobs: List[Optional[bytes]] = []
    for g in range(g_cap):
        lo, hi = bounds[g], bounds[g + 1]
        blobs.append(fn.build(vv[lo:hi]) if hi > lo else None)
    final = TpuColumnVector.from_arrow(pa.array(blobs, type=pa.binary()))
    return {"__final": final}


def _segment_covar(fn, cols, seg_ids, g_cap: int, capacity: int,
                   num_rows: int, perm):
    cx, cy = cols
    mask = row_mask(num_rows, capacity)
    vx = (cx.validity & mask) if cx.validity is not None else mask
    vy = (cy.validity & mask) if cy.validity is not None else mask
    pair = jnp.take(vx & vy, perm)
    sx_scale = (10.0 ** -cx.dtype.scale) if isinstance(cx.dtype, DecimalType) else 1.0
    sy_scale = (10.0 ** -cy.dtype.scale) if isinstance(cy.dtype, DecimalType) else 1.0
    x = jnp.where(pair, jnp.take(cx.data, perm), 0).astype(jnp.float64) * sx_scale
    y = jnp.where(pair, jnp.take(cy.data, perm), 0).astype(jnp.float64) * sy_scale
    z = lambda: jnp.zeros((g_cap,), jnp.float64)
    return {
        "n": jnp.zeros((g_cap,), jnp.int64).at[seg_ids].add(
            pair.astype(jnp.int64), mode="drop"),
        "sx": z().at[seg_ids].add(x, mode="drop"),
        "sy": z().at[seg_ids].add(y, mode="drop"),
        "sxy": z().at[seg_ids].add(x * y, mode="drop"),
        "sx2": z().at[seg_ids].add(x * x, mode="drop"),
        "sy2": z().at[seg_ids].add(y * y, mode="drop"),
    }


def _evaluate_agg(fn: AggregateFunction, state: Dict[str, jnp.ndarray],
                  n_groups: int, cap: int) -> TpuColumnVector:
    gmask = row_mask(n_groups, cap)
    op = fn.update_op
    if "__final" in state:  # host-assembled column (strings, nested, blobs)
        f = state["__final"]
        from ..columnar.batch import _repad
        if f.capacity < cap:
            f = _repad(f, cap)
        return TpuColumnVector(f.dtype, f.data, f.validity, n_groups,
                               offsets=f.offsets, child=f.child,
                               host_data=f.host_data,
                               host_capacity=f.host_capacity,
                               children=f.children)
    if op == "count":
        return TpuColumnVector(LongT, state["count"], None, n_groups)
    if op == "sum":
        valid = (state["nonnull"] > 0) & gmask
        return TpuColumnVector(fn.dtype, state["sum"], valid, n_groups)
    if op == "avg":
        n = state["count"]
        valid = (n > 0) & gmask
        data = state["sum"] / jnp.where(n > 0, n, 1).astype(jnp.float64)
        return TpuColumnVector(DoubleT, jnp.where(valid, data, 0.0), valid, n_groups)
    if op in ("min", "max"):
        valid = (state["nonnull"] > 0) & gmask
        data = jnp.where(valid, state[op], jnp.zeros((), state[op].dtype))
        return TpuColumnVector(fn.dtype, data, valid, n_groups)
    if op in ("first", "last"):
        valid = state[f"{op}_valid"] & gmask
        return TpuColumnVector(fn.dtype, state[op], valid, n_groups)
    if op in ("stddev_samp", "stddev_pop", "var_samp", "var_pop"):
        n = state["n"].astype(jnp.float64)
        s, s2 = state["sum"], state["sumsq"]
        m2 = s2 - (s * s) / jnp.where(n > 0, n, 1.0)
        ddof = 1.0 if op.endswith("samp") else 0.0
        denom = n - ddof
        ok = denom > 0
        var = jnp.where(ok, m2 / jnp.where(ok, denom, 1.0), 0.0)
        var = jnp.maximum(var, 0.0)
        out = jnp.sqrt(var) if op.startswith("stddev") else var
        valid = ok & (n > 0) & gmask
        return TpuColumnVector(DoubleT, jnp.where(valid, out, 0.0), valid, n_groups)
    if op in ("collect_list", "collect_set"):
        child = state["__list_child"]
        offsets = state["__list_offsets"]
        return TpuColumnVector(fn.dtype, child.data, None, n_groups,
                               offsets=offsets, child=child)
    if op in ("percentile", "approx_percentile"):
        n = state["n"]
        valid = (n > 0) & gmask
        ps = [state[f"p{k}"] for k in range(len(fn.percentages))]
        if not fn.is_array:
            data = jnp.where(valid, ps[0], jnp.zeros((), ps[0].dtype))
            elem_t = DoubleT if op == "percentile" else fn.dtype
            return TpuColumnVector(elem_t, data, valid, n_groups)
        k = len(ps)
        stacked = jnp.stack(ps, axis=1).reshape((cap * k,))  # row-major per group
        elem_t = fn.dtype.element_type
        child = TpuColumnVector(elem_t, stacked, None, n_groups * k)
        offsets = (jnp.arange(cap + 1, dtype=jnp.int32) * k)
        return TpuColumnVector(fn.dtype, child.data, valid, n_groups,
                               offsets=offsets, child=child)
    if op in ("covar_samp", "covar_pop", "corr"):
        n = state["n"].astype(jnp.float64)
        sx, sy = state["sx"], state["sy"]
        sxy, sx2, sy2 = state["sxy"], state["sx2"], state["sy2"]
        safe_n = jnp.where(n > 0, n, 1.0)
        cov = sxy - sx * sy / safe_n
        if op == "covar_pop":
            valid = (state["n"] > 0) & gmask
            out = cov / safe_n
        elif op == "covar_samp":
            valid = (state["n"] > 1) & gmask
            out = cov / jnp.where(n > 1, n - 1.0, 1.0)
        else:  # corr: null when n<2 or either variance is 0 (Spark divide-null);
            # NaN inputs propagate as NaN (denom != 0 holds for NaN)
            mx2 = sx2 - sx * sx / safe_n
            my2 = sy2 - sy * sy / safe_n
            denom = jnp.sqrt(jnp.maximum(mx2, 0.0) * jnp.maximum(my2, 0.0))
            valid = (state["n"] > 1) & (denom != 0) & gmask
            out = cov / jnp.where(denom != 0, denom, 1.0)
        return TpuColumnVector(DoubleT, jnp.where(valid, out, 0.0), valid, n_groups)
    raise NotImplementedError(op)


def _global_mergeable(fn) -> bool:
    """Whether the ungrouped chunked-merge path can combine this aggregate's
    partial states (order-sensitive and collection aggs are excluded; they keep
    the concat path)."""
    op = fn.update_op
    if op in ("count", "sum", "avg", "stddev_samp", "stddev_pop", "var_samp",
              "var_pop", "covar_samp", "covar_pop", "corr"):
        return True
    if op in ("min", "max", "first", "last"):
        from ..types import is_fixed_width
        child = fn.children[0] if fn.children else None
        return child is None or is_fixed_width(child.dtype)
    return False


def _merge_global_states(fn, states: List[Dict]) -> Dict:
    """Merge per-chunk one-group partial states into a single state dict (the
    reference's merge aggregation expressions, aggregateFunctions.scala)."""
    if len(states) == 1:
        return states[0]
    op = fn.update_op
    stk = {k: jnp.stack([s[k] for s in states]) for k in states[0]}
    if op == "count":
        return {"count": stk["count"].sum(0)}
    if op == "sum":
        return {"sum": stk["sum"].sum(0), "nonnull": stk["nonnull"].sum(0)}
    if op == "avg":
        return {"sum": stk["sum"].sum(0), "count": stk["count"].sum(0)}
    if op in ("stddev_samp", "stddev_pop", "var_samp", "var_pop") \
            or op in ("covar_samp", "covar_pop", "corr"):
        return {k: v.sum(0) for k, v in stk.items()}
    if op in ("min", "max"):
        red, nn = stk[op], stk["nonnull"]
        nonnull = nn.sum(0)
        has = nn > 0
        if jnp.issubdtype(red.dtype, jnp.floating):
            # chunk red is NaN iff (min) the chunk was all-NaN / (max) any NaN
            isnan = jnp.isnan(red)
            if op == "max":
                neutral = jnp.asarray(-np.inf, red.dtype)
                m = jnp.where(has & ~isnan, red, neutral).max(0)
                m = jnp.where((has & isnan).any(0),
                              jnp.asarray(np.nan, red.dtype), m)
            else:
                neutral = jnp.asarray(np.inf, red.dtype)
                m = jnp.where(has & ~isnan, red, neutral).min(0)
                m = jnp.where(~(has & ~isnan).any(0) & (nonnull > 0),
                              jnp.asarray(np.nan, red.dtype), m)
            return {op: m, "nonnull": nonnull}
        info = np.iinfo(np.asarray(jnp.zeros((), red.dtype)).dtype)
        neutral = jnp.asarray(info.max if op == "min" else info.min, red.dtype)
        clean = jnp.where(has, red, neutral)
        m = clean.min(0) if op == "min" else clean.max(0)
        return {op: m, "nonnull": nonnull}
    if op in ("first", "last"):
        has, vals, vvalid = stk["has"], stk[op], stk[f"{op}_valid"]
        nch = has.shape[0]
        idxs = jnp.arange(nch)[:, None]
        sel = jnp.where(has, idxs, nch).min(0) if op == "first" \
            else jnp.where(has, idxs, -1).max(0)
        sel_c = jnp.clip(sel, 0, nch - 1)[None, :]
        return {op: jnp.take_along_axis(vals, sel_c, 0)[0],
                "has": has.any(0),
                f"{op}_valid": jnp.take_along_axis(vvalid, sel_c, 0)[0]}
    raise NotImplementedError(f"merge of {op}")


class TpuHashAggregateExec(TpuExec):
    """Sort-based grouped aggregation on device (complete mode)."""

    def __init__(self, grouping: Sequence[Expression],
                 aggregates: Sequence[Expression], child: PhysicalPlan,
                 output: List[AttributeReference], mode: str = "complete",
                 per_partition: bool = False):
        super().__init__([child])
        self.grouping = bind_all(list(grouping), child.output)
        self.aggregates = [bind_references(a, child.output) for a in aggregates]
        self._output = output
        self.mode = mode
        self.per_partition = per_partition

    @property
    def output(self):
        return self._output

    def num_partitions(self) -> int:
        return self.children[0].num_partitions() if self.per_partition else 1

    def node_desc(self) -> str:
        return f"TpuHashAggregate[keys={len(self.grouping)}]"

    def additional_metrics(self):
        return {"sortTime": "MODERATE", "reduceTime": "MODERATE",
                "numGroups": "DEBUG", "opFusedAggBatches": "DEBUG",
                "sortFallbacks": "DEBUG"}

    def query_counters(self):
        return [("agg.sort_fallback", self.metrics["sortFallbacks"])]

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        child = self.children[0]
        batches: List[TpuColumnarBatch] = []
        if self.per_partition:
            batches.extend(child.execute_partition(idx, ctx))
        else:
            for p in range(child.num_partitions()):
                batches.extend(child.execute_partition(p, ctx))
        yield from self.aggregate_batches(batches, ctx)

    def aggregate_batches(self, batches: List[TpuColumnarBatch],
                          ctx: TaskContext) -> Iterator:
        """Aggregate already-collected input batches — the entry point a
        fused stage segment (execs/fusion.py) uses when the aggregate is its
        trailing stage, and the body of the normal per-partition path."""
        from ..config import BATCH_SIZE_ROWS
        agg_fns, result_exprs = split_result_exprs(self.aggregates)
        if not batches:
            if not self.grouping:
                yield self._empty_global_result(agg_fns, result_exprs, ctx)
            return
        max_rows = ctx.conf.get(BATCH_SIZE_ROWS)
        total = sum(b.num_rows for b in batches)
        if self.grouping and total > max_rows:
            # overflow: out-of-core sort by the grouping keys, then aggregate
            # key-boundary-aligned slices — the reference's sort-based
            # fallback (GpuAggregateExec.scala:757, GpuOutOfCoreSortIterator
            # reuse); no group straddles a slice so no state merge is needed
            self.metrics["sortFallbacks"].add(1)
            yield from self._sort_fallback(batches, agg_fns, result_exprs,
                                           ctx, max_rows)
            return
        if not self.grouping and total > max_rows and len(batches) > 1 \
                and all(_global_mergeable(fn) for fn in agg_fns):
            # ungrouped overflow: per-chunk partial states merged into one
            # final state (the reference's update→merge decomposition,
            # GpuAggregateExec.scala GpuMergeAggregateIterator) — never
            # concatenates the whole input on device
            yield self._global_chunked(batches, agg_fns, result_exprs, ctx,
                                       max_rows)
            return
        batch = concat_batches(batches) if len(batches) > 1 else batches[0]
        from ..memory.retry import with_retry_no_split
        from ..memory.spill import SpillableColumnarBatch
        yield with_retry_no_split(
            SpillableColumnarBatch(batch),
            lambda b: self._aggregate_batch(b, agg_fns, result_exprs, ctx))

    def _sort_fallback(self, batches, agg_fns, result_exprs, ctx,
                       max_rows: int) -> Iterator:
        from ..config import (SHUFFLE_PIPELINE_ENABLED,
                              SHUFFLE_PIPELINE_PREFETCH)
        from ..plan.logical import SortOrder
        from ..utils.pipeline import prefetch_iterator
        from .oocsort import OutOfCoreSorter
        order = [SortOrder(g, True, True) for g in self.grouping]
        ooc = OutOfCoreSorter(order, ctx)
        try:
            depth = (ctx.conf.get(SHUFFLE_PIPELINE_PREFETCH)
                     if ctx.conf.get(SHUFFLE_PIPELINE_ENABLED) else 0)
            # slice k+1's merge+gather dispatches overlap slice k's
            # aggregation (same pipelining discipline as the shuffle read)
            slices = prefetch_iterator(
                ooc.iter_sorted(max_rows, group_boundaries=True), depth)
            try:
                with self.metrics["sortTime"].timed():
                    for b in batches:
                        ooc.add_batch(b)
                for sl in slices:
                    yield self._aggregate_batch(sl, agg_fns, result_exprs,
                                                ctx)
            finally:
                slices.close()  # stop the prefetch worker FIRST
        finally:
            ooc.close()

    def _eval_agg_input(self, fn, batch: TpuColumnarBatch, ctx: TaskContext):
        if len(fn.children) >= 2:
            return tuple(
                to_column(c.eval_tpu(batch, ctx.eval_ctx), batch, c.dtype)
                for c in fn.children)
        if fn.children:
            return to_column(fn.children[0].eval_tpu(batch, ctx.eval_ctx),
                             batch, fn.children[0].dtype)
        return None

    def _global_chunked(self, batches, agg_fns, result_exprs, ctx,
                        max_rows: int) -> TpuColumnarBatch:
        """Ungrouped aggregate over the row budget: chunk the input, compute a
        one-group partial state per chunk, merge states, finalize once."""
        chunks: List[List[TpuColumnarBatch]] = []
        cur: List[TpuColumnarBatch] = []
        cur_rows = 0
        for b in batches:
            if cur and cur_rows + b.num_rows > max_rows:
                chunks.append(cur)
                cur, cur_rows = [], 0
            cur.append(b)
            cur_rows += b.num_rows
        if cur:
            chunks.append(cur)
        g_cap = bucket_capacity(1)
        per_fn: List[List[Dict]] = [[] for _ in agg_fns]
        from ..memory.retry import with_retry_no_split
        from ..memory.spill import SpillableColumnarBatch

        def chunk_states(chunk: TpuColumnarBatch) -> List[Dict]:
            cap, n = chunk.capacity, chunk.num_rows
            perm = jnp.arange(cap, dtype=jnp.int32)
            seg_ids = jnp.zeros((cap,), jnp.int32)
            return [_segment_update(fn, self._eval_agg_input(fn, chunk, ctx),
                                    seg_ids, g_cap, cap, n, perm)
                    for fn in agg_fns]

        with self.metrics["reduceTime"].timed():
            for group in chunks:
                chunk = concat_batches(group) if len(group) > 1 else group[0]
                # same OOM-retry discipline as the in-core path: the chunk is
                # spillable while its partial state is computed
                states = with_retry_no_split(SpillableColumnarBatch(chunk),
                                             chunk_states)
                for i in range(len(agg_fns)):
                    per_fn[i].append(states[i])
            states = [_merge_global_states(fn, sts)
                      for fn, sts in zip(agg_fns, per_fn)]
            agg_cols = [_evaluate_agg(fn, st, 1, g_cap)
                        for fn, st in zip(agg_fns, states)]
        agg_batch = TpuColumnarBatch(agg_cols, 1)
        final_cols = []
        for expr, attr in zip(result_exprs, self._output):
            bound = _bind_agg_refs(expr, None, 0)
            final_cols.append(to_column(bound.eval_tpu(agg_batch, ctx.eval_ctx),
                                        agg_batch, attr.dtype))
        return TpuColumnarBatch(final_cols, 1, [a.name for a in self._output])

    def _aggregate_batch(self, batch: TpuColumnarBatch, agg_fns, result_exprs,
                         ctx: TaskContext) -> TpuColumnarBatch:
        """Sort phase + reduce phase, each running as ONE cached executable
        when it traces (execs/opjit.py) and falling back to the eager op
        chain otherwise — the two phases gate independently (string group
        keys can still jit the reduce; collect-style aggregates can still
        jit the sort). Results are identical either way."""
        from . import opjit
        cap = batch.capacity
        use_jit = opjit.enabled(ctx.eval_ctx)
        if use_jit and self.grouping:
            fused = self._fused_aggregate_batch(batch, agg_fns, result_exprs,
                                                ctx)
            if fused is not None:
                return fused
        n = batch.num_rows
        perm = seg_ids = is_new = key_rows = None
        key_cols: List[TpuColumnVector] = []
        if self.grouping:
            plan = None
            dc = None
            if use_jit:
                # string keys carrying a device dict_encoding trace the
                # sort phase over their int32 codes (ONE launch) instead
                # of dropping to the eager chain at the string boundary
                dc = self._dict_coded_sort_inputs(batch)
                sort_grouping, sort_batch = dc if dc is not None \
                    else (self.grouping, batch)
                with self.metrics["sortTime"].timed():
                    plan = opjit.agg_sort_plan(sort_grouping, sort_batch,
                                               ctx.eval_ctx, self.metrics)
            if plan is not None:
                perm, seg_ids, is_new, n_groups, key_cols = plan
                if dc is not None:
                    # the traced key columns are the CODES; the output key
                    # columns are the real columns (every grouping expr in
                    # the dc path is a bare reference, so this is free)
                    key_cols = [batch.columns[g.ordinal]
                                for g in self.grouping]
            else:
                key_cols = [to_column(g.eval_tpu(batch, ctx.eval_ctx),
                                      batch, g.dtype)
                            for g in self.grouping]
                with self.metrics["sortTime"].timed():
                    enc = encode_group_keys(key_cols, n, cap)
                    perm = lex_sort_permutation(enc, n, cap)
                    is_new, seg_ids, ng = segment_boundaries(
                        enc, perm, row_mask(n, cap))
                    n_groups = int(ng)
            self.metrics["numGroups"].add(n_groups)
        else:
            n_groups = 1
        g_cap = bucket_capacity(max(n_groups, 1))
        agg_cols = None
        if use_jit:
            with self.metrics["reduceTime"].timed():
                red = opjit.agg_reduce(agg_fns, batch, perm, seg_ids, is_new,
                                       n_groups, g_cap, ctx.eval_ctx,
                                       self.metrics)
            if red is not None:
                # perm/seg_ids/is_new were donated to the reduce program
                agg_cols, key_rows = red
        if agg_cols is None:
            if perm is None:  # ungrouped, reduce ran eager
                perm = jnp.arange(cap, dtype=jnp.int32)
                seg_ids = jnp.zeros((cap,), jnp.int32)
            in_cols: List[Optional[TpuColumnVector]] = [
                self._eval_agg_input(fn, batch, ctx) for fn in agg_fns]
            with self.metrics["reduceTime"].timed():
                states = [_segment_update(fn, col, seg_ids, g_cap, cap, n,
                                          perm)
                          for fn, col in zip(agg_fns, in_cols)]
                agg_cols = [_evaluate_agg(fn, st, n_groups, g_cap)
                            for fn, st in zip(agg_fns, states)]
        # group key output: first row of each segment
        out_key_cols = []
        if self.grouping:
            if key_rows is None:
                first_pos = jnp.zeros((g_cap,), jnp.int32).at[
                    jnp.where(is_new, seg_ids, g_cap)].set(
                    jnp.arange(cap, dtype=jnp.int32), mode="drop")
                key_rows = jnp.take(perm, first_pos)
            key_batch = TpuColumnarBatch(key_cols, n)
            gathered = gather(key_batch, key_rows, n_groups, out_capacity=g_cap)
            out_key_cols = gathered.columns
        # result projection over agg columns
        agg_batch = TpuColumnarBatch(list(out_key_cols) + agg_cols, n_groups)
        ng = len(self.grouping)
        final_cols = list(out_key_cols)
        bound = [_bind_agg_refs(expr, None, ng, self.grouping)
                 for expr in result_exprs]
        final_cols.extend(opjit.eval_exprs(
            bound, [attr.dtype for attr in self._output[ng:]], agg_batch,
            ctx.eval_ctx, self.metrics))
        return TpuColumnarBatch(final_cols, n_groups,
                                [a.name for a in self._output])

    def _dict_coded_sort_inputs(self, batch: TpuColumnarBatch):
        """Traced sort-phase inputs for STRING group keys: when every
        grouping expr is a bare column reference and every string key
        column carries a device `dict_encoding` (parquet dictionary pages,
        the dictionary exchange's decode-on-read), the sort phase traces
        over int32 code columns appended to a widened batch — the opjit
        key-encode program consumes the codes directly, so string-keyed
        aggregation stays device-resident with the same ONE-launch sort
        phase fixed-width keys get. Returns (grouping, batch) with the
        string keys substituted, or None (caller uses the original)."""
        from ..types import IntegerType
        if not any(isinstance(g.dtype, StringType) for g in self.grouping):
            return None
        if not all(isinstance(g, AttributeReference)
                   and g.ordinal is not None
                   and 0 <= g.ordinal < len(batch.columns)
                   for g in self.grouping):
            return None
        new_cols = list(batch.columns)
        new_grouping: List[AttributeReference] = []
        for g in self.grouping:
            if not isinstance(g.dtype, StringType):
                new_grouping.append(g)
                continue
            col = batch.columns[g.ordinal]
            de = getattr(col, "dict_encoding", None)
            if de is None:
                return None
            new_grouping.append(AttributeReference(
                f"{g.name}__dictcode", IntegerType(), g.nullable,
                ordinal=len(new_cols)))
            new_cols.append(TpuColumnVector(IntegerType(), de[0],
                                            col.validity, batch.rows_lazy))
        return new_grouping, TpuColumnarBatch(new_cols, batch.rows_lazy)

    def _fused_aggregate_batch(self, batch: TpuColumnarBatch, agg_fns,
                               result_exprs,
                               ctx: TaskContext) -> Optional[TpuColumnarBatch]:
        """The whole grouped update as ONE launch (opjit.agg_stage_program,
        spark.rapids.tpu.opjit.fuseAggs): the group table is sized to the
        batch's capacity bucket so the group count stays a DEVICE scalar —
        no sort→reduce phase-boundary sync. Falls back (None) to the
        two-phase path for unsupported aggregates with identical results."""
        from ..config import DEFERRED_COMPACTION, OPJIT_FUSE_AGGS
        from . import opjit
        if not ctx.conf.get(OPJIT_FUSE_AGGS):
            return None
        with self.metrics["reduceTime"].timed():
            fused = opjit.agg_stage_program(self.grouping, agg_fns, batch,
                                            ctx.eval_ctx, self.metrics)
        if fused is None:
            return None
        key_cols, agg_cols, ng_dev = fused
        self.metrics["numGroups"].add_lazy(ng_dev)
        self.metrics["opFusedAggBatches"].add(1)
        ng_rows = ng_dev
        if not ctx.conf.get(DEFERRED_COMPACTION):
            from ..columnar.vector import audited_sync_int
            ng_rows = audited_sync_int(ng_dev, "rows")
        agg_batch = TpuColumnarBatch(list(key_cols) + list(agg_cols), ng_rows)
        nk = len(self.grouping)
        final_cols = list(agg_batch.columns[:nk])
        bound = [_bind_agg_refs(expr, None, nk, self.grouping)
                 for expr in result_exprs]
        final_cols.extend(opjit.eval_exprs(
            bound, [attr.dtype for attr in self._output[nk:]], agg_batch,
            ctx.eval_ctx, self.metrics))
        return TpuColumnarBatch(final_cols, agg_batch.rows_lazy,
                                [a.name for a in self._output])

    def _empty_global_result(self, agg_fns, result_exprs, ctx):
        """Global aggregate over zero rows: count=0, others null (Spark)."""
        cols = []
        for fn in agg_fns:
            if isinstance(fn, Count):
                cols.append(TpuColumnVector.from_numpy(LongT, np.zeros(1, np.int64)))
            elif fn.update_op in ("collect_list", "collect_set"):
                import pyarrow as pa
                from ..types import to_arrow as type_to_arrow
                cols.append(TpuColumnVector.from_arrow(
                    pa.array([[]], type=type_to_arrow(fn.dtype))))
            else:
                cols.append(TpuColumnVector.from_scalar(None, fn.dtype, 1))
        agg_batch = TpuColumnarBatch(cols, 1)
        final = []
        for expr, attr in zip(result_exprs, self._output):
            bound = _bind_agg_refs(expr, None, 0)
            final.append(to_column(bound.eval_tpu(agg_batch, ctx.eval_ctx),
                                   agg_batch, attr.dtype))
        return TpuColumnarBatch(final, 1, [a.name for a in self._output])



