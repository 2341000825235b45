"""Whole-stage compiled aggregation: scan→filter→project→group-by fused into
ONE jitted XLA program per batch shape.

This is the framework's central TPU-first execution feature. The reference
accelerates the same pipeline as a chain of per-expression cuDF kernel
launches fused only by iterator structure (GpuAggFirstPassIterator,
GpuAggregateExec.scala:549; tiered projection basicPhysicalOperators.scala:
350). On TPU the dominant cost of that shape is dispatch latency — every
`columnarEval` is a host→device round trip — so the winning design is the
opposite: trace the whole stage once and let XLA fuse filter masks, projected
measures, and the grouped reduction into a single executable (no compaction,
no per-op dispatch, no host syncs in the hot loop).

Eligibility (anything else falls back to the general sort-based aggregate):
  * group keys are direct column references of integral/date/bool/string
    type that pass through the stage unchanged; string keys are
    dictionary-encoded host-side ONCE per column object (memoized), so
    repeated runs stay fully on device;
  * key domains are small (≤ spark.rapids.tpu.agg.compiled.maxGroups after
    combining); integral domains come from per-column min/max stats
    (memoized on the column), checked batch by batch as the source is
    pulled, with in-trace out-of-range detection behind them. A stage that
    gives up on what it reads in its input HANDS what it pulled to the
    general path (TpuStageSourceExec): its source never produces a batch a
    second time;
  * aggregates are sum/count/avg/min/max over fixed-width non-decimal,
    non-bool inputs;
  * every filter/project expression is device-pure (its rule is not
    host_assisted) and fixed-width; ANSI mode disables the pass (ANSI
    checks host-sync inside eval).

The grouped reduction uses a direct-indexed group table: combined key code =
Σ code_k · stride_k over a static domain, accumulated chunk-by-chunk with a
`lax.scan` whose chunk size scales inversely with the table width (bounded
working set, no scatter — TPU scatter serializes under index collisions).
The tiny group table also ELIMINATES the partial/final shuffle: partials
merge on one shard.

Compiled executables are cached process-wide keyed by a structural
fingerprint of the stage (expressions by class/ordinal/literal, dtypes,
capacity, key-domain sizes), so re-planning the same query re-uses the
compiled program instead of re-tracing.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.batch import TpuColumnarBatch, _repad, compact
from ..columnar.vector import TpuColumnVector, bucket_capacity, row_mask
from ..expressions.aggregates import (AggregateFunction, Average, Count, Max,
                                      Min, Sum)
from ..expressions.base import (Alias, AttributeReference, Expression,
                                Literal, to_column)
from ..obs import tracer as _obs
from ..types import (BooleanType, DataType, DateType, DecimalType,
                     FloatType, DoubleType, IntegralType, StringType,
                     is_fixed_width)
from .base import PhysicalPlan, TaskContext, TpuExec

_SUPPORTED_AGGS = (Sum, Count, Average, Min, Max)


# ---------------------------------------------------------------------------
# eligibility
# ---------------------------------------------------------------------------


def _device_pure(expr: Expression) -> bool:
    """Expression evaluates entirely on device (traceable into the stage)."""
    from ..columnar.vector import device_layout_ok
    from ..plan.typechecks import all_expr_rules
    rules = all_expr_rules()

    def ok(e: Expression) -> bool:
        if not isinstance(e, (Literal, AttributeReference, Alias)):
            r = rules.get(type(e))
            if r is None or r.host_assisted:
                return False
        if isinstance(e.dtype, (StringType, DecimalType)):
            return False
        if not is_fixed_width(e.dtype):
            return False
        if not device_layout_ok(e.dtype):
            return False
        return all(ok(c) for c in e.children)

    return ok(expr)


def _key_eligible(dtype: DataType) -> bool:
    return isinstance(dtype, (IntegralType, DateType, BooleanType, StringType))


def _agg_eligible(fn: AggregateFunction) -> bool:
    if not isinstance(fn, _SUPPORTED_AGGS):
        return False
    if getattr(fn, "distinct", False):
        return False
    if fn.children:
        child = fn.children[0]
        if isinstance(child.dtype, (DecimalType, BooleanType)):
            return False
        if not _device_pure(child):
            return False
    return True


def _fingerprint(e: Expression) -> str:
    """Structural fingerprint (expr-id free) for the compile cache key."""
    name = type(e).__name__
    extra = ""
    if isinstance(e, Literal):
        extra = f"={e.value!r}"
    elif isinstance(e, AttributeReference):
        extra = f"@{e.ordinal}"
    elif isinstance(e, Alias):
        extra = ""
    kids = ",".join(_fingerprint(c) for c in e.children)
    return f"{name}{extra}:{type(e.dtype).__name__}({kids})"


# ---------------------------------------------------------------------------
# pattern extraction
# ---------------------------------------------------------------------------


class _StageSpec:
    """Extracted pattern: source → layers (bottom-up) → grouping/aggs."""

    #: plan-cache clone protocol (execs/base.py _clone_spec): the spec's
    #: layer expressions must see re-bound parameter literals on a hit
    _PLAN_SPEC = True

    def __init__(self, source, layers, grouping, key_source_ordinals,
                 agg_fns, result_exprs, output, needed_source_ordinals):
        self.source = source
        self.layers = layers  # ("filter", cond) | ("project", exprs, outs)
        self.grouping = grouping
        self.key_source_ordinals = key_source_ordinals
        self.agg_fns = agg_fns
        self.result_exprs = result_exprs
        self.output = output
        self.needed_source_ordinals = needed_source_ordinals

    def cache_key(self, cap: int, domain_sizes: Tuple[int, ...]) -> Tuple:
        parts = []
        for layer in self.layers:
            if layer[0] == "filter":
                parts.append("F" + _fingerprint(layer[1]))
            else:
                parts.append("P" + ";".join(_fingerprint(e)
                                            for e in layer[1]))
        parts.append("G" + ";".join(_fingerprint(g) for g in self.grouping))
        parts.append("A" + ";".join(_fingerprint(f) for f in self.agg_fns))
        parts.append("S" + ";".join(type(a.dtype).__name__
                                    for a in self.source.output))
        parts.append("N" + ",".join(map(str, self.needed_source_ordinals)))
        parts.append("K" + ",".join(map(str, self.key_source_ordinals)))
        return ("|".join(parts), cap, domain_sizes)


def _identity_source_ordinal(final_ordinal: int, layers) -> Optional[int]:
    """Walk a final-layer ordinal down identity projections to the source
    ordinal; None when any layer computes rather than forwards it."""
    ordinal = final_ordinal
    for layer in reversed(layers):  # top-down
        if layer[0] == "filter":
            continue
        exprs = layer[1]
        if ordinal >= len(exprs):
            return None
        e = exprs[ordinal]
        if isinstance(e, Alias):
            e = e.children[0]
        if not isinstance(e, AttributeReference) or e.ordinal is None:
            return None
        ordinal = e.ordinal
    return ordinal


def _refs(e: Expression) -> List[int]:
    return [a.ordinal for a in
            e.collect(lambda x: isinstance(x, AttributeReference))
            if a.ordinal is not None]


def try_extract_stage(agg) -> Optional["_StageSpec"]:
    """Match TpuHashAggregateExec over [exchange/reader] over project/filter
    chain over a device source; None when ineligible."""
    from ..shuffle.exchange import (TpuShuffleExchangeExec,
                                    TpuShuffleReaderExec)
    from .aggregates import TpuHashAggregateExec, split_result_exprs
    from .basic import (TpuCoalesceBatchesExec, TpuFilterExec, TpuProjectExec)

    if not isinstance(agg, TpuHashAggregateExec):
        return None
    agg_fns, result_exprs = split_result_exprs(agg.aggregates)
    if not agg_fns or not all(_agg_eligible(f) for f in agg_fns):
        return None
    grouping = list(agg.grouping)
    if not all(isinstance(g, AttributeReference) and g.ordinal is not None
               and _key_eligible(g.dtype) for g in grouping):
        return None

    node = agg.children[0]
    # an exchange below a grouped aggregation only redistributes rows; the
    # compiled stage aggregates globally, so it is skipped outright
    while isinstance(node, (TpuShuffleReaderExec, TpuShuffleExchangeExec,
                            TpuCoalesceBatchesExec)):
        if isinstance(node, TpuShuffleExchangeExec) \
                and node.partitioning != "hash":
            return None
        node = node.children[0]

    chain: List[Tuple] = []  # top-down
    while isinstance(node, (TpuProjectExec, TpuFilterExec,
                            TpuCoalesceBatchesExec)):
        if isinstance(node, TpuProjectExec):
            for e in node.exprs:
                inner = e.children[0] if isinstance(e, Alias) else e
                if isinstance(inner, AttributeReference):
                    continue  # identity forward (strings allowed here)
                if not _device_pure(e):
                    return None
            chain.append(("project", list(node.exprs), list(node.output)))
        elif isinstance(node, TpuFilterExec):
            if not _device_pure(node.condition):
                return None
            chain.append(("filter", node.condition))
        node = node.children[0]
    if not isinstance(node, TpuExec):
        return None
    layers = list(reversed(chain))  # bottom-up execution order

    # group keys must forward untouched to a source column
    key_source_ordinals = []
    for g in grouping:
        src = _identity_source_ordinal(g.ordinal, layers)
        if src is None or src >= len(node.output):
            return None
        key_source_ordinals.append(src)

    # needed source ordinals (column pruning for the stage inputs)
    cur = set(g.ordinal for g in grouping)
    for f in agg_fns:
        for c in f.children:
            cur.update(_refs(c))
    for layer in reversed(layers):  # top-down
        if layer[0] == "filter":
            cur.update(_refs(layer[1]))
        else:
            nxt = set()
            for o in cur:
                if o < len(layer[1]):
                    nxt.update(_refs(layer[1][o]))
            cur = nxt
    needed = cur

    # needed source columns must be fixed-width, except string group keys
    # (dictionary-coded outside the trace); a string column used anywhere
    # else disqualifies the stage
    key_set = set(key_source_ordinals)
    for o in sorted(needed):
        dt = node.output[o].dtype
        if isinstance(dt, StringType):
            if o not in key_set:
                return None
        elif not is_fixed_width(dt) or isinstance(dt, DecimalType):
            return None

    return _StageSpec(node, layers, grouping, key_source_ordinals, agg_fns,
                      result_exprs, list(agg.output),
                      sorted(needed | key_set))


# ---------------------------------------------------------------------------
# key statistics (memoized on column objects)
# ---------------------------------------------------------------------------


class _KeyDomain:
    """Static per-key domain: ints carry [lo, hi]; strings the global
    dictionary. `size` includes the trailing null slot."""

    def __init__(self, dtype: DataType):
        self.dtype = dtype
        self.lo: Optional[int] = None
        self.hi: Optional[int] = None
        self.values: List = []
        self.value_code: Dict = {}

    @property
    def size(self) -> int:
        if isinstance(self.dtype, StringType):
            return len(self.values) + 1
        if isinstance(self.dtype, BooleanType):
            return 3
        if self.lo is None:
            return 2  # all-null key column: one dummy value slot + null slot
        return int(self.hi - self.lo) + 2


def _int_stats(col: TpuColumnVector) -> Tuple[Optional[int], Optional[int]]:
    """min/max of valid rows (one sync; memoized on the column object)."""
    memo = getattr(col, "_gb_range", None)
    if memo is not None:
        return memo
    mask = col.validity_or_true()
    data = col.data.astype(jnp.int64)
    big = jnp.iinfo(jnp.int64).max
    lo = jnp.min(jnp.where(mask, data, big))
    hi = jnp.max(jnp.where(mask, data, -big - 1))
    n = int(jnp.sum(mask))
    stats = (None, None) if n == 0 else (int(lo), int(hi))
    try:
        object.__setattr__(col, "_gb_range", stats)
    except Exception:
        pass
    return stats


def _string_codes(col: TpuColumnVector, domain: _KeyDomain) -> jnp.ndarray:
    """Global dictionary codes for a string key column (device int32; nulls
    and padding carry -1). The local encode is memoized per column object;
    the local→global remap is a cheap host lookup over the small dict."""
    memo = getattr(col, "_gb_dict", None)
    if memo is None:
        import pyarrow as pa
        import pyarrow.compute as pc
        arr = col.to_arrow()
        enc = pc.dictionary_encode(arr)
        if isinstance(enc, pa.ChunkedArray):
            enc = enc.combine_chunks()
        values = enc.dictionary.to_pylist()
        codes = np.asarray(enc.indices.fill_null(-1)
                           .to_numpy(zero_copy_only=False)).astype(np.int32)
        buf = np.full(col.capacity, -1, np.int32)
        buf[: len(codes)] = codes
        memo = (values, jnp.asarray(buf))
        try:
            object.__setattr__(col, "_gb_dict", memo)
        except Exception:
            pass
    values, local_codes = memo
    remap = np.empty(len(values) + 1, np.int32)
    remap[-1] = -1
    for i, v in enumerate(values):
        if v not in domain.value_code:
            domain.value_code[v] = len(domain.values)
            domain.values.append(v)
        remap[i] = domain.value_code[v]
    if np.array_equal(remap[:-1], np.arange(len(values), dtype=np.int32)):
        return local_codes  # local == global: no remap dispatch
    return jnp.take(jnp.asarray(remap), local_codes)


# ---------------------------------------------------------------------------
# the traced stage
# ---------------------------------------------------------------------------

# process-wide compiled program cache (structural key → jitted fn).
# Pipelined exchange / concurrent join collection (PR 2) can build stages
# from pool threads: the lock makes lookup/insert atomic (a lost race just
# rebuilds the same program once, benignly).
_STAGE_FN_CACHE: Dict[Tuple, "jax.stages.Wrapped"] = {}
_STAGE_FN_LOCK = threading.Lock()


def _is_fp(dtype: DataType) -> bool:
    return isinstance(dtype, (FloatType, DoubleType))


def _build_stage_fn(spec: _StageSpec, cap: int,
                    domains: List["_KeyDomain"], eval_ctx):
    """Build + jit the stage program (cached process-wide). Returns
    fn(rowmask, *flat) -> (oob, rowcount, *carry)."""
    from .opjit import _conf_fp, _trace_ctx
    domain_sizes = tuple(d.size for d in domains)
    domain_los = tuple(getattr(d, "lo", None) for d in domains)
    key = spec.cache_key(cap, domain_sizes) + (domain_los,
                                               _conf_fp(eval_ctx))
    with _STAGE_FN_LOCK:
        fn = _STAGE_FN_CACHE.get(key)
    if fn is not None:
        return fn
    # the traced closure must capture the detached trace context, never the
    # live eval_ctx: conf read through it is frozen into the program, and
    # the fingerprint above is exactly what keys it (TL032)
    tctx = _trace_ctx(eval_ctx)

    source_attrs = list(spec.source.output)
    needed = spec.needed_source_ordinals
    key_set = {o: k for k, o in enumerate(spec.key_source_ordinals)}
    G = 1
    strides = []
    for d in domains:
        strides.append(G)
        G *= d.size

    # chunk length: bound the [CH, G] broadcast working set to ~2^21 cells
    ch = max(256, (1 << 21) // max(G, 1))
    ch = 1 << (ch.bit_length() - 1)
    ch = min(ch, cap)
    n_chunks = max(cap // ch, 1)
    if cap % n_chunks:
        n_chunks = 1  # a capacity that is no power of two: one chunk

    agg_fns = spec.agg_fns
    layers = spec.layers
    sizes = domain_sizes
    los = domain_los

    def stage(rowmask, *flat):
        cols: List[Optional[TpuColumnVector]] = [None] * len(source_attrs)
        key_cols: List[Optional[TpuColumnVector]] = [None] * len(domains)
        for j, o in enumerate(needed):
            data, valid = flat[2 * j], flat[2 * j + 1]
            attr = source_attrs[o]
            if o in key_set:
                key_cols[key_set[o]] = TpuColumnVector(
                    attr.dtype, data, valid, cap)
            if not isinstance(attr.dtype, StringType):
                cols[o] = TpuColumnVector(attr.dtype, data,
                                          valid & rowmask, cap)
        for o in range(len(source_attrs)):
            if cols[o] is None:
                cols[o] = TpuColumnVector(
                    source_attrs[o].dtype, jnp.zeros((cap,), jnp.int32),
                    jnp.zeros((cap,), jnp.bool_), cap)
        batch = TpuColumnarBatch(cols, cap)
        mask = rowmask
        # stable names in the device trace (metadata only)
        with jax.named_scope("filter"):
            for layer in layers:
                if layer[0] == "filter":
                    c = to_column(layer[1].eval_tpu(batch, tctx), batch)
                    m = c.data.astype(jnp.bool_)
                    if c.validity is not None:
                        m = m & c.validity
                    mask = mask & m
                else:
                    exprs, outs = layer[1], layer[2]
                    new_cols = []
                    for e, a in zip(exprs, outs):
                        src = e.children[0] if isinstance(e, Alias) else e
                        if isinstance(src, AttributeReference) \
                                and src.ordinal is not None:
                            new_cols.append(batch.columns[src.ordinal])
                        else:
                            new_cols.append(to_column(
                                e.eval_tpu(batch, tctx), batch, a.dtype))
                    batch = TpuColumnarBatch(new_cols, cap)

        # combined group code + out-of-domain detection
        with jax.named_scope("key_index"):
            code = jnp.zeros((cap,), jnp.int32)
            oob = jnp.zeros((), jnp.bool_)
            for k, (d_size, d_lo, stride) in enumerate(
                    zip(sizes, los, strides)):
                kc = key_cols[k]
                kv = kc.validity if kc.validity is not None else rowmask
                dt = domains[k].dtype
                if isinstance(dt, StringType):
                    raw = kc.data  # global codes; -1 == null
                    ci = jnp.where(raw >= 0, raw, d_size - 1)
                elif isinstance(dt, BooleanType):
                    ci = jnp.where(kv, kc.data.astype(jnp.int32), 2)
                else:
                    lo = d_lo if d_lo is not None else 0
                    raw = (kc.data.astype(jnp.int64) - lo).astype(jnp.int32)
                    oob = oob | jnp.any(mask & kv
                                        & ((raw < 0) | (raw >= d_size - 1)))
                    ci = jnp.where(kv, jnp.clip(raw, 0, d_size - 2),
                                   d_size - 1)
                code = code + ci * stride
            code = jnp.clip(code, 0, G - 1)

        with jax.named_scope("accumulate"):
            # measure inputs (evaluated once over the full batch; the scan
            # below only re-slices them)
            meas = []
            for fn_ in agg_fns:
                if fn_.children:
                    c = to_column(fn_.children[0].eval_tpu(batch, tctx),
                                  batch, fn_.children[0].dtype)
                    v = c.validity if c.validity is not None else rowmask
                    meas.append((c.data, v & mask))
                else:
                    meas.append((None, mask))

            gidx = jnp.arange(G, dtype=jnp.int32)

            def scan_body(carry, xs):
                code_c = xs[0]
                onehot = code_c[:, None] == gidx[None, :]
                pos = 2  # xs[0] = codes, xs[1] = row mask
                out = [carry[0] + jnp.sum(onehot & xs[1][:, None], axis=0,
                                          dtype=jnp.int64)]
                ci = 1
                for fn_, (x0, _v0) in zip(agg_fns, meas):
                    op = fn_.update_op
                    if x0 is None:  # count(*)
                        v = xs[pos]
                        pos += 1
                        out.append(carry[ci] + jnp.sum(
                            onehot & v[:, None], axis=0, dtype=jnp.int64))
                        ci += 1
                        continue
                    x, v = xs[pos], xs[pos + 1]
                    pos += 2
                    ohv = onehot & v[:, None]
                    nn = jnp.sum(ohv, axis=0, dtype=jnp.int64)
                    if op == "count":
                        out.append(carry[ci] + nn)
                        ci += 1
                    elif op in ("sum", "avg"):
                        acc = carry[ci].dtype
                        contrib = jnp.where(ohv, x[:, None],
                                            jnp.zeros((), x.dtype)).astype(acc)
                        out.append(carry[ci] + jnp.sum(contrib, axis=0))
                        out.append(carry[ci + 1] + nn)
                        ci += 2
                    elif op in ("min", "max"):
                        if jnp.issubdtype(x.dtype, jnp.floating):
                            neutral = jnp.asarray(
                                np.inf if op == "min" else -np.inf, x.dtype)
                            nan_x = jnp.isnan(x)
                            clean = jnp.where(ohv & ~nan_x[:, None],
                                              x[:, None], neutral)
                            red = clean.min(0) if op == "min" else clean.max(0)
                            comb = jnp.minimum if op == "min" else jnp.maximum
                            out.append(comb(carry[ci], red))
                            out.append(carry[ci + 1]
                                       | jnp.any(ohv & nan_x[:, None], axis=0))
                            out.append(carry[ci + 2] + jnp.sum(
                                ohv & ~nan_x[:, None], axis=0,
                                dtype=jnp.int64))
                            out.append(carry[ci + 3] + nn)
                            ci += 4
                        else:
                            info = jnp.iinfo(x.dtype)
                            neutral = jnp.asarray(
                                info.max if op == "min" else info.min, x.dtype)
                            red = jnp.where(ohv, x[:, None], neutral)
                            red = red.min(0) if op == "min" else red.max(0)
                            comb = jnp.minimum if op == "min" else jnp.maximum
                            out.append(comb(carry[ci], red))
                            out.append(carry[ci + 1] + nn)
                            ci += 2
                return tuple(out), None

            # initial carries
            init = [jnp.zeros((G,), jnp.int64)]  # rowcount
            for fn_, (x0, _v0) in zip(agg_fns, meas):
                op = fn_.update_op
                if op == "count":
                    init.append(jnp.zeros((G,), jnp.int64))
                elif op in ("sum", "avg"):
                    acc = jnp.float64 if op == "avg" else \
                        np.dtype(fn_.dtype.np_dtype)
                    init.append(jnp.zeros((G,), acc))
                    init.append(jnp.zeros((G,), jnp.int64))
                else:  # min/max
                    if jnp.issubdtype(x0.dtype, jnp.floating):
                        neutral = jnp.asarray(
                            np.inf if op == "min" else -np.inf, x0.dtype)
                        init.extend([jnp.full((G,), neutral, x0.dtype),
                                     jnp.zeros((G,), jnp.bool_),
                                     jnp.zeros((G,), jnp.int64),
                                     jnp.zeros((G,), jnp.int64)])
                    else:
                        info = jnp.iinfo(x0.dtype)
                        neutral = jnp.asarray(
                            info.max if op == "min" else info.min, x0.dtype)
                        init.extend([jnp.full((G,), neutral, x0.dtype),
                                     jnp.zeros((G,), jnp.int64)])

            xs = [code.reshape(n_chunks, -1), mask.reshape(n_chunks, -1)]
            for x, v in meas:
                if x is not None:
                    xs.append(x.reshape(n_chunks, -1))
                xs.append(v.reshape(n_chunks, -1))
            carry, _ = jax.lax.scan(scan_body, tuple(init), tuple(xs))
        return (oob,) + carry

    fn = jax.jit(stage)
    with _STAGE_FN_LOCK:
        _STAGE_FN_CACHE[key] = fn
    return fn


def _np_merge_carries(spec: _StageSpec, carries: List[Tuple]):
    """Merge per-batch carries (already numpy, fetched in ONE device_get)
    into (rowcount, per-fn raw-state dicts) — pure host work, no syncs.

    Float sums may legitimately produce NaN here (a group with +inf in one
    batch and -inf in another sums to NaN, matching Java), so the merge runs
    under errstate(invalid=ignore): the NaN is the answer, not an accident."""
    with np.errstate(invalid="ignore", over="ignore"):
        return _np_merge_carries_impl(spec, carries)


def _np_merge_carries_impl(spec: _StageSpec, carries: List[Tuple]):
    rowcount = None
    merged: List[Dict] = []
    for bi, carry in enumerate(carries):
        rc = carry[0]
        rowcount = rc.copy() if rowcount is None else rowcount + rc
        ci = 1
        for i, fn in enumerate(spec.agg_fns):
            op = fn.update_op
            first = bi == 0
            if first:
                merged.append(None)
            st = merged[i]
            if op == "count":
                merged[i] = {"count": carry[ci].copy()} if first \
                    else {"count": st["count"] + carry[ci]}
                ci += 1
            elif op in ("sum", "avg"):
                k2 = "nonnull" if op == "sum" else "count"
                merged[i] = {"sum": carry[ci].copy(),
                             k2: carry[ci + 1].copy()} if first else \
                    {"sum": st["sum"] + carry[ci],
                     k2: st[k2] + carry[ci + 1]}
                ci += 2
            elif fn.children and _is_fp(fn.children[0].dtype):
                comb = np.minimum if op == "min" else np.maximum
                if first:
                    merged[i] = {"clean": carry[ci].copy(),
                                 "nan_any": carry[ci + 1].copy(),
                                 "nonnan": carry[ci + 2].copy(),
                                 "nonnull": carry[ci + 3].copy()}
                else:
                    merged[i] = {"clean": comb(st["clean"], carry[ci]),
                                 "nan_any": st["nan_any"] | carry[ci + 1],
                                 "nonnan": st["nonnan"] + carry[ci + 2],
                                 "nonnull": st["nonnull"] + carry[ci + 3]}
                ci += 4
            else:
                comb = np.minimum if op == "min" else np.maximum
                merged[i] = {op: carry[ci].copy(),
                             "nonnull": carry[ci + 1].copy()} if first else \
                    {op: comb(st[op], carry[ci]),
                     "nonnull": st["nonnull"] + carry[ci + 1]}
                ci += 2
    return rowcount, merged


def _np_finalize(fn: AggregateFunction, st: Optional[Dict], idx: np.ndarray):
    """Raw merged state → (values, validity) numpy arrays over the occupied
    group indices, with _evaluate_agg's null/NaN semantics."""
    import pyarrow as pa

    from ..types import to_arrow as t2a
    op = fn.update_op
    n = len(idx)
    if st is None:  # empty input, global agg
        if op == "count":
            return pa.array(np.zeros(n, np.int64))
        return pa.nulls(n, t2a(fn.dtype))
    if op == "count":
        return pa.array(st["count"][idx], type=t2a(fn.dtype))
    if op == "sum":
        vals = st["sum"][idx]
        valid = st["nonnull"][idx] > 0
        return pa.array(vals, type=t2a(fn.dtype), mask=~valid)
    if op == "avg":
        cnt = st["count"][idx]
        valid = cnt > 0
        vals = st["sum"][idx] / np.where(valid, cnt, 1)
        return pa.array(vals.astype(np.float64), type=t2a(fn.dtype),
                        mask=~valid)
    # min/max
    valid = st["nonnull"][idx] > 0
    if "clean" in st:  # fp: Spark NaN ordering
        vals = st["clean"][idx].copy()
        if op == "min":
            vals[(st["nonnan"][idx] == 0) & valid] = np.nan
        else:
            vals[st["nan_any"][idx] & valid] = np.nan
    else:
        vals = st[op][idx]
    return pa.array(vals, type=t2a(fn.dtype), mask=~valid)


class _StageFallback(Exception):
    """Internal: abandon the compiled path for a reason read from the
    stage's own input; the general aggregate takes over what was pulled."""


class TpuStageSourceExec(TpuExec):
    """Stands where a compiled stage's source stood in the stage's FALLBACK
    subtree, over the same source node as the stage (the rewriting passes'
    id-memos and `clone_for_execution`'s keep it the same). When the stage
    gives up it hands this node what pass 1 pulled: a partition it pulled
    yields the held batches, closing each as it is consumed, and a
    partition it never started runs the source. So the general aggregate
    above reads the stage's own source output and no source partition is
    executed twice. With nothing handed (the memory-pressure rerun) it is
    the source."""

    def __init__(self, source: PhysicalPlan):
        super().__init__([source])
        # {source partition: its batches, held spillable} between a give-up
        # and the end of the fallback's run; None otherwise (a template
        # never runs, so a clone starts from None)
        self._handed: Optional[Dict[int, List]] = None

    @property
    def output(self):
        return self.children[0].output

    def node_desc(self) -> str:
        return "TpuStageSource"

    def hand(self, pulled: Dict[int, List]) -> None:
        self._handed = pulled

    def release(self) -> None:
        """Close what the fallback did not consume (a limit above it, an
        error, a cancel)."""
        handed, self._handed = self._handed, None
        for held in (handed or {}).values():
            _close_all(held)

    def internal_do_execute_columnar(self, idx: int,
                                     ctx: TaskContext) -> Iterator:
        # pop: map tasks of an exchange above pull partitions from pool
        # threads, each partition once
        held = self._handed.pop(idx, None) if self._handed else None
        if held is None:
            yield from self.children[0].execute_partition(idx, ctx)
            return
        try:
            while held:
                with held.pop(0) as sb:
                    b = sb.get_batch()
                yield b
        finally:
            _close_all(held)


def _close_all(held: List) -> None:
    while held:
        held.pop().close()


class TpuCompiledAggStageExec(TpuExec):
    """The fused scan→filter→project→group-by stage (one jit per shape)."""

    def __init__(self, spec: _StageSpec, fallback: PhysicalPlan,
                 max_groups: int):
        super().__init__([spec.source])
        self.spec = spec
        # the original aggregate, over a TpuStageSourceExec where the
        # source stood (compile_agg_stages)
        self.fallback = fallback
        self.max_groups = max_groups

    @property
    def output(self):
        return self.spec.output

    def num_partitions(self) -> int:
        return 1

    def collect_nodes(self):
        # the fallback subtree holds the exchanges whose shuffle state the
        # session releases at query end — it MUST stay reachable here, or
        # every fallback run leaks its shuffle blocks in the catalog
        out = super().collect_nodes()
        seen = {id(n) for n in out}
        out.extend(n for n in self.fallback.collect_nodes()
                   if id(n) not in seen)
        return out

    def node_desc(self) -> str:
        keys = ", ".join(g.name for g in self.spec.grouping) or "<global>"
        return f"TpuCompiledAggStage[keys={keys}]"

    def additional_metrics(self):
        return {"stageTime": "MODERATE", "numGroups": "DEBUG",
                "fallbackHandoffs": "DEBUG", "fallbackReruns": "DEBUG"}

    def query_counters(self):
        return [("stage.fallback_handoffs", self.metrics["fallbackHandoffs"]),
                ("stage.fallback_reruns", self.metrics["fallbackReruns"])]

    def _fallback_source(self) -> TpuStageSourceExec:
        # found, not kept: the rewriting passes copy the nodes they change,
        # and a kept link would be one more to keep in step
        node = self.fallback
        while not isinstance(node, TpuStageSourceExec):
            node = node.children[0]
        return node

    def internal_do_execute_columnar(self, idx: int,
                                     ctx: TaskContext) -> Iterator:
        from ..memory.hbm import TpuRetryOOM, TpuSplitAndRetryOOM
        handoff = self._fallback_source()
        result = None
        try:
            result = self._run_compiled(ctx, handoff)
        except _StageFallback:
            # decided from what the stage saw of its input: the general
            # aggregate reads on from where pass 1 stopped
            self.metrics["fallbackHandoffs"].add(1)
        except (TpuRetryOOM, TpuSplitAndRetryOOM):
            # memory pressure: a source iterator that raised cannot be
            # resumed and what is held is best let go, so the general path,
            # with the full spill/retry/split machinery, runs the source
            # again (nothing is handed)
            self.metrics["fallbackReruns"].add(1)
        if result is not None:
            yield result
            return
        try:
            for p in range(self.fallback.num_partitions()):
                yield from self.fallback.execute_partition(p, ctx)
        finally:
            handoff.release()

    def _run_compiled(self, ctx: TaskContext,
                      handoff: TpuStageSourceExec) -> TpuColumnarBatch:
        from ..memory.spill import SpillableColumnarBatch
        from ..parallel.mesh import on_chip, run_chip_tasks
        spec = self.spec
        # pull through the plan-tree link, NOT the spec's captured source:
        # passes that run after stage compilation (whole-stage segment
        # fusion, coalescing) rewrite children[0], and executing the stale
        # spec.source would silently run the pre-fusion operator chain
        src = self.children[0]
        pulled: Dict[int, List[SpillableColumnarBatch]] = {}
        domains = [_KeyDomain(g.dtype) for g in spec.grouping]
        carries = []
        oob_flags = []
        try:
            # pass 1: collect batches (spillable) + key statistics; stats are
            # memoized on the column objects so cached relations pay once.
            # The domains only grow, so the batch that takes them over
            # maxGroups decides: the partition it came in is pulled to its
            # end without statistics (a blocking sync a key a batch), so
            # that its iterator and task context close here, and no other
            # partition is started.
            # In a mesh session a round is a partition a chip, pulled at
            # once (`run_chip_tasks`); the statistics then follow in
            # partition order on this thread, so which batch decides — and
            # on which chip its statistics' programs run — does not hang on
            # which chip answered first. A round that a give-up ends is
            # handed over whole.
            with _obs.phase("stage.collect"):
                n_parts = src.num_partitions()
                chips = ctx.chips
                step = len(chips) if chips else 1
                if chips:
                    from ..shuffle.exchange import materialize_exchanges
                    materialize_exchanges(src, ctx)

                def pull(p: int) -> None:
                    held = pulled[p] = []    # closed below whatever ends it
                    pctx = TaskContext(p, ctx.conf)
                    try:
                        for b in src.execute_partition(p, pctx):
                            held.append(SpillableColumnarBatch(b))
                    finally:
                        pctx.complete()

                fits = True
                for lo in range(0, n_parts, step):
                    ids = range(lo, min(lo + step, n_parts))
                    run_chip_tasks(ctx.conf, ids, pull)
                    for p in ids:
                        empty = []
                        with on_chip(chips[p % step] if chips else None):
                            for sb in pulled[p]:
                                b = sb.get_batch()
                                if fits and not b.num_rows:
                                    empty.append(sb)
                                    continue
                                fits = fits and self._grow_domains(b, domains)
                        pulled[p] = [sb for sb in pulled[p]
                                     if sb not in empty]
                        _close_all(empty)
                    if not fits:
                        raise _StageFallback()
            # pass 2: one fused program per batch shape. Dispatches are
            # async; the ONLY sync is a single device_get of every carry +
            # the oob flags at the end (high-latency links pay one round
            # trip per query, like the hand-fused kernel)
            with self.metrics["stageTime"].timed():
                laps = _obs.PhaseLaps()  # per batch: clock reads only
                try:
                    for p in sorted(pulled):
                        # a mesh session's batch is launched where it is
                        chip = None if ctx.chips is None \
                            else ctx.chips[p % len(ctx.chips)]
                        for sb in pulled[p]:
                            with laps.lap("stage.launch"), on_chip(chip):
                                b = sb.get_batch()
                                out = self._run_batch(b, domains, ctx)
                                oob_flags.append(out[0])
                                carries.append(out[1:])
                finally:
                    laps.flush()
                from ..columnar.vector import audited_device_get
                with _obs.phase("stage.fetch", cat="wait"):
                    host = audited_device_get((oob_flags, carries), "stage")
                oob_np, carries_np = host
                if oob_np and bool(np.any(np.stack(oob_np))):
                    raise _StageFallback()
        except _StageFallback:
            handoff.hand(pulled)
            pulled = {}
            raise
        finally:
            for held in pulled.values():
                _close_all(held)
        with _obs.phase("stage.assemble"):
            return self._assemble(domains, carries_np, ctx)

    def _grow_domains(self, b: TpuColumnarBatch,
                      domains: List[_KeyDomain]) -> bool:
        """Grow the key domains by one batch. False as soon as their
        product (a string key's dictionary alone, too) passes maxGroups or
        a key column is one the stage cannot take: the stage gives up."""
        G = 1
        for k, o in enumerate(self.spec.key_source_ordinals):
            d = domains[k]
            col = b.columns[o]
            if isinstance(d.dtype, StringType):
                _string_codes(col, d)  # grows the global dictionary
            elif not isinstance(d.dtype, BooleanType):
                if col.offsets is not None or col.host_data is not None \
                        or col.children is not None:
                    return False
                lo, hi = _int_stats(col)
                if lo is not None:
                    d.lo = lo if d.lo is None else min(d.lo, lo)
                    d.hi = hi if d.hi is None else max(d.hi, hi)
            G *= d.size
            if G > self.max_groups:
                return False
        return True

    def _run_batch(self, b: TpuColumnarBatch, domains: List[_KeyDomain],
                   ctx: TaskContext):
        spec = self.spec
        cap = b.capacity
        key_ord = {o: k for k, o in enumerate(spec.key_source_ordinals)}
        flat = []
        for o in spec.needed_source_ordinals:
            col = b.columns[o]
            if o in key_ord and isinstance(domains[key_ord[o]].dtype,
                                           StringType):
                codes = _string_codes(col, domains[key_ord[o]])
                flat.append(codes)
                flat.append(codes >= 0)
            else:
                if col.offsets is not None or col.host_data is not None \
                        or col.children is not None:
                    raise _StageFallback()
                flat.append(col.data)
                flat.append(col.validity if col.validity is not None
                            else row_mask(b.num_rows, cap))
        fn = _build_stage_fn(spec, cap, domains, ctx.eval_ctx)
        # compiled-stage launch = one device dispatch: chaos site + bounded
        # transient retry (the stage fn is pure over its device inputs)
        from ..chaos import inject
        from ..failure import with_device_retry
        from . import opjit

        opjit.record_external_dispatch("compiledagg")

        def dispatch():
            inject("device.dispatch", detail="compiled_stage")
            return fn(row_mask(b.num_rows, cap), *flat)

        return with_device_retry(dispatch, ctx.conf)

    def _assemble(self, domains: List[_KeyDomain], carries: List[Tuple],
                  ctx: TaskContext) -> TpuColumnarBatch:
        """Pure host work over the fetched numpy carries: merge, finalize,
        decode keys, project results (eval_cpu over the tiny table) — zero
        device round trips after the one carry download."""
        import pyarrow as pa

        from ..types import to_arrow as t2a
        from .aggregates import _bind_agg_refs
        spec = self.spec
        G = 1
        strides = []
        for d in domains:
            strides.append(G)
            G *= d.size

        if not carries:
            if spec.grouping:  # grouped agg over empty input: no rows
                return _host_batch(
                    pa.Table.from_arrays(
                        [pa.nulls(0, t2a(a.dtype)) for a in spec.output],
                        names=[a.name for a in spec.output]))
            rowcount = np.zeros(G, np.int64)
            states: List[Optional[Dict]] = [None] * len(spec.agg_fns)
        else:
            rowcount, states = _np_merge_carries(spec, carries)

        if spec.grouping:
            occ_idx = np.nonzero(rowcount > 0)[0]
        else:
            occ_idx = np.array([0])
        self.metrics["numGroups"].add(len(occ_idx))

        key_arrays = []
        for d, stride in zip(domains, strides):
            comp = (occ_idx // stride) % d.size
            null_slot = d.size - 1
            if isinstance(d.dtype, StringType):
                vals = [None if c == null_slot else d.values[c]
                        for c in comp]
                key_arrays.append(pa.array(vals, type=t2a(d.dtype)))
            elif isinstance(d.dtype, BooleanType):
                key_arrays.append(pa.array(
                    [None if c == 2 else bool(c) for c in comp],
                    type=pa.bool_()))
            else:
                lo = d.lo if d.lo is not None else 0
                key_arrays.append(pa.array(
                    [None if c == null_slot else int(lo + c) for c in comp],
                    type=t2a(d.dtype)))
        agg_arrays = [_np_finalize(fn, st, occ_idx)
                      for fn, st in zip(spec.agg_fns, states)]

        ng = len(spec.grouping)
        agg_table = pa.Table.from_arrays(
            key_arrays + agg_arrays,
            names=[f"__k_{i}" for i in range(ng)]
            + [f"__agg_{i}" for i in range(len(agg_arrays))])
        out_arrays = list(key_arrays)
        for expr, attr in zip(spec.result_exprs, spec.output[ng:]):
            bound = _bind_agg_refs(expr, None, ng, spec.grouping)
            r = bound.eval_cpu(agg_table, ctx.eval_ctx)
            if not isinstance(r, (pa.Array, pa.ChunkedArray)):
                r = pa.array([r] * agg_table.num_rows, type=t2a(attr.dtype))
            elif isinstance(r, pa.ChunkedArray):
                r = r.combine_chunks()
            out_arrays.append(r)
        return _host_batch(pa.Table.from_arrays(
            out_arrays, names=[a.name for a in spec.output]))


def _host_batch(table) -> TpuColumnarBatch:
    """Host Arrow result → numpy-backed batch: collect() reads it with zero
    device round trips, and downstream device execs (sort/limit/joins)
    consume it like any other batch (jax uploads the tiny buffers on first
    use)."""
    return TpuColumnarBatch.from_arrow(table, to_device=False)


def compile_agg_stages(plan: PhysicalPlan, conf) -> PhysicalPlan:
    """Post-pass over the physical tree: replace eligible aggregate subtrees
    with compiled stages (spark.rapids.tpu.agg.compiledStage.enabled)."""
    from ..config import (ANSI_ENABLED, COMPILED_AGG_ENABLED,
                          COMPILED_AGG_MAX_GROUPS)
    if not conf.get(COMPILED_AGG_ENABLED) or conf.get(ANSI_ENABLED):
        return plan
    max_groups = conf.get(COMPILED_AGG_MAX_GROUPS)

    def rewrite(node: PhysicalPlan) -> PhysicalPlan:
        spec = try_extract_stage(node)
        if spec is not None:
            # the fallback is the aggregate itself, re-based: the node that
            # pulled the source pulls a TpuStageSourceExec over it
            above = node
            while above.children[0] is not spec.source:
                above = above.children[0]
            above.children = [TpuStageSourceExec(spec.source)]
            return TpuCompiledAggStageExec(spec, node, max_groups)
        node.children = [rewrite(c) for c in node.children]
        return node

    return rewrite(plan)
