"""Whole-stage compiled star-join aggregation: fact scan→filter→project →
chain of many-to-one equi-joins → group-by, fused into ONE jitted XLA
program per fact batch.

The reference executes this pipeline as a chain of per-partition hash-join
kernel launches threaded through shuffle exchanges
(GpuShuffledHashJoinExec / GpuHashJoin.scala:994 iterator chain,
GpuShuffleExchangeExecBase.scala:277). On TPU behind a high-latency dispatch
link that shape is catastrophic: every per-partition program launch pays the
full dispatch cost, so a three-table join measures launch count, not
silicon. The TPU-first design inverts it:

  * dimension (build) sides are small by star-schema construction: they
    materialize ONCE as sorted device key arrays + payload columns — the
    broadcast relation analogue, but laid out for vectorized probing;
  * the fact (stream) side is traced: filters, projections, the whole probe
    chain (`searchsorted` on the sorted dim keys + gather of payloads), and
    the grouped aggregation all fuse into one XLA program;
  * many-to-one joins keep the fact cardinality static (each probe row
    matches at most one build row when build keys are unique — verified at
    build time, duplicate keys fall back), so the trace needs no dynamic
    shapes: unmatched rows are masked, never compacted;
  * grouping keys that live on one dimension table group by the dimension
    ROW INDEX — a dense code with G = |dim|, aggregated with segment
    reductions. No key-domain products, no group-table explosion: TPC-H q3's
    (o_orderkey, o_orderdate) grouping is just "group by orders row".

Carry layout is IDENTICAL to the compiled aggregation stage
(execs/compiled.py), so the host-side merge/finalize machinery is shared.

Eligibility (anything else transparently falls back to the shuffled-join
plan): inner/left-semi equi-joins with no residual condition; integral/date
join keys — multi-column keys pack into one monotone int64 composite at
build time (r5), so the probe stays a single searchsorted; the fact leaf is
a device-pure filter/project chain over a source; every traced column
fixed-width non-decimal; group keys are columns of ONE inner dimension (or
absent: global aggregate); aggregates sum/count/avg/min/max.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import threading as _threading

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.batch import TpuColumnarBatch, concat_batches
from ..columnar.vector import TpuColumnVector, bucket_capacity, row_mask
from ..expressions.base import (Alias, AttributeReference, Expression,
                                to_column)
from ..types import (DataType, DateType, DecimalType, IntegralType,
                     StringType, is_fixed_width)
from .base import PhysicalPlan, TaskContext, TpuExec
from .compiled import (_agg_eligible, _device_pure, _fingerprint,
                       _identity_source_ordinal, _np_finalize,
                       _np_merge_carries, _host_batch, _refs)


class _Ineligible(Exception):
    pass


class _JoinStageFallback(Exception):
    pass


# ---------------------------------------------------------------------------
# pattern extraction
# ---------------------------------------------------------------------------


class _DimSpec:
    """One build side: `plan` materializes once; the stream probes its
    `key_ordinals` columns with the values at `probe_locs` (each
    ("fact", o) or ("dim", earlier_dim_index, o)). Multi-column keys pack
    into one monotone int64 composite at build time (per-key min/stride),
    so the probe stays a single searchsorted."""

    #: plan-cache clone protocol (execs/base.py _clone_spec): the dim's
    #: build subtree EXECUTES, so a cached-plan clone needs its own copy
    _PLAN_SPEC = True

    def __init__(self, plan: PhysicalPlan, key_ordinals: List[int],
                 probe_locs: List, semi: bool):
        self.plan = plan
        self.key_ordinals = list(key_ordinals)
        self.probe_locs = list(probe_locs)
        self.semi = semi
        self.payload_ordinals: List[int] = []  # device-gathered columns


class _JoinStageSpec:
    #: plan-cache clone protocol (execs/base.py _clone_spec)
    _PLAN_SPEC = True

    def __init__(self, fact_source, fact_layers, fact_needed_source,
                 fact_output, dims, top_output, col_loc, top_layers,
                 grouping, group_dim, group_key_ordinals, agg_fns,
                 result_exprs, output, needed_top):
        self.fact_source = fact_source
        self.fact_layers = fact_layers          # bottom-up, like _StageSpec
        self.fact_needed_source = fact_needed_source
        self.fact_output = fact_output          # attrs of the fact leaf top
        self.dims = dims                        # probe order
        self.top_output = top_output            # top join node's output attrs
        self.col_loc = col_loc                  # top ordinal -> location
        self.top_layers = top_layers            # between join and agg
        self.grouping = grouping
        self.group_dim = group_dim              # dim index or None (global)
        self.group_key_ordinals = group_key_ordinals  # into group dim output
        self.agg_fns = agg_fns
        self.result_exprs = result_exprs
        self.output = output
        self.needed_top = needed_top            # traced top-output ordinals

    def cache_key(self, cap: int, dim_caps: Tuple[int, ...]) -> Tuple:
        parts = []
        for layer in self.fact_layers:
            parts.append(("F" if layer[0] == "filter" else "P")
                         + (_fingerprint(layer[1]) if layer[0] == "filter"
                            else ";".join(_fingerprint(e)
                                          for e in layer[1])))
        parts.append("T")
        for layer in self.top_layers:
            parts.append(("F" if layer[0] == "filter" else "P")
                         + (_fingerprint(layer[1]) if layer[0] == "filter"
                            else ";".join(_fingerprint(e)
                                          for e in layer[1])))
        parts.append("A" + ";".join(_fingerprint(f) for f in self.agg_fns))
        parts.append("S" + ";".join(type(a.dtype).__name__
                                    for a in self.fact_source.output))
        parts.append("N" + ",".join(map(str, self.fact_needed_source)))
        parts.append("NT" + ",".join(map(str, self.needed_top)))
        for d in self.dims:
            parts.append(f"D{tuple(d.key_ordinals)}:{int(d.semi)}:"
                         f"{tuple(d.probe_locs)}:"
                         + ",".join(map(str, d.payload_ordinals)))
        parts.append(f"G{self.group_dim}")
        return ("|".join(parts), cap, dim_caps)


def _strip_exchanges(node: PhysicalPlan) -> PhysicalPlan:
    from ..shuffle.exchange import (TpuShuffleExchangeExec,
                                    TpuShuffleReaderExec)
    from .basic import TpuCoalesceBatchesExec
    while isinstance(node, (TpuShuffleExchangeExec, TpuShuffleReaderExec,
                            TpuCoalesceBatchesExec)):
        node = node.children[0]
    return node


def _unwrap_widening_cast(e: Expression) -> Expression:
    """Integral/date widening casts on join keys (inserted by the planner's
    key-type coercion) are transparent to the stage: the probe compares in
    int64 anyway, and widening preserves equality."""
    from ..expressions.cast import Cast
    if isinstance(e, Cast) and len(e.children) == 1 \
            and isinstance(e.children[0], AttributeReference) \
            and isinstance(e.dtype, (IntegralType, DateType)) \
            and isinstance(e.children[0].dtype, (IntegralType, DateType)):
        return e.children[0]
    return e


def _flatten_join_tree(node: PhysicalPlan):
    """Flatten a tree of eligible hash joins into (leaves, conditions).
    Conditions are (left_key_attr, right_key_attr, is_semi)."""
    from .joins import TpuShuffledHashJoinExec
    node = _strip_exchanges(node)
    if isinstance(node, TpuShuffledHashJoinExec):
        if node.join_type not in ("inner", "leftsemi", "semi"):
            raise _Ineligible()
        if node.condition is not None:
            raise _Ineligible()
        if not node.left_keys or len(node.left_keys) != len(node.right_keys):
            raise _Ineligible()
        lks = [_unwrap_widening_cast(k) for k in node.left_keys]
        rks = [_unwrap_widening_cast(k) for k in node.right_keys]
        if not all(isinstance(k, AttributeReference) for k in lks + rks):
            raise _Ineligible()
        semi = node.join_type in ("leftsemi", "semi")
        l_leaves, l_conds = _flatten_join_tree(node.children[0])
        if semi:
            # the probed-against side of a semi join must be a single leaf
            r_node = _strip_exchanges(node.children[1])
            r_leaves, r_conds = [r_node], []
            if isinstance(r_node, TpuShuffledHashJoinExec):
                raise _Ineligible()
        else:
            r_leaves, r_conds = _flatten_join_tree(node.children[1])
        return l_leaves + r_leaves, l_conds + r_conds + [(lks, rks, semi)]
    return [node], []


def _estimate_rows(plan: PhysicalPlan) -> int:
    """Best-effort leaf size: max scan cardinality in the subtree."""
    best = 0
    stack = [plan]
    while stack:
        n = stack.pop()
        t = getattr(n, "table", None)
        if t is not None and hasattr(t, "num_rows"):
            best = max(best, t.num_rows)
        b = getattr(n, "_batches", None) or getattr(n, "batches", None)
        if b is not None:
            best = max(best, sum(getattr(x, "num_rows", 0) for x in b))
        stack.extend(n.children)
    return best


def _walk_pure_chain(node: PhysicalPlan):
    """Walk a device-pure filter/project chain downward. Returns
    (base_node, layers bottom-up); raises _Ineligible on a non-device-pure
    expression. Shared by the fact-leaf walk and the above-join walk so the
    two eligibility rules can never drift apart."""
    from .basic import TpuCoalesceBatchesExec, TpuFilterExec, TpuProjectExec
    chain: List[Tuple] = []
    while isinstance(node, (TpuProjectExec, TpuFilterExec,
                            TpuCoalesceBatchesExec)):
        if isinstance(node, TpuProjectExec):
            for e in node.exprs:
                inner = e.children[0] if isinstance(e, Alias) else e
                if isinstance(inner, AttributeReference):
                    continue
                if not _device_pure(e):
                    raise _Ineligible()
            chain.append(("project", list(node.exprs), list(node.output)))
        elif isinstance(node, TpuFilterExec):
            if not _device_pure(node.condition):
                raise _Ineligible()
            chain.append(("filter", node.condition))
        node = node.children[0]
    return node, list(reversed(chain))


def _extract_fact_chain(leaf: PhysicalPlan):
    """Fact leaf must be a device-pure filter/project chain over a source."""
    node, layers = _walk_pure_chain(leaf)
    if not isinstance(node, TpuExec):
        raise _Ineligible()
    return node, layers


def _walk_needed(top_ordinals, layers) -> set:
    """Map needed ordinals at the top of a layer chain down to its base."""
    cur = set(top_ordinals)
    for layer in reversed(layers):  # top-down
        if layer[0] == "filter":
            cur.update(_refs(layer[1]))
        else:
            nxt = set()
            for o in cur:
                if o < len(layer[1]):
                    nxt.update(_refs(layer[1][o]))
            cur = nxt
    return cur


def try_extract_join_stage(agg) -> Optional[_JoinStageSpec]:
    from ..shuffle.exchange import (TpuShuffleExchangeExec,
                                    TpuShuffleReaderExec)
    from .aggregates import TpuHashAggregateExec, split_result_exprs
    from .basic import TpuCoalesceBatchesExec
    from .joins import TpuShuffledHashJoinExec

    if not isinstance(agg, TpuHashAggregateExec):
        return None
    agg_fns, result_exprs = split_result_exprs(agg.aggregates)
    if not agg_fns or not all(_agg_eligible(f) for f in agg_fns):
        return None
    grouping = list(agg.grouping)
    if not all(isinstance(g, AttributeReference) and g.ordinal is not None
               for g in grouping):
        return None

    try:
        node = agg.children[0]
        while isinstance(node, (TpuShuffleReaderExec, TpuShuffleExchangeExec,
                                TpuCoalesceBatchesExec)):
            if isinstance(node, TpuShuffleExchangeExec) \
                    and node.partitioning != "hash":
                return None
            node = node.children[0]

        # layers between the aggregation and the top join
        node, top_layers = _walk_pure_chain(node)

        node = _strip_exchanges(node)
        if not isinstance(node, TpuShuffledHashJoinExec):
            return None
        top_output = list(node.output)
        leaves, conds = _flatten_join_tree(node)
        if len(leaves) < 2 or not conds:
            return None

        # expr_id -> (leaf index, ordinal)
        leaf_loc: Dict[int, Tuple[int, int]] = {}
        for li, leaf in enumerate(leaves):
            for o, a in enumerate(leaf.output):
                leaf_loc[a.expr_id] = (li, o)

        # the fact is the largest leaf; it must carry a traceable chain
        sizes = [_estimate_rows(lf) for lf in leaves]
        fact_idx = int(np.argmax(sizes))
        fact_source, fact_layers = _extract_fact_chain(leaves[fact_idx])
        fact_output = list(leaves[fact_idx].output)

        # resolve probe order: a condition is ready when its probe-side
        # value is on the fact or an already-probed inner dimension
        def loc_of(attr) -> Optional[Tuple[int, int]]:
            return leaf_loc.get(attr.expr_id)

        dims: List[_DimSpec] = []
        dim_of_leaf: Dict[int, int] = {}
        pending = list(conds)
        while pending:
            progressed = False
            for cond in list(pending):
                lks, rks, semi = cond
                l_locs = [loc_of(k) for k in lks]
                r_locs = [loc_of(k) for k in rks]
                if any(x is None for x in l_locs + r_locs):
                    raise _Ineligible()
                # semi: only the right side may be the dimension
                orientations = ((l_locs, r_locs, lks, rks),) if semi else \
                    ((l_locs, r_locs, lks, rks),
                     (r_locs, l_locs, rks, lks))
                placed = False
                for p_locs, d_locs, p_attrs, d_attrs in orientations:
                    # ALL dim-side keys must live on one un-joined leaf
                    d_leaves = {loc[0] for loc in d_locs}
                    if len(d_leaves) != 1:
                        continue
                    d_leaf = next(iter(d_leaves))
                    if d_leaf == fact_idx or d_leaf in dim_of_leaf:
                        continue
                    if not all(isinstance(a.dtype, (IntegralType, DateType))
                               for a in d_attrs):
                        continue
                    probe_locs = []
                    ok = True
                    for (p_leaf, p_ord) in p_locs:
                        if p_leaf == fact_idx:
                            probe_locs.append(("fact", p_ord))
                        elif p_leaf in dim_of_leaf \
                                and not dims[dim_of_leaf[p_leaf]].semi:
                            probe_locs.append(
                                ("dim", dim_of_leaf[p_leaf], p_ord))
                        else:
                            ok = False
                            break
                    if not ok:
                        continue
                    spec = _DimSpec(leaves[d_leaf],
                                    [loc[1] for loc in d_locs],
                                    probe_locs, semi)
                    dim_of_leaf[d_leaf] = len(dims)
                    dims.append(spec)
                    pending.remove(cond)
                    placed = progressed = True
                    break
                if placed:
                    continue
            if not progressed:
                raise _Ineligible()
        if len(dim_of_leaf) != len(leaves) - 1:
            raise _Ineligible()

        # top-output ordinal -> ("fact"|"dim", ...) location
        col_loc: Dict[int, Tuple] = {}
        for o, a in enumerate(top_output):
            loc = leaf_loc.get(a.expr_id)
            if loc is None:
                continue
            li, lo = loc
            col_loc[o] = ("fact", lo) if li == fact_idx else \
                ("dim", dim_of_leaf[li], lo)

        # group keys must all live on ONE inner dimension (or no grouping)
        group_dim: Optional[int] = None
        group_key_ordinals: List[int] = []
        group_keys_device = True
        for g in grouping:
            src = _identity_source_ordinal(g.ordinal, top_layers)
            if src is None or src not in col_loc:
                raise _Ineligible()
            loc = col_loc[src]
            if loc[0] != "dim":
                raise _Ineligible()
            _, di, o = loc
            if dims[di].semi:
                raise _Ineligible()
            if group_dim is None:
                group_dim = di
            elif group_dim != di:
                raise _Ineligible()
            group_key_ordinals.append(o)
            dt = dims[di].plan.output[o].dtype
            if isinstance(dt, (StringType, DecimalType)) \
                    or not is_fixed_width(dt):
                group_keys_device = False
        # Grouping by dim ROW INDEX is only value-correct when the group
        # key columns are UNIQUE per dim row: two dim rows could otherwise
        # share identical payload values and row-grouping would split what
        # SQL groups together (found by TPC-H q21: two suppliers with equal
        # s_name). Covering all join keys proves it statically (the build
        # verifies composite uniqueness); a subset defers the uniqueness
        # check to build time over the materialized dim.
        group_unique_checked = (
            group_dim is not None
            and not (set(dims[group_dim].key_ordinals)
                     <= set(group_key_ordinals)))

        # traced columns: agg children + top layers, walked to the join out
        agg_refs = set()
        for f in agg_fns:
            for c in f.children:
                agg_refs.update(_refs(c))
        needed_top = sorted(_walk_needed(agg_refs, top_layers))

        for o in needed_top:
            loc = col_loc.get(o)
            if loc is None:
                raise _Ineligible()
            dt = top_output[o].dtype
            if isinstance(dt, (StringType, DecimalType)) \
                    or not is_fixed_width(dt):
                raise _Ineligible()
            if loc[0] == "dim":
                di, lo = loc[1], loc[2]
                if dims[di].semi:
                    raise _Ineligible()
                if lo not in dims[di].payload_ordinals:
                    dims[di].payload_ordinals.append(lo)

        # device-resident output: when the result projection is an identity
        # over the aggregates AND the group keys are fixed-width, the stage
        # emits DEVICE columns (keys gathered from dim payloads, aggregates
        # finalized in-trace) — the whole aggregate never leaves HBM, and a
        # downstream TopN/sort fetches only its final rows
        def _identity_result(expr, i):
            e = expr.children[0] if isinstance(expr, Alias) else expr
            return (isinstance(e, AttributeReference)
                    and e.expr_id == -(i + 1))

        device_output = (group_keys_device
                         and all(_identity_result(e, i)
                                 for i, e in enumerate(result_exprs)))
        if device_output and group_dim is not None:
            for o in group_key_ordinals:
                if o not in dims[group_dim].payload_ordinals:
                    dims[group_dim].payload_ordinals.append(o)

        # probe-chain payloads gather on device too
        for d in dims:
            for loc in d.probe_locs:
                if loc[0] == "dim":
                    _, di, o = loc
                    dt = dims[di].plan.output[o].dtype
                    if isinstance(dt, (StringType, DecimalType)) \
                            or not is_fixed_width(dt):
                        raise _Ineligible()
                    if o not in dims[di].payload_ordinals:
                        dims[di].payload_ordinals.append(o)
        for d in dims:
            d.payload_ordinals.sort()

        # fact source pruning: needed fact-top ordinals walked to the source
        fact_top_needed = {col_loc[o][1] for o in needed_top
                           if col_loc[o][0] == "fact"}
        for d in dims:
            for loc in d.probe_locs:
                if loc[0] == "fact":
                    fact_top_needed.add(loc[1])
        fact_needed_source = sorted(
            _walk_needed(fact_top_needed, fact_layers))
        for o in fact_needed_source:
            if o >= len(fact_source.output):
                raise _Ineligible()
            dt = fact_source.output[o].dtype
            if isinstance(dt, (StringType, DecimalType)) \
                    or not is_fixed_width(dt):
                raise _Ineligible()

        spec = _JoinStageSpec(
            fact_source, fact_layers, fact_needed_source, fact_output,
            dims, top_output, col_loc, top_layers, grouping, group_dim,
            group_key_ordinals, agg_fns, result_exprs, list(agg.output),
            needed_top)
        spec.device_output = device_output
        spec.group_unique_check = group_unique_checked
        return spec
    except _Ineligible:
        return None


# ---------------------------------------------------------------------------
# the traced program
# ---------------------------------------------------------------------------

_JOIN_STAGE_FN_CACHE: Dict[Tuple, "jax.stages.Wrapped"] = {}
#: joins collect both sides concurrently (PR 2): cache ops are locked so a
#: racing build can only cost a benign duplicate trace, never a torn dict
_JOIN_CACHE_LOCK = _threading.Lock()


def _segment_states(fn, x, v, gcode, G):
    """Per-aggregate segment-reduced carry arrays, laid out EXACTLY like the
    compiled-agg scan carries (compiled.py _build_stage_fn init/scan_body)
    so _np_merge_carries consumes them unchanged."""
    from .compiled import _is_fp
    op = fn.update_op
    seg = jax.ops.segment_sum
    if x is None:  # count(*)
        return [seg(v.astype(jnp.int64), gcode, num_segments=G)]
    nn = seg(v.astype(jnp.int64), gcode, num_segments=G)
    if op == "count":
        return [nn]
    if op in ("sum", "avg"):
        acc = jnp.float64 if op == "avg" else \
            np.dtype(fn.dtype.np_dtype)
        contrib = jnp.where(v, x, jnp.zeros((), x.dtype)).astype(acc)
        return [seg(contrib, gcode, num_segments=G), nn]
    # min/max
    if jnp.issubdtype(x.dtype, jnp.floating):
        neutral = jnp.asarray(np.inf if op == "min" else -np.inf, x.dtype)
        nan_x = jnp.isnan(x)
        clean = jnp.where(v & ~nan_x, x, neutral)
        red = (jax.ops.segment_min if op == "min"
               else jax.ops.segment_max)(clean, gcode, num_segments=G)
        # empty segments come back as dtype extrema; normalize to neutral
        red = jnp.where(jnp.isfinite(red) | (red == neutral), red, neutral)
        nan_any = jax.ops.segment_max(
            (v & nan_x).astype(jnp.int32), gcode, num_segments=G) > 0
        nonnan = seg((v & ~nan_x).astype(jnp.int64), gcode, num_segments=G)
        return [red, nan_any, nonnan, nn]
    info = jnp.iinfo(x.dtype)
    neutral = jnp.asarray(info.max if op == "min" else info.min, x.dtype)
    masked = jnp.where(v, x, neutral)
    red = (jax.ops.segment_min if op == "min"
           else jax.ops.segment_max)(masked, gcode, num_segments=G)
    return [red, nn]


def _build_join_stage_fn(spec: _JoinStageSpec, cap: int,
                         dim_caps: Tuple[int, ...], dim_dense, eval_ctx):
    from .opjit import _conf_fp, _trace_ctx
    key = spec.cache_key(cap, dim_caps) + (tuple(dim_dense),
                                           _conf_fp(eval_ctx))
    with _JOIN_CACHE_LOCK:
        fn = _JOIN_STAGE_FN_CACHE.get(key)
    if fn is not None:
        return fn
    # the traced closure must capture the detached trace context, never the
    # live eval_ctx: conf read through it is frozen into the program, and
    # the fingerprint above is exactly what keys it (TL032)
    tctx = _trace_ctx(eval_ctx)

    source_attrs = list(spec.fact_source.output)
    needed_src = spec.fact_needed_source
    fact_layers = spec.fact_layers
    top_layers = spec.top_layers
    dims = spec.dims
    G = (dim_caps[spec.group_dim] + 1) if spec.group_dim is not None else 2

    def stage(rowmask, fact_flat, dim_flat):
        # ---- fact leaf: source batch -> device-pure layers -------------
        cols: List[Optional[TpuColumnVector]] = [None] * len(source_attrs)
        for j, o in enumerate(needed_src):
            data, valid = fact_flat[2 * j], fact_flat[2 * j + 1]
            cols[o] = TpuColumnVector(source_attrs[o].dtype, data,
                                      valid & rowmask, cap)
        for o in range(len(source_attrs)):
            if cols[o] is None:
                cols[o] = TpuColumnVector(
                    source_attrs[o].dtype, jnp.zeros((cap,), jnp.int32),
                    jnp.zeros((cap,), jnp.bool_), cap)
        batch = TpuColumnarBatch(cols, cap)
        alive = rowmask
        for layer in fact_layers:
            if layer[0] == "filter":
                c = to_column(layer[1].eval_tpu(batch, tctx), batch)
                m = c.data.astype(jnp.bool_)
                if c.validity is not None:
                    m = m & c.validity
                alive = alive & m
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
        fact_cols = batch.columns  # fact leaf top space

        # ---- probe chain ----------------------------------------------
        # dim_flat per dim: (keys_sorted_i64, n_valid, lo, mins, strides,
        # maxs, {payload data+valid})
        dim_idx: List[Optional[jnp.ndarray]] = [None] * len(dims)

        def resolve_probe(loc):
            if loc[0] == "fact":
                c = fact_cols[loc[1]]
                v = c.validity if c.validity is not None else rowmask
                return c.data, v
            _, di, o = loc
            j = dims[di].payload_ordinals.index(o)
            pdata, pvalid = dim_flat[di][6 + 2 * j], dim_flat[di][7 + 2 * j]
            idx = dim_idx[di]
            return jnp.take(pdata, idx), jnp.take(pvalid, idx)

        for di, d in enumerate(dims):
            keys, n_valid, lo = (dim_flat[di][0], dim_flat[di][1],
                                 dim_flat[di][2])
            mins, strides, maxs = (dim_flat[di][3], dim_flat[di][4],
                                   dim_flat[di][5])
            parts = [resolve_probe(loc) for loc in d.probe_locs]
            if len(parts) == 1:
                pdata, pvalid = parts[0]
                probe = pdata.astype(jnp.int64)
                in_range = pvalid
            else:
                # recompute the build's monotone composite; rows with any
                # key outside the build ranges can alias a real composite
                # value, so they are excluded explicitly
                probe = jnp.zeros((cap,), jnp.int64)
                in_range = jnp.ones((cap,), bool)
                for k, (pdata, pvalid) in enumerate(parts):
                    pv = pdata.astype(jnp.int64)
                    in_range = in_range & pvalid \
                        & (pv >= mins[k]) & (pv <= maxs[k])
                    probe = probe + (pv - mins[k]) * strides[k]
            if dim_dense[di]:
                # contiguous keys: direct addressing, no binary search
                rel = probe - lo
                idx = jnp.clip(rel, 0, keys.shape[0] - 1).astype(jnp.int32)
                matched = ((rel >= 0) & (rel < n_valid.astype(jnp.int64))
                           & in_range)
            else:
                idx = jnp.searchsorted(keys, probe).astype(jnp.int32)
                idx = jnp.clip(idx, 0, keys.shape[0] - 1)
                matched = (jnp.take(keys, idx) == probe) \
                    & (idx < n_valid) & in_range
            alive = alive & matched
            dim_idx[di] = idx

        # ---- joined batch for the layers above the join ----------------
        top_cols: List[Optional[TpuColumnVector]] = \
            [None] * len(spec.top_output)
        for o in spec.needed_top:
            loc = spec.col_loc[o]
            if loc[0] == "fact":
                top_cols[o] = fact_cols[loc[1]]
            else:
                _, di, lo = loc
                j = dims[di].payload_ordinals.index(lo)
                pdata = dim_flat[di][6 + 2 * j]
                pvalid = dim_flat[di][7 + 2 * j]
                top_cols[o] = TpuColumnVector(
                    spec.top_output[o].dtype,
                    jnp.take(pdata, dim_idx[di]),
                    jnp.take(pvalid, dim_idx[di]), cap)
        for o in range(len(spec.top_output)):
            if top_cols[o] is None:
                top_cols[o] = TpuColumnVector(
                    spec.top_output[o].dtype, jnp.zeros((cap,), jnp.int32),
                    jnp.zeros((cap,), jnp.bool_), cap)
        jbatch = TpuColumnarBatch(top_cols, cap)
        for layer in top_layers:
            if layer[0] == "filter":
                c = to_column(layer[1].eval_tpu(jbatch, tctx), jbatch)
                m = c.data.astype(jnp.bool_)
                if c.validity is not None:
                    m = m & c.validity
                alive = alive & m
            else:
                exprs, outs = layer[1], layer[2]
                new_cols = []
                for e, a in zip(exprs, outs):
                    src = e.children[0] if isinstance(e, Alias) else e
                    if isinstance(src, AttributeReference) \
                            and src.ordinal is not None:
                        new_cols.append(jbatch.columns[src.ordinal])
                    else:
                        new_cols.append(to_column(
                            e.eval_tpu(jbatch, tctx), jbatch, a.dtype))
                jbatch = TpuColumnarBatch(new_cols, cap)

        # ---- grouped segment aggregation -------------------------------
        if spec.group_dim is not None:
            gcode = jnp.where(alive, dim_idx[spec.group_dim],
                              jnp.int32(G - 1))
        else:
            gcode = jnp.where(alive, jnp.int32(0), jnp.int32(1))
        carry: List = [jax.ops.segment_sum(
            alive.astype(jnp.int64), gcode, num_segments=G)]
        for fn_ in spec.agg_fns:
            if fn_.children:
                c = to_column(fn_.children[0].eval_tpu(jbatch, tctx),
                              jbatch, fn_.children[0].dtype)
                v = c.validity if c.validity is not None else rowmask
                carry.extend(_segment_states(fn_, c.data, v & alive,
                                             gcode, G))
            else:
                carry.extend(_segment_states(fn_, None, alive, gcode, G))
        return tuple(carry)

    fn = jax.jit(stage)
    with _JOIN_CACHE_LOCK:
        _JOIN_STAGE_FN_CACHE[key] = fn
    return fn


import functools as _functools


@_functools.partial(jax.jit, static_argnames=("ops",))
def _merge_carries_dev(cs, ops):
    out = list(cs[0])
    for nxt in cs[1:]:
        for i, op in enumerate(ops):
            if op == "sum":
                out[i] = out[i] + nxt[i]
            elif op == "min":
                out[i] = jnp.minimum(out[i], nxt[i])
            elif op == "max":
                out[i] = jnp.maximum(out[i], nxt[i])
            else:  # or
                out[i] = out[i] | nxt[i]
    return tuple(out)


@_functools.partial(jax.jit, static_argnames=("cap_occ",))
def _compact_carries_dev(ms, mask, cap_occ):
    pos = jnp.cumsum(mask) - 1
    n = int(mask.shape[0])
    idx = jnp.zeros((cap_occ,), jnp.int32).at[
        jnp.where(mask, pos, cap_occ)].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return (idx,) + tuple(jnp.take(m, idx, axis=0) for m in ms)


@_functools.partial(jax.jit, static_argnames=("cap_occ", "fnspec"))
def _finalize_output_dev(merged, occ_mask, key_cols, cap_occ, fnspec):
    """Compact + finalize IN HBM: occupied-group indices, gathered group-key
    columns, and per-aggregate (value, validity) arrays — the device-output
    path of the compiled join stage. fnspec: per fn a tuple
    (op, is_fp, out_dtype_str)."""
    pos = jnp.cumsum(occ_mask) - 1
    n = int(occ_mask.shape[0])
    idx = jnp.zeros((cap_occ,), jnp.int32).at[
        jnp.where(occ_mask, pos, cap_occ)].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    live = jnp.arange(cap_occ) < jnp.sum(occ_mask)
    keys_out = []
    for kdata, kvalid in key_cols:
        kd = jnp.take(kdata, idx, axis=0)
        kv = live if kvalid is None else (jnp.take(kvalid, idx) & live)
        keys_out.append((kd, kv))
    aggs_out = []
    ci = 1  # merged[0] = rowcount
    for op, is_fp, dt_str in fnspec:
        dt = np.dtype(dt_str)
        if op == "count":
            v = jnp.take(merged[ci], idx).astype(dt)
            aggs_out.append((v, live))
            ci += 1
        elif op in ("sum", "avg"):
            s = jnp.take(merged[ci], idx)
            c = jnp.take(merged[ci + 1], idx)
            valid = (c > 0) & live
            if op == "avg":
                v = s.astype(jnp.float64) / jnp.where(c > 0, c, 1)
            else:
                v = s.astype(dt)
            aggs_out.append((jnp.where(valid, v, jnp.zeros((), v.dtype)),
                             valid))
            ci += 2
        elif is_fp:  # min/max float: clean, nan_any, nonnan, nonnull
            clean = jnp.take(merged[ci], idx)
            nan_any = jnp.take(merged[ci + 1], idx)
            nonnan = jnp.take(merged[ci + 2], idx)
            nonnull = jnp.take(merged[ci + 3], idx)
            # Spark NaN-greatest: max → NaN if any NaN; min → NaN only if
            # the whole group is NaN
            if op == "max":
                v = jnp.where(nan_any, jnp.float64(np.nan),
                              clean.astype(jnp.float64))
            else:
                v = jnp.where(nonnan > 0, clean.astype(jnp.float64),
                              jnp.float64(np.nan))
            valid = (nonnull > 0) & live
            aggs_out.append((jnp.where(valid, v, 0.0).astype(dt), valid))
            ci += 4
        else:  # min/max integral
            red = jnp.take(merged[ci], idx)
            nonnull = jnp.take(merged[ci + 1], idx)
            valid = (nonnull > 0) & live
            aggs_out.append((jnp.where(valid, red,
                                       jnp.zeros((), red.dtype)).astype(dt),
                             valid))
            ci += 2
    return idx, tuple(keys_out), tuple(aggs_out)


# process-wide dim-build cache: the physical plan is rebuilt per execution,
# so instance-level memoization never survives a re-collect. Keyed by the
# IDENTITY of the source data objects (strong refs held and re-verified, so
# id() reuse can never alias) + the dim chain's structural description —
# the broadcast-relation reuse semantics across replans. Bounded LRU: each
# entry pins device arrays.
import collections as _collections

# key -> (source refs, built arrays, {group ordinals: uniqueness verdict}).
# The uniqueness verdicts live INSIDE the build entry so a dim rebuilt over
# changed source data (source-identity mismatch below) starts with no
# memoized verdict — a structurally-keyed side table would serve a stale
# "unique" answer after a rebuild and silently split SQL groups.
_DIM_BUILD_CACHE: "_collections.OrderedDict" = _collections.OrderedDict()
#: guards the OrderedDict's LRU bookkeeping (move_to_end/popitem) against
#: concurrent fact-side tasks sharing one dimension cache
_DIM_CACHE_LOCK = _threading.Lock()


def clear_dim_cache() -> None:
    """Release the cached dimension builds (host tables, source refs, the
    HBM key/payload arrays they pin, and their uniqueness verdicts)."""
    with _DIM_CACHE_LOCK:
        _DIM_BUILD_CACHE.clear()


def _dim_sources(plan: PhysicalPlan):
    out = []
    for n in plan.collect_nodes():
        t = getattr(n, "table", None)
        if t is not None:
            out.append(t)
        b = getattr(n, "batches", None)
        if b is not None:
            out.extend(b)
    return out


def _dim_structure(plan: PhysicalPlan) -> str:
    return "|".join(n.node_desc() for n in plan.collect_nodes())


# ---------------------------------------------------------------------------
# the exec
# ---------------------------------------------------------------------------


class TpuCompiledJoinAggStageExec(TpuExec):
    """The fused fact→probe-chain→group-by stage (one jit per shape)."""

    def __init__(self, spec: _JoinStageSpec, fallback: PhysicalPlan,
                 max_dim_rows: int):
        super().__init__([spec.fact_source])
        self.spec = spec
        self.fallback = fallback
        self.max_dim_rows = max_dim_rows
        # dims materialize ONCE per plan instance and are reused across
        # re-executions — the broadcast-relation semantics
        # (TpuBroadcastHashJoinExec._build_side memoizes the same way)
        self._dims_built = None

    @property
    def output(self):
        return self.spec.output

    def num_partitions(self) -> int:
        return 1

    def collect_nodes(self):
        # keep the fallback AND dim subtrees reachable: they hold the
        # exchanges whose shuffle state the session releases at query end
        out = super().collect_nodes()
        seen = {id(n) for n in out}
        for sub in [self.fallback] + [d.plan for d in self.spec.dims]:
            for n in sub.collect_nodes():
                if id(n) not in seen:
                    seen.add(id(n))
                    out.append(n)
        return out

    def node_desc(self) -> str:
        keys = ", ".join(g.name for g in self.spec.grouping) or "<global>"
        return (f"TpuCompiledJoinAggStage[keys={keys}, "
                f"dims={len(self.spec.dims)}]")

    def additional_metrics(self):
        return {"stageTime": "MODERATE", "buildTime": "MODERATE",
                "numGroups": "DEBUG", "fallbackReruns": "DEBUG"}

    def query_counters(self):
        return [("joinstage.fallback_reruns", self.metrics["fallbackReruns"])]

    def internal_do_execute_columnar(self, idx: int,
                                     ctx: TaskContext) -> Iterator:
        from ..memory.hbm import TpuRetryOOM, TpuSplitAndRetryOOM
        try:
            result = self._run_compiled(ctx)
        except (_JoinStageFallback, TpuRetryOOM, TpuSplitAndRetryOOM):
            result = None
        if result is None:
            self.metrics["fallbackReruns"].add(1)
            for p in range(self.fallback.num_partitions()):
                yield from self.fallback.execute_partition(p, ctx)
            return
        yield result

    # -- dimension build ---------------------------------------------------

    def _build_dim(self, d: _DimSpec, ctx: TaskContext):
        """Materialize one dimension: host-sorted arrow table + device
        (sorted_keys_i64 padded with int64.max, n_valid, payload arrays)."""
        import pyarrow as pa
        import pyarrow.compute as pc
        batches = []
        for p in range(d.plan.num_partitions()):
            pctx = TaskContext(p, ctx.conf)
            try:
                batches.extend(d.plan.execute_partition(p, pctx))
            finally:
                pctx.complete()
        if batches:
            table = concat_batches(batches).to_arrow()
        else:
            table = pa.Table.from_arrays(
                [pa.nulls(0, _arrow_of(a.dtype)) for a in d.plan.output],
                names=[a.name for a in d.plan.output])
        if table.num_rows > self.max_dim_rows:
            raise _JoinStageFallback()

        def key_i64(ordinal):
            kc = table.column(ordinal)
            if isinstance(kc, pa.ChunkedArray):
                kc = kc.combine_chunks()
            if pa.types.is_date32(kc.type) or pa.types.is_time32(kc.type):
                kc = kc.cast(pa.int32())
            return np.asarray(kc.cast(pa.int64()).to_numpy(
                zero_copy_only=False), np.int64)

        valid = None
        for o in d.key_ordinals:
            kc = table.column(o)
            v = pc.is_valid(kc.combine_chunks()
                            if isinstance(kc, pa.ChunkedArray) else kc)
            valid = v if valid is None else pc.and_(valid, v)
        table = table.filter(valid)
        key_parts = [key_i64(o) for o in d.key_ordinals]
        nk = len(key_parts)
        if nk == 1:
            keys = key_parts[0]
            mins = np.zeros(1, np.int64)
            strides = np.ones(1, np.int64)
            maxs = np.full(1, np.iinfo(np.int64).max - 1, np.int64)
        else:
            # monotone composite: (k_i - min_i) * stride_i summed; the probe
            # recomputes the same packing, so a single searchsorted covers
            # the whole multi-column key
            mins = np.array([k.min() if len(k) else 0 for k in key_parts],
                            np.int64)
            maxs = np.array([k.max() if len(k) else 0 for k in key_parts],
                            np.int64)
            # python-int spans: an int64-wrapping span (keys near both
            # extremes) must fail the guard, not alias past it
            spans = [int(hi) - int(lo) + 1 for lo, hi in zip(mins, maxs)]
            prod = 1
            for sp in spans:
                prod *= sp
            if prod >= 2**62:
                raise _JoinStageFallback()  # composite would overflow
            strides = np.ones(nk, np.int64)
            for i in range(nk - 2, -1, -1):
                strides[i] = strides[i + 1] * spans[i + 1]
            keys = np.zeros(len(key_parts[0]), np.int64)
            for k, mn, st in zip(key_parts, mins, strides):
                keys = keys + (k - mn) * st
        order = np.argsort(keys, kind="stable")
        keys = keys[order]
        if d.semi:
            first = np.ones(len(keys), bool)
            first[1:] = keys[1:] != keys[:-1]
            order = order[first]
            keys = keys[first]
        elif len(keys) and bool(np.any(keys[1:] == keys[:-1])):
            raise _JoinStageFallback()  # fan-out join: not many-to-one
        sorted_tbl = table.take(pa.array(order, pa.int64()))
        n = len(keys)
        cap_d = bucket_capacity(n)
        padded = np.full(cap_d, np.iinfo(np.int64).max, np.int64)
        padded[:n] = keys
        # dense contiguous keys (sequential PKs — the common dimension
        # shape): probe resolves by SUBTRACTION instead of a 20-gather
        # binary search over HBM — the probe program's dominant cost
        dense = bool(nk == 1 and n and keys[0] + n - 1 == keys[-1]
                     and np.all(np.diff(keys) == 1))
        lo = int(keys[0]) if n else 0
        flat = [jnp.asarray(padded), jnp.int32(n),
                jnp.int64(lo if dense else 0),
                jnp.asarray(mins), jnp.asarray(strides), jnp.asarray(maxs)]
        for o in d.payload_ordinals:
            vec = TpuColumnVector.from_arrow(sorted_tbl.column(o))
            if vec.offsets is not None or vec.host_data is not None \
                    or vec.children is not None:
                raise _JoinStageFallback()
            data, vv = vec.data, vec.validity
            if data.shape[0] != cap_d:
                pad = cap_d - data.shape[0]
                data = jnp.pad(data, (0, pad)) if pad > 0 else data[:cap_d]
                if vv is not None:
                    vv = jnp.pad(vv, (0, pad)) if pad > 0 else vv[:cap_d]
            if vv is None:
                vv = row_mask(n, cap_d)
            flat.extend([data, vv])
        return sorted_tbl, tuple(flat), cap_d, dense

    # -- the run -----------------------------------------------------------

    def _run_compiled(self, ctx: TaskContext) -> TpuColumnarBatch:
        from ..memory.spill import SpillableColumnarBatch
        spec = self.spec
        if self._dims_built is None:
            with self.metrics["buildTime"].timed():
                dim_tables, dim_flats, dim_caps, dim_dense = [], [], [], []
                from ..config import ANSI_ENABLED, SESSION_TZ
                # eval-relevant session conf is part of the key: the same
                # dim plan under a different timezone/ANSI setting must not
                # reuse a stale build across sessions sharing source tables
                conf_fp = (ctx.conf.get(SESSION_TZ),
                           ctx.conf.get(ANSI_ENABLED))
                dim_entries = []
                for d in spec.dims:
                    key = (_dim_structure(d.plan), tuple(d.key_ordinals),
                           tuple(d.payload_ordinals), d.semi, conf_fp)
                    srcs = _dim_sources(d.plan)
                    with _DIM_CACHE_LOCK:
                        hit = _DIM_BUILD_CACHE.get(key)
                        if hit is not None and len(hit[0]) == len(srcs) \
                                and all(a is b
                                        for a, b in zip(hit[0], srcs)):
                            entry = hit
                            _DIM_BUILD_CACHE.move_to_end(key)
                        else:
                            entry = None
                    if entry is None:
                        # rebuild (outside the lock: device uploads are
                        # slow): fresh entry, fresh (empty) verdict memo —
                        # a racing rebuild just wins last, benignly
                        entry = (srcs, self._build_dim(d, ctx), {})
                        from ..config import COMPILED_JOIN_DIM_CACHE_SIZE
                        cache_max = ctx.conf.get(COMPILED_JOIN_DIM_CACHE_SIZE)
                        with _DIM_CACHE_LOCK:
                            _DIM_BUILD_CACHE[key] = entry
                            while len(_DIM_BUILD_CACHE) > cache_max:
                                _DIM_BUILD_CACHE.popitem(last=False)
                    tbl, flat, cap_d, dense = entry[1]
                    dim_tables.append(tbl)
                    dim_flats.append(flat)
                    dim_caps.append(cap_d)
                    dim_dense.append(dense)
                    dim_entries.append(entry)
                if getattr(spec, "group_unique_check", False):
                    # group keys are a subset of the dim's join keys:
                    # row-index grouping is correct only if those columns
                    # alone are unique over the materialized dim. Ordinal-
                    # based and numpy-side: attribute NAMES are not unique,
                    # so pyarrow group_by could KeyError instead of falling
                    # back. Verdict memoized IN the dim's build-cache entry
                    # (a rebuild over changed sources starts a fresh memo).
                    verdicts = dim_entries[spec.group_dim][2]
                    uord = tuple(spec.group_key_ordinals)
                    uniq = verdicts.get(uord)
                    if uniq is None:
                        gt = dim_tables[spec.group_dim]
                        uniq = True
                        if gt.num_rows > 1:
                            arrs = [np.asarray(
                                gt.column(o).combine_chunks()
                                .to_numpy(zero_copy_only=False))
                                for o in spec.group_key_ordinals]
                            order = np.lexsort(arrs[::-1])
                            eq = np.ones(gt.num_rows - 1, bool)
                            for a in arrs:
                                s = a[order]
                                eq &= s[1:] == s[:-1]
                            uniq = not bool(np.any(eq))
                        verdicts[uord] = uniq
                    if not uniq:
                        raise _JoinStageFallback()
                self._dims_built = (dim_tables, dim_flats, dim_caps,
                                    tuple(dim_dense))
        dim_tables, dim_flats, dim_caps, dim_dense = self._dims_built
        held: List[SpillableColumnarBatch] = []
        carries = []
        try:
            # the plan-tree link, not the captured spec.fact_source: passes
            # after stage compilation (segment fusion, coalescing) rewrite
            # children[0] and the stale pointer would bypass them
            src = self.children[0]
            for p in range(src.num_partitions()):
                pctx = TaskContext(p, ctx.conf)
                try:
                    for b in src.execute_partition(p, pctx):
                        if b.num_rows:
                            held.append(SpillableColumnarBatch(b))
                finally:
                    pctx.complete()
            with self.metrics["stageTime"].timed():
                for sb in held:
                    b = sb.get_batch()
                    carries.append(self._run_batch(
                        b, dim_flats, tuple(dim_caps), dim_dense, ctx))
                # carries are G-sized (G = group-dim capacity, can be
                # millions): merge across batches ON DEVICE and fetch ONLY
                # the occupied groups — a full-G download through a
                # high-latency link costs more than the whole query.
                # With device_output, not even the occupied groups download:
                # the stage finalizes in HBM and emits device columns.
                if carries and getattr(spec, "device_output", False) \
                        and spec.grouping:
                    out = self._device_finalize(carries, dim_flats)
                    if out is not None:
                        return out
                if carries:
                    occ_np, carry_np, nocc = self._merge_and_compact(carries)
                else:
                    occ_np, carry_np, nocc = np.zeros(0, np.int64), [], 0
        finally:
            for sb in held:
                sb.close()
        return self._assemble_compact(dim_tables, occ_np, carry_np, nocc,
                                      ctx)

    def _carry_combine_ops(self) -> List[str]:
        """Elementwise combine op per carry slot, mirroring
        _np_merge_carries' layout exactly."""
        from .compiled import _is_fp
        ops = ["sum"]  # rowcount
        for fn in self.spec.agg_fns:
            op = fn.update_op
            if not fn.children or op == "count":
                ops.append("sum")
            elif op in ("sum", "avg"):
                ops.extend(["sum", "sum"])
            elif _is_fp(fn.children[0].dtype):
                ops.extend([op, "or", "sum", "sum"])
            else:
                ops.extend([op, "sum"])
        return ops

    def _merge_occ(self, carries):
        """Shared prologue of both download paths: device merge across
        batches + occupied-group mask (slot G-1 holds dropped rows) + the
        single scalar sync for the occupied count."""
        ops = tuple(self._carry_combine_ops())
        merged = (_merge_carries_dev(tuple(carries), ops)
                  if len(carries) > 1 else carries[0])
        G = int(merged[0].shape[0])
        if self.spec.grouping:
            occ_mask = merged[0][:G - 1] > 0
        else:
            occ_mask = jnp.ones((1,), bool)
        nocc = int(jnp.sum(occ_mask))  # the one scalar sync
        return merged, occ_mask, nocc, bucket_capacity(max(nocc, 1))

    def _merge_and_compact(self, carries):
        """Device-side cross-batch carry merge + occupied-group compaction:
        two small programs and ONE scalar sync, then a download whose size
        scales with the RESULT (occupied groups), not the group capacity."""
        merged, occ_mask, nocc, cap_occ = self._merge_occ(carries)
        from ..columnar.vector import audited_device_get
        host = audited_device_get(
            _compact_carries_dev(tuple(merged), occ_mask, cap_occ),
            "carries")
        return host[0][:nocc], [h[:nocc] for h in host[1:]], nocc

    def _device_finalize(self, carries, dim_flats):
        """Device-output path: merge, compact, finalize and emit a DEVICE
        batch (one scalar sync for the row count; no aggregate download)."""
        from .compiled import _is_fp
        spec = self.spec
        merged, occ_mask, nocc, cap_occ = self._merge_occ(carries)
        gd = spec.dims[spec.group_dim]
        key_cols = []
        for o in spec.group_key_ordinals:
            j = gd.payload_ordinals.index(o)
            key_cols.append((dim_flats[spec.group_dim][6 + 2 * j],
                             dim_flats[spec.group_dim][7 + 2 * j]))
        fnspec = []
        for fn in spec.agg_fns:
            is_fp = bool(fn.children) and _is_fp(fn.children[0].dtype)
            out_dt = np.dtype(np.float64) if fn.update_op == "avg" \
                else np.dtype(fn.dtype.np_dtype)
            fnspec.append((fn.update_op, is_fp, out_dt.str))
        _, keys_out, aggs_out = _finalize_output_dev(
            merged, occ_mask, tuple(key_cols), cap_occ, tuple(fnspec))
        ng = len(spec.grouping)
        cols = []
        for (kd, kv), attr in zip(keys_out, spec.output[:ng]):
            cols.append(TpuColumnVector(attr.dtype, kd, kv, nocc))
        for (vd, vv), attr in zip(aggs_out, spec.output[ng:]):
            cols.append(TpuColumnVector(attr.dtype, vd, vv, nocc))
        self.metrics["numGroups"].add(nocc)
        return TpuColumnarBatch(cols, nocc,
                                [a.name for a in spec.output])

    def _run_batch(self, b: TpuColumnarBatch, dim_flats,
                   dim_caps: Tuple[int, ...], dim_dense, ctx: TaskContext):
        spec = self.spec
        cap = b.capacity
        flat = []
        for o in spec.fact_needed_source:
            col = b.columns[o]
            if col.offsets is not None or col.host_data is not None \
                    or col.children is not None:
                raise _JoinStageFallback()
            flat.append(col.data)
            flat.append(col.validity if col.validity is not None
                        else row_mask(b.num_rows, cap))
        fn = _build_join_stage_fn(spec, cap, dim_caps, dim_dense,
                                  ctx.eval_ctx)
        # compiled-stage launch = one device dispatch: chaos site + bounded
        # transient retry (the stage fn is pure over its device inputs)
        from ..chaos import inject
        from ..failure import with_device_retry
        from ..obs import tracer as _obs

        if _obs._ACTIVE:
            _obs.event("dispatch", cat="dispatch", kind="compiledjoin",
                       source="compiled")

        def dispatch():
            inject("device.dispatch", detail="compiled_join_stage")
            return fn(row_mask(b.num_rows, cap), tuple(flat),
                      tuple(dim_flats))

        return with_device_retry(dispatch, ctx.conf)

    def _assemble_compact(self, dim_tables, occ_np, carry_np, nocc: int,
                          ctx: TaskContext):
        """Host finalize over OCCUPIED groups only: occ_np holds the group
        dim row of each occupied group; carry_np the compacted states."""
        import pyarrow as pa

        from ..types import to_arrow as t2a
        from .aggregates import _bind_agg_refs
        spec = self.spec

        if nocc == 0 or not carry_np:
            if spec.grouping:
                return _host_batch(pa.Table.from_arrays(
                    [pa.nulls(0, t2a(a.dtype)) for a in spec.output],
                    names=[a.name for a in spec.output]))
            rowcount = np.zeros(1, np.int64)
            states: List[Optional[Dict]] = [None] * len(spec.agg_fns)
            occ_idx = np.array([0])
        else:
            # one already-merged compacted carry: reuse the shared merge
            # walker to lay the state dicts out
            rowcount, states = _np_merge_carries(spec, [tuple(carry_np)])
            occ_idx = np.arange(nocc)
        self.metrics["numGroups"].add(len(occ_idx))

        key_arrays = []
        if spec.grouping:
            gtbl = dim_tables[spec.group_dim]
            take_idx = pa.array(np.asarray(occ_np, np.int64), pa.int64())
            for o in spec.group_key_ordinals:
                col = gtbl.column(o).take(take_idx)
                if isinstance(col, pa.ChunkedArray):
                    col = col.combine_chunks()
                key_arrays.append(col)
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


def _arrow_of(dtype: DataType):
    from ..types import to_arrow
    return to_arrow(dtype)


def compile_join_agg_stages(plan: PhysicalPlan, conf) -> PhysicalPlan:
    """Post-pass over the physical tree: replace eligible join-aggregate
    subtrees with compiled join stages
    (spark.rapids.tpu.join.compiledStage.enabled). Runs BEFORE the plain
    compiled-agg pass so join pipelines get the fused treatment."""
    from ..config import (ANSI_ENABLED, COMPILED_JOIN_ENABLED,
                          COMPILED_JOIN_MAX_DIM_ROWS)
    if not conf.get(COMPILED_JOIN_ENABLED) or conf.get(ANSI_ENABLED):
        return plan
    max_dim = conf.get(COMPILED_JOIN_MAX_DIM_ROWS)

    def rewrite(node: PhysicalPlan) -> PhysicalPlan:
        spec = try_extract_join_stage(node)
        if spec is not None:
            return TpuCompiledJoinAggStageExec(spec, node, max_dim)
        node.children = [rewrite(c) for c in node.children]
        return node

    return rewrite(plan)
