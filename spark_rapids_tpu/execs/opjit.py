"""Jit-compiled per-operator executable cache for the GENERAL execution path.

The compiled whole-stage paths (compiled.py, compiled_join.py) rest on the
premise that the fixed cost of a program launch (dispatch + sync; its value
on the directly attached chip is in PERF.md), not kernel time, dominates a
chain of small operators — but they only cover a narrow eligibility window.
Everything else runs the general path, which evaluates expression trees
eagerly op by op: hundreds of launches, and on a TPU hundreds of small
compiles, for one multi-join query.

This module closes that gap without a whole-stage rewrite: each operator's
per-batch device transform (a projection forest, a filter predicate, a join
side's key encoding, the hash partitioner, the sort-based aggregate's sort
and reduce phases) is traced ONCE into a jitted XLA program and cached
process-wide, keyed by a structural fingerprint of the expression forest
(class/ordinal/literal/scalar-attrs — the compiled.py fingerprint idiom,
hardened with non-child scalar attributes) plus the bucketed batch capacity,
input carrier dtypes and validity layout. Re-running the same operator over
any batch of the same bucketed shape reuses the executable: the general
path's dispatch count drops from O(expression nodes) to O(operators).

Unlike the compiled stages there is NO eligibility window:

* host-assisted expressions split the trace at the host boundary — the
  device-pure subtrees under a host node each run as their own cached
  executable (spliced back via a precomputed-column leaf) while the host
  patch stays eager;
* anything that cannot trace at all (ANSI host-sync checks, string kernels
  that size on data, nondeterministic task-state reads) is detected either
  statically or by the optimistic first trace failing with a concretization
  error, after which the fingerprint is pinned to the eager path — results
  are bit-identical to eager evaluation either way.

Cache behavior surfaces through the opJitCacheHits / opJitCacheMisses /
opJitTraceTime metrics every TpuExec registers (execs/base.py) and the
spark.rapids.tpu.opjit.* tunables (config.py).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.batch import TpuColumnarBatch
from ..columnar.vector import TpuColumnVector, device_layout_ok
from ..config import OPJIT_CACHE_SIZE, OPJIT_ENABLED
from ..expressions.base import (Alias, AttributeReference, EvalContext,
                                Expression, Literal, to_column)
from ..obs import tracer as _obs
from ..types import (DataType, DecimalType, DoubleT, IntegerT, LongT,
                     NullType, StringType, is_fixed_width)

# ---------------------------------------------------------------------------
# process-wide LRU of compiled executables
# ---------------------------------------------------------------------------

_LOCK = threading.RLock()
_CACHE: "OrderedDict[Tuple, Any]" = OrderedDict()
#: fingerprints whose first trace failed — permanently eager. Kept OUTSIDE
#: the executable LRU so cache pressure can never evict a pin and re-pay the
#: doomed trace attempt per batch (own generous FIFO bound).
_EAGER_PINS: "OrderedDict[Tuple, None]" = OrderedDict()
_EAGER_PIN_MAX = 4096
#: programs on their first dispatch, not yet entries: threads that miss the
#: same key at once take the one jit object from here
_BUILDING: Dict[Tuple, Any] = {}
_FAILED = object()  # call outcome: run the eager fallback

#: process-wide counters (per-exec metrics mirror them)
_STATS = {"hits": 0, "misses": 0, "traces": 0, "trace_time_ns": 0}
#: dispatch accounting (docs/configs.md "Dispatch accounting"): one entry per
#: program dispatch through the cache, keyed by program kind ("segment",
#: "project", "filter", "joinenc", "exchsplit", "pids", "aggsort",
#: "aggreduce", plus the whole-stage/grouped kinds: "segmentg" — one fused
#: segment over a GROUP of partitions' batches, "exchsplitg" — the hash
#: encode+split of a whole partition group, "joinbuild" — a fused segment's
#: join build prepared once (sort + bucket directory), "joinprobe"/"joinemit"
#: — its streamed-side join probe and pair-emit+downstream halves a batch,
#: "aggstage" — the sort-based aggregate's whole update as one launch). A
#: fully fused N-operator chain shows ONE "segment" dispatch per batch where
#: the per-operator path shows N "project"/"filter" dispatches; "exchsplit"
#: likewise replaces a "pids"+split-plan pair, and the grouped kinds replace
#: one dispatch PER PARTITION with one per partition group.
_KIND_CALLS: Dict[str, int] = {}


def cache_stats() -> Dict[str, Any]:
    with _LOCK:
        return {**_STATS, "calls_by_kind": dict(_KIND_CALLS)}


def record_external_dispatch(kind: str) -> None:
    """Fold a program launch made OUTSIDE the opjit cache (e.g. the parquet
    device-decode programs, kind "parquet_decode") into the process-wide
    dispatch accounting: calls_by_kind, the timeline dispatch events, and
    therefore the diagnostics-bundle reconciliation all see it."""
    with _LOCK:
        _KIND_CALLS[kind] = _KIND_CALLS.get(kind, 0) + 1
    if _obs._ACTIVE:
        _obs.dispatch_event(kind, cache="extern", source=kind)


def cache_len() -> int:
    with _LOCK:
        return len(_CACHE)


def clear_cache() -> None:
    with _LOCK:
        _CACHE.clear()
        _BUILDING.clear()
        _EAGER_PINS.clear()


def enabled(eval_ctx: EvalContext) -> bool:
    try:
        return bool(eval_ctx.conf.get(OPJIT_ENABLED))
    except Exception:  # noqa: BLE001 — eval ctx without conf
        return False


def _trace_failure_types() -> Tuple[type, ...]:
    errs: List[type] = [NotImplementedError]
    for name in ("ConcretizationTypeError", "TracerArrayConversionError",
                 "TracerBoolConversionError", "TracerIntegerConversionError",
                 "NonConcreteBooleanIndexError", "UnexpectedTracerError"):
        e = getattr(jax.errors, name, None)
        if isinstance(e, type):
            errs.append(e)
    return tuple(errs)


_TRACE_FAILURES = _trace_failure_types()


def _note(metrics, name: str, v: int) -> None:
    if metrics:
        m = metrics.get(name)
        if m is not None:
            m.add(v)


def _dispatch(fn, args: Tuple, eval_ctx, kind: str,
              donated: bool = False):
    """One program launch through the chaos `device.dispatch` site and the
    transient-device-error retry: an UNAVAILABLE/RESOURCE_EXHAUSTED hiccup
    re-dispatches the (idempotent, cached) program with bounded backoff
    instead of killing the query; fatal statuses and trace failures
    propagate untouched (failure.with_device_retry). A dispatch with
    donated input buffers is NOT retried — after a failed launch the
    donated buffers' state is undefined."""
    from ..chaos import inject
    from ..failure import with_device_retry

    def call():
        inject("device.dispatch", detail=kind)
        return fn(*args)

    if donated:
        return call()
    return with_device_retry(call, getattr(eval_ctx, "conf", None))


def _cached_call(key: Tuple, build, args: Tuple, eval_ctx, metrics,
                 donate_argnums: Tuple[int, ...] = ()):
    """Run the program for `key`, tracing+compiling on first sight. Returns
    the program's output pytree, or _FAILED when the fingerprint is pinned
    eager (the caller runs its eager fallback)."""
    with _LOCK:
        if key in _EAGER_PINS:
            return _FAILED
        entry = _CACHE.get(key)
        if entry is not None:
            _CACHE.move_to_end(key)
    if entry is not None:
        _note(metrics, "opJitCacheHits", 1)
        with _LOCK:
            _STATS["hits"] += 1
            _KIND_CALLS[key[0]] = _KIND_CALLS.get(key[0], 0) + 1
        # one timeline event + per-query dispatch count per program
        # dispatch, recorded exactly where calls_by_kind increments so the
        # counters reconcile per query (even under concurrent queries)
        if _obs._ACTIVE:
            _obs.dispatch_event(key[0], cache="hit", source="opjit")
        return _dispatch(entry, args, eval_ctx, key[0],
                         donated=bool(donate_argnums))

    _note(metrics, "opJitCacheMisses", 1)
    with _LOCK:
        _STATS["misses"] += 1
        _KIND_CALLS[key[0]] = _KIND_CALLS.get(key[0], 0) + 1
    if _obs._ACTIVE:
        _obs.dispatch_event(key[0], cache="miss", source="opjit")
    # threads that miss one key at once (a mesh session's chips, each with
    # operands on its own chip) share ONE jit object: each compiles it for
    # its chip now, and the next query finds all of them behind the entry
    with _LOCK:
        fn = _BUILDING.get(key)
        if fn is None:
            fn = _BUILDING[key] = jax.jit(build(),
                                          donate_argnums=donate_argnums)
    t0 = time.perf_counter_ns()
    try:
        out = _dispatch(fn, args, eval_ctx, key[0],
                        donated=bool(donate_argnums))
    except _TRACE_FAILURES:
        # not traceable (host sync / host-assisted / ANSI check): pin eager
        with _LOCK:
            _BUILDING.pop(key, None)
            _EAGER_PINS[key] = None
            while len(_EAGER_PINS) > _EAGER_PIN_MAX:
                _EAGER_PINS.popitem(last=False)
        return _FAILED
    except BaseException:
        with _LOCK:
            _BUILDING.pop(key, None)
        raise
    dt = time.perf_counter_ns() - t0
    _note(metrics, "opJitTraceTime", dt)
    with _LOCK:
        _STATS["traces"] += 1
        _STATS["trace_time_ns"] += dt
        _BUILDING.pop(key, None)
        _CACHE[key] = fn
        _evict(eval_ctx)
    return out


def _evict(eval_ctx) -> None:
    try:
        limit = int(eval_ctx.conf.get(OPJIT_CACHE_SIZE))
    except Exception:  # noqa: BLE001
        limit = 256
    with _LOCK:  # reentrant: callers already inside _LOCK pay nothing
        while len(_CACHE) > max(limit, 1):
            _CACHE.popitem(last=False)


def _donate(positions: Tuple[int, ...]) -> Tuple[int, ...]:
    """Buffer donation helps only where XLA owns the allocator; the CPU
    backend ignores it with a warning, so gate on the active backend."""
    return positions if jax.default_backend() != "cpu" else ()


# ---------------------------------------------------------------------------
# structural fingerprint (the compiled.py idiom + non-child scalar attrs)
# ---------------------------------------------------------------------------

_SCALAR_ATTRS = (bool, int, float, str, bytes, type(None))
#: Alias/AttributeReference (whose `name`/`expr_id` are display-only) never
#: reach _attr_fp, so only the memo fields need skipping here
_FP_SKIP_KEYS = {"children", "_ojfp", "_ojgate"}


def _attr_fp(e: Expression) -> str:
    """Non-child scalar attributes (hash seeds, format strings, flags, …)
    that change the traced program but are invisible to the tree shape."""
    items = []
    for k, v in sorted(getattr(e, "__dict__", {}).items()):
        if k in _FP_SKIP_KEYS or isinstance(v, Expression):
            continue
        if isinstance(v, _SCALAR_ATTRS):
            items.append(f"{k}={v!r}")
        elif isinstance(v, (tuple, list)) and all(
                isinstance(x, _SCALAR_ATTRS) for x in v):
            items.append(f"{k}={tuple(v)!r}")
        elif isinstance(v, DataType):
            items.append(f"{k}={type(v).__name__}")
    return ",".join(items)


def _fp(e: Expression) -> str:
    memo = getattr(e, "_ojfp", None)
    if memo is not None:
        return memo
    name = type(e).__name__
    if isinstance(e, Literal):
        extra = f"={e.value!r}"
    elif isinstance(e, AttributeReference):
        extra = f"@{e.ordinal}"
    elif isinstance(e, Alias):
        extra = ""
    else:
        a = _attr_fp(e)
        extra = f"[{a}]" if a else ""
    kids = ",".join(_fp(c) for c in e.children)
    out = f"{name}{extra}:{type(e.dtype).__name__}({kids})"
    try:
        object.__setattr__(e, "_ojfp", out)
    except Exception:  # noqa: BLE001 — slotted/frozen expression
        pass
    return out


# ---------------------------------------------------------------------------
# static jittability gate (optimistic: anything passing may still fall back
# via the first-trace failure path; anything failing is definitely eager)
# ---------------------------------------------------------------------------


def _nondet_classes() -> Tuple[type, ...]:
    """Expressions reading/mutating task state (partition id, row counters,
    input-file info) — all defined in expressions/misc.py. Tracing one would
    bake the state of the first batch into the cached program."""
    from ..expressions import misc as _misc
    return tuple(v for v in vars(_misc).values()
                 if isinstance(v, type) and issubclass(v, Expression)
                 and v.__module__ == _misc.__name__)


_NONDET: Tuple[type, ...] = _nondet_classes()

#: context-dependent nodes: their eval only works inside a parent-managed
#: scope (higher-order functions bind lambda variables), so a subtree
#: containing one can never be evaluated standalone
_CONTEXT_BOUND = frozenset(("LambdaFunction", "NamedLambdaVariable"))


def _gate_ok(e: Expression) -> bool:
    memo = getattr(e, "_ojgate", None)
    if memo is not None:
        return memo
    ok = True
    try:
        if isinstance(e, _NONDET) or type(e).__name__ in _CONTEXT_BOUND:
            ok = False  # task state / parent-managed scope: never standalone
        else:
            dt = e.dtype
            if isinstance(dt, (StringType, DecimalType, NullType)) \
                    or not is_fixed_width(dt) or not device_layout_ok(dt):
                ok = False
            elif isinstance(e, AttributeReference) and (
                    e.ordinal is None or e.ordinal < 0):
                ok = False
            elif not isinstance(e, (Literal, AttributeReference, Alias)):
                from ..plan.typechecks import all_expr_rules
                r = all_expr_rules().get(type(e))
                if r is not None and r.host_assisted:
                    ok = False
        if ok:
            ok = all(_gate_ok(c) for c in e.children)
    except Exception:  # noqa: BLE001 — unresolved dtype etc: not jittable
        ok = False
    try:
        object.__setattr__(e, "_ojgate", ok)
    except Exception:  # noqa: BLE001
        pass
    return ok


def _refs(exprs: Sequence[Expression]) -> List[int]:
    s = set()
    for e in exprs:
        for a in e.collect(lambda x: isinstance(x, AttributeReference)):
            if a.ordinal is not None and a.ordinal >= 0:
                s.add(a.ordinal)
    return sorted(s)


def _inputs_ok(exprs: Sequence[Expression], batch: TpuColumnarBatch) -> bool:
    """Referenced columns must be plain fixed-width device vectors (the gate
    covers dtypes; this covers the actual buffer layout)."""
    if not batch.columns:
        return False
    for o in _refs(exprs):
        if o >= len(batch.columns):
            return False
        c = batch.columns[o]
        if c.offsets is not None or c.host_data is not None \
                or c.child is not None or c.children is not None \
                or getattr(c.data, "ndim", 1) != 1:
            return False
    return True


def _input_sig(exprs, batch) -> Tuple:
    return tuple((o, str(batch.columns[o].data.dtype),
                  batch.columns[o].validity is not None,
                  type(batch.columns[o].dtype).__name__)
                 for o in _refs(exprs))


def _flat_args(batch, sig) -> List:
    # rows_arg: a deferred-compaction batch passes its pending device count
    # straight through as a program argument — no host sync on the chain
    args: List = [batch.rows_arg]
    for (o, _, has_v, _) in sig:
        c = batch.columns[o]
        args.append(c.data)
        if has_v:
            args.append(c.validity)
    return args


def _rebuild_batch(flat, sig, src_dtypes, n_cols: int, cap: int, rowmask):
    """Inside-trace reconstruction of the operator's input batch. Validity is
    normalized to (orig & rowmask) so padding rows are invalid — expressions
    see num_rows == cap, and the rowmask contribution the eager path gets
    from row_mask(num_rows) flows in through the input validities instead."""
    cols: List[Optional[TpuColumnVector]] = [None] * n_cols
    pos = 1  # flat[0] == num_rows
    for (o, _, has_v, _) in sig:
        data = flat[pos]
        pos += 1
        if has_v:
            v = flat[pos] & rowmask
            pos += 1
        else:
            v = rowmask
        cols[o] = TpuColumnVector(src_dtypes[o], data, v, cap)
    for o in range(n_cols):
        if cols[o] is None:  # unreferenced: typed dummy, never read
            cols[o] = TpuColumnVector(IntegerT, jnp.zeros((cap,), jnp.int32),
                                      jnp.zeros((cap,), jnp.bool_), cap)
    return TpuColumnarBatch(cols, cap)


def _conf_fp(eval_ctx) -> Tuple:
    # traced programs bake in everything eval reads off the context
    return (bool(eval_ctx.ansi), eval_ctx.tz)


_TRACE_CTXS: Dict[Tuple, EvalContext] = {}


def _trace_ctx(eval_ctx: EvalContext) -> EvalContext:
    """Detached minimal context captured by the traced closures. Cached
    programs are process-wide, so they must NOT pin a task's live
    EvalContext (its session conf, row counters, input-file fields): the
    trace context carries exactly the fingerprinted fields (ansi, tz) —
    gate-eligible expressions read nothing else off the context, and any
    future one that does bakes in a deterministic default, not whatever
    session happened to trace first."""
    key = _conf_fp(eval_ctx)
    with _LOCK:
        ctx = _TRACE_CTXS.get(key)
        if ctx is None:
            from ..config import RapidsConf
            ctx = EvalContext(RapidsConf({
                "spark.sql.ansi.enabled": "true" if key[0] else "false",
                "spark.sql.session.timeZone": key[1]}))
            _TRACE_CTXS[key] = ctx
    return ctx


# ---------------------------------------------------------------------------
# projection forests (TpuProjectExec, result projections, key evaluation)
# ---------------------------------------------------------------------------


class _Precomputed(Expression):
    """Leaf splicing an already-evaluated device result under a host-assisted
    parent — the host-boundary split point."""

    def __init__(self, result, dtype: DataType, nullable: bool):
        self.children = ()
        self._result = result
        self._dtype = dtype
        self._nullable = nullable

    @property
    def dtype(self) -> DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self._nullable

    @property
    def foldable(self) -> bool:
        return False

    def eval_tpu(self, batch, ctx=None):
        return self._result

    def eval_cpu(self, table, ctx=None):
        r = self._result
        if isinstance(r, TpuColumnVector):
            return r.to_arrow()
        return r.value

    def pretty(self) -> str:
        return f"<jit:{type(self._dtype).__name__}>"


def _passthrough(e: Expression) -> Optional[AttributeReference]:
    inner = e.children[0] if isinstance(e, Alias) else e
    return inner if isinstance(inner, AttributeReference) else None


def _forest_program(exprs, out_dtypes, batch, eval_ctx, metrics):
    """All-device forest → ONE executable returning (data, validity) per
    expression. None when the fingerprint is pinned eager."""
    cap = batch.capacity
    sig = _input_sig(exprs, batch)
    key = ("project", tuple(_fp(e) for e in exprs),
           tuple(type(d).__name__ for d in out_dtypes), cap,
           len(batch.columns), sig, _conf_fp(eval_ctx))
    src_dtypes = {o: batch.columns[o].dtype for (o, _, _, _) in sig}
    n_cols = len(batch.columns)
    exprs = list(exprs)
    out_dtypes = list(out_dtypes)

    tctx = _trace_ctx(eval_ctx)

    def build():
        def fn(*flat):
            rowmask = jnp.arange(cap) < flat[0]
            tb = _rebuild_batch(flat, sig, src_dtypes, n_cols, cap, rowmask)
            outs = []
            for e, dt in zip(exprs, out_dtypes):
                c = to_column(e.eval_tpu(tb, tctx), tb, dt)
                outs.append((c.data, c.validity))
            return tuple(outs)
        return fn

    out = _cached_call(key, build, tuple(_flat_args(batch, sig)),
                       eval_ctx, metrics)
    if out is _FAILED:
        return None
    return [TpuColumnVector(dt, data, v, batch.rows_lazy)
            for (data, v), dt in zip(out, out_dtypes)]


def _split_eval(e: Expression, batch, eval_ctx, metrics):
    """Evaluate one expression, jitting its maximal device-pure subtrees and
    leaving host-assisted nodes eager (the trace splits at the boundary).
    Only fully device-pure children are precomputed and spliced back — a
    child outside the gate (strings, lambdas, host data) stays untouched so
    the parent's own eval drives it with whatever context it needs."""
    if not e.children or isinstance(e, (Literal, AttributeReference)):
        return e.eval_tpu(batch, eval_ctx)  # leaf: no dispatch to save
    if _gate_ok(e) and _inputs_ok([e], batch):
        outs = _forest_program([e], [e.dtype], batch, eval_ctx, metrics)
        if outs is not None:
            return outs[0]
    new_kids = []
    changed = False
    for c in e.children:
        if (not c.children or isinstance(c, (Literal, AttributeReference))
                or not _gate_ok(c) or not _inputs_ok([c], batch)):
            new_kids.append(c)
            continue
        r = _split_eval(c, batch, eval_ctx, metrics)
        new_kids.append(_Precomputed(r, c.dtype, c.nullable))
        changed = True
    node = e.with_children(new_kids) if changed else e
    return node.eval_tpu(batch, eval_ctx)


def eval_exprs(exprs: Sequence[Expression], out_dtypes: Sequence[DataType],
               batch: TpuColumnarBatch, eval_ctx: EvalContext,
               metrics=None) -> List[TpuColumnVector]:
    """Evaluate a projection forest into columns. Jittable expressions fuse
    into one cached executable; the rest run eagerly with device-pure
    subtrees routed through the cache. Disabled → plain eager evaluation."""
    if not enabled(eval_ctx):
        return [to_column(e.eval_tpu(batch, eval_ctx), batch, dt)
                for e, dt in zip(exprs, out_dtypes)]
    results: List[Optional[TpuColumnVector]] = [None] * len(exprs)
    jit_idx: List[int] = []
    for i, e in enumerate(exprs):
        a = _passthrough(e)
        if a is not None:
            results[i] = to_column(a.eval_tpu(batch, eval_ctx), batch,
                                   out_dtypes[i])
        elif _gate_ok(e) and _inputs_ok([e], batch):
            jit_idx.append(i)
        else:
            results[i] = to_column(
                _split_eval(e, batch, eval_ctx, metrics), batch,
                out_dtypes[i])
    if jit_idx:
        outs = _forest_program([exprs[i] for i in jit_idx],
                               [out_dtypes[i] for i in jit_idx],
                               batch, eval_ctx, metrics)
        if outs is None:
            for i in jit_idx:
                results[i] = to_column(
                    _split_eval(exprs[i], batch, eval_ctx, metrics), batch,
                    out_dtypes[i])
        else:
            for i, c in zip(jit_idx, outs):
                results[i] = c
    return results


# ---------------------------------------------------------------------------
# filter predicate (TpuFilterExec)
# ---------------------------------------------------------------------------


def filter_mask(cond: Expression, batch: TpuColumnarBatch,
                eval_ctx: EvalContext, metrics=None):
    """Keep-mask (cond & validity) as one executable; None → caller eager."""
    if not enabled(eval_ctx) or not (_gate_ok(cond)
                                     and _inputs_ok([cond], batch)):
        return None
    cap = batch.capacity
    sig = _input_sig([cond], batch)
    key = ("filter", _fp(cond), cap, len(batch.columns), sig,
           _conf_fp(eval_ctx))
    src_dtypes = {o: batch.columns[o].dtype for (o, _, _, _) in sig}
    n_cols = len(batch.columns)

    tctx = _trace_ctx(eval_ctx)

    def build():
        def fn(*flat):
            rowmask = jnp.arange(cap) < flat[0]
            tb = _rebuild_batch(flat, sig, src_dtypes, n_cols, cap, rowmask)
            c = to_column(cond.eval_tpu(tb, tctx), tb)
            mask = c.data.astype(jnp.bool_)
            if c.validity is not None:
                mask = mask & c.validity  # null predicate → drop row
            return mask
        return fn

    out = _cached_call(key, build, tuple(_flat_args(batch, sig)),
                       eval_ctx, metrics)
    return None if out is _FAILED else out


# ---------------------------------------------------------------------------
# join key encoding (execs/joins.py _encode_sides, fixed-width branch)
# ---------------------------------------------------------------------------


def encode_join_sides(left_keys: Sequence[Expression],
                      right_keys: Sequence[Expression],
                      left: TpuColumnarBatch, right: TpuColumnarBatch,
                      eval_ctx: EvalContext, metrics=None):
    """Both sides' (key eval → cross-side-comparable encode) as ONE
    executable, mirroring joins._encode_sides' fixed-width branch (the
    64-bit limb split is a per-key-PAIR decision, so both sides must trace
    together). Returns (l_enc, r_enc) or None (caller runs _encode_sides)."""
    if not enabled(eval_ctx):
        return None
    keys = list(left_keys) + list(right_keys)
    if not all(_gate_ok(k) for k in keys) \
            or any(isinstance(k.dtype, StringType) for k in keys) \
            or not _inputs_ok(left_keys, left) \
            or not _inputs_ok(right_keys, right):
        return None
    l_cap, r_cap = left.capacity, right.capacity
    l_sig = _input_sig(left_keys, left)
    r_sig = _input_sig(right_keys, right)
    key = ("joinenc", tuple(_fp(k) for k in left_keys),
           tuple(_fp(k) for k in right_keys), l_cap, r_cap,
           len(left.columns), len(right.columns), l_sig, r_sig,
           _conf_fp(eval_ctx))
    l_dtypes = {o: left.columns[o].dtype for (o, _, _, _) in l_sig}
    r_dtypes = {o: right.columns[o].dtype for (o, _, _, _) in r_sig}
    nl, nr = len(left.columns), len(right.columns)
    left_keys, right_keys = list(left_keys), list(right_keys)
    l_args = _flat_args(left, l_sig)
    r_args = _flat_args(right, r_sig)

    tctx = _trace_ctx(eval_ctx)

    def build():
        def fn(l_flat, r_flat):
            from .aggregates import _sortable_bits
            from .joins import encode_fixed_key_pair
            l_mask = jnp.arange(l_cap) < l_flat[0]
            r_mask = jnp.arange(r_cap) < r_flat[0]
            lt = _rebuild_batch(l_flat, l_sig, l_dtypes, nl, l_cap, l_mask)
            rt = _rebuild_batch(r_flat, r_sig, r_dtypes, nr, r_cap, r_mask)
            l_enc, r_enc = [], []
            for lk, rk in zip(left_keys, right_keys):
                lc = to_column(lk.eval_tpu(lt, tctx), lt, lk.dtype)
                rc = to_column(rk.eval_tpu(rt, tctx), rt, rk.dtype)
                encode_fixed_key_pair(_sortable_bits(lc), _sortable_bits(rc),
                                      lc.validity, rc.validity, l_enc, r_enc)
            return tuple(l_enc), tuple(r_enc)
        return fn

    out = _cached_call(key, build, (tuple(l_args), tuple(r_args)),
                       eval_ctx, metrics)
    if out is _FAILED:
        return None
    return list(out[0]), list(out[1])


# ---------------------------------------------------------------------------
# hash partitioner (shuffle/partitioner.py)
# ---------------------------------------------------------------------------


def partition_ids(batch: TpuColumnarBatch, key_exprs: Sequence[Expression],
                  n: int, eval_ctx: EvalContext, seed: int, metrics=None):
    """pmod(murmur3(keys, seed), n) as one executable; None → caller eager."""
    if not enabled(eval_ctx):
        return None
    if not all(_gate_ok(k) for k in key_exprs) \
            or not _inputs_ok(key_exprs, batch):
        return None
    cap = batch.capacity
    sig = _input_sig(key_exprs, batch)
    key = ("pids", tuple(_fp(k) for k in key_exprs), cap,
           len(batch.columns), sig, int(n), int(seed), _conf_fp(eval_ctx))
    src_dtypes = {o: batch.columns[o].dtype for (o, _, _, _) in sig}
    n_cols = len(batch.columns)
    key_exprs = list(key_exprs)

    tctx = _trace_ctx(eval_ctx)

    def build():
        def fn(*flat):
            from ..expressions.hashexprs import murmur3_batch
            rowmask = jnp.arange(cap) < flat[0]
            tb = _rebuild_batch(flat, sig, src_dtypes, n_cols, cap, rowmask)
            cols = [to_column(k.eval_tpu(tb, tctx), tb, k.dtype)
                    for k in key_exprs]
            h = murmur3_batch(cols, cap, cap, seed)
            pid = h % n
            return jnp.where(pid < 0, pid + n, pid).astype(jnp.int32)
        return fn

    out = _cached_call(key, build, tuple(_flat_args(batch, sig)),
                       eval_ctx, metrics)
    return None if out is _FAILED else out


def partition_split_plan(batch: TpuColumnarBatch,
                         key_exprs: Sequence[Expression], n: int,
                         eval_ctx: EvalContext, seed: int, metrics=None):
    """The exchange map side's hash-partition ENCODE+SPLIT as one executable:
    key eval → murmur3 → pmod → stable sort-by-pid → partition bounds, in a
    single dispatch (the eager path pays one program for the pids and a
    second for the split plan). Returns (order, bounds) device arrays or
    None (caller runs the two-program path)."""
    if not enabled(eval_ctx):
        return None
    if not all(_gate_ok(k) for k in key_exprs) \
            or not _inputs_ok(key_exprs, batch):
        return None
    cap = batch.capacity
    sig = _input_sig(key_exprs, batch)
    key = ("exchsplit", tuple(_fp(k) for k in key_exprs), cap,
           len(batch.columns), sig, int(n), int(seed), _conf_fp(eval_ctx))
    src_dtypes = {o: batch.columns[o].dtype for (o, _, _, _) in sig}
    n_cols = len(batch.columns)
    key_exprs = list(key_exprs)

    tctx = _trace_ctx(eval_ctx)

    def build():
        def fn(*flat):
            from ..expressions.hashexprs import murmur3_batch
            rowmask = jnp.arange(cap) < flat[0]
            tb = _rebuild_batch(flat, sig, src_dtypes, n_cols, cap, rowmask)
            cols = [to_column(k.eval_tpu(tb, tctx), tb, k.dtype)
                    for k in key_exprs]
            h = murmur3_batch(cols, cap, cap, seed)
            pid = h % n
            pid = jnp.where(pid < 0, pid + n, pid).astype(jnp.int32)
            # identical composition to partitioner._split_plan: padding last
            sort_key = jnp.where(rowmask, pid, n)
            order = jnp.argsort(sort_key, stable=True)
            sorted_pid = jnp.take(sort_key, order)
            return order, jnp.searchsorted(sorted_pid, jnp.arange(n + 1))
        return fn

    out = _cached_call(key, build, tuple(_flat_args(batch, sig)),
                       eval_ctx, metrics)
    return None if out is _FAILED else out


# ---------------------------------------------------------------------------
# sort-based aggregate (execs/aggregates.py): sort phase + reduce phase
# ---------------------------------------------------------------------------

#: update ops the reduce phase can trace (the collect/percentile family syncs
#: element counts on host; variable-width inputs take host-assisted paths)
_DEVICE_AGG_OPS = frozenset((
    "count", "sum", "avg", "min", "max", "first", "last",
    "stddev_samp", "stddev_pop", "var_samp", "var_pop",
    "covar_samp", "covar_pop", "corr"))


def agg_out_dtype(fn) -> DataType:
    """The dtype _evaluate_agg actually emits for a device-reducible fn."""
    op = fn.update_op
    if op == "count":
        return LongT
    if op in ("avg", "stddev_samp", "stddev_pop", "var_samp", "var_pop",
              "covar_samp", "covar_pop", "corr"):
        return DoubleT
    return fn.dtype


def _agg_fn_ok(fn) -> bool:
    if fn.update_op not in _DEVICE_AGG_OPS:
        return False
    if isinstance(fn.dtype, DecimalType):
        return False
    for c in fn.children:
        if not _gate_ok(c):
            return False
    return True


def agg_sort_plan(grouping: Sequence[Expression], batch: TpuColumnarBatch,
                  eval_ctx: EvalContext, metrics=None):
    """Phase 1 of the sort-based aggregate as one executable: evaluate the
    grouping keys, encode, stable lex-sort, segment boundaries. Returns
    (perm, seg_ids, is_new, n_groups, key_cols) or None (caller eager)."""
    if not enabled(eval_ctx) or not grouping:
        return None
    if not all(_gate_ok(g) for g in grouping) \
            or not _inputs_ok(grouping, batch):
        return None
    cap = batch.capacity
    sig = _input_sig(grouping, batch)
    key = ("aggsort", tuple(_fp(g) for g in grouping), cap,
           len(batch.columns), sig, _conf_fp(eval_ctx))
    src_dtypes = {o: batch.columns[o].dtype for (o, _, _, _) in sig}
    n_cols = len(batch.columns)
    grouping = list(grouping)

    tctx = _trace_ctx(eval_ctx)

    def build():
        def fn(*flat):
            from .aggregates import (encode_group_keys, lex_sort_permutation,
                                     segment_boundaries)
            n_rows = flat[0]
            rowmask = jnp.arange(cap) < n_rows
            tb = _rebuild_batch(flat, sig, src_dtypes, n_cols, cap, rowmask)
            key_cols = [to_column(g.eval_tpu(tb, tctx), tb, g.dtype)
                        for g in grouping]
            enc = encode_group_keys(key_cols, cap, cap)
            perm = lex_sort_permutation(enc, n_rows, cap)
            is_new, seg_ids, ng = segment_boundaries(enc, perm, rowmask)
            return (perm, seg_ids, is_new, ng,
                    tuple((c.data, c.validity) for c in key_cols))
        return fn

    out = _cached_call(key, build, tuple(_flat_args(batch, sig)),
                       eval_ctx, metrics)
    if out is _FAILED:
        return None
    perm, seg_ids, is_new, ng, key_flat = out
    key_cols = [TpuColumnVector(g.dtype, d, v, batch.rows_lazy)
                for g, (d, v) in zip(grouping, key_flat)]
    return perm, seg_ids, is_new, int(ng), key_cols


def agg_reduce(agg_fns, batch: TpuColumnarBatch, perm, seg_ids, is_new,
               n_groups: int, g_cap: int, eval_ctx: EvalContext,
               metrics=None):
    """Phase 2 as one executable: evaluate the measure inputs, run every
    segment update + finalization, and locate each group's first sorted row.
    perm/seg_ids/is_new (phase-1 outputs, dead afterwards) are donated on
    device backends. Returns (agg_cols, key_rows) or None (caller eager)."""
    if not enabled(eval_ctx) or not all(_agg_fn_ok(f) for f in agg_fns):
        return None
    in_exprs = [c for f in agg_fns for c in f.children]
    if not _inputs_ok(in_exprs, batch):
        return None
    cap = batch.capacity
    grouped = perm is not None
    sig = _input_sig(in_exprs, batch)
    key = ("aggreduce", tuple(_fp(f) for f in agg_fns), cap, g_cap,
           grouped, len(batch.columns), sig, _conf_fp(eval_ctx))
    src_dtypes = {o: batch.columns[o].dtype for (o, _, _, _) in sig}
    n_cols = len(batch.columns)
    agg_fns = list(agg_fns)

    tctx = _trace_ctx(eval_ctx)

    def build():
        def fn(n_rows, ng, perm_, seg_, new_, *flat):
            from .aggregates import _evaluate_agg, _segment_update
            rowmask = jnp.arange(cap) < n_rows
            tb = _rebuild_batch((n_rows,) + flat, sig, src_dtypes, n_cols,
                                cap, rowmask)
            if perm_ is None:
                perm_ = jnp.arange(cap, dtype=jnp.int32)
                seg_ = jnp.zeros((cap,), jnp.int32)
            outs = []
            for f in agg_fns:
                if len(f.children) >= 2:
                    col = tuple(to_column(c.eval_tpu(tb, tctx), tb,
                                          c.dtype) for c in f.children)
                elif f.children:
                    col = to_column(f.children[0].eval_tpu(tb, tctx),
                                    tb, f.children[0].dtype)
                else:
                    col = None
                st = _segment_update(f, col, seg_, g_cap, cap, n_rows, perm_)
                c = _evaluate_agg(f, st, ng, g_cap)
                outs.append((c.data, c.validity))
            key_rows = None
            if new_ is not None:
                first_pos = jnp.zeros((g_cap,), jnp.int32).at[
                    jnp.where(new_, seg_, g_cap)].set(
                    jnp.arange(cap, dtype=jnp.int32), mode="drop")
                key_rows = jnp.take(perm_, first_pos)
            return tuple(outs), key_rows
        return fn

    args = [batch.rows_arg, n_groups, perm, seg_ids, is_new]
    args += _flat_args(batch, sig)[1:]
    donate = _donate((2, 3, 4)) if grouped else ()
    out = _cached_call(key, build, tuple(args), eval_ctx, metrics,
                       donate_argnums=donate)
    if out is _FAILED:
        return None
    outs, key_rows = out
    agg_cols = [TpuColumnVector(agg_out_dtype(f), d, v, n_groups)
                for f, (d, v) in zip(agg_fns, outs)]
    return agg_cols, key_rows


# ---------------------------------------------------------------------------
# whole-stage segment fusion (execs/fusion.py): a chain of project/filter
# operators flattened into ONE executable per batch shape
# ---------------------------------------------------------------------------


def strip_alias(e: Expression) -> Expression:
    return e.children[0] if isinstance(e, Alias) else e


def substitute(e: Expression, cur_exprs) -> Expression:
    """Rewrite `e` (bound to the CURRENT schema of a segment position) into an
    expression over the segment's INPUT schema: every AttributeReference's
    ordinal indexes `cur_exprs`, the list of input-schema expressions that
    produce the current schema. `cur_exprs is None` means the current schema
    IS the input schema (identity). This is classic projection collapse —
    shared subtrees are duplicated symbolically, which is safe because only
    deterministic expressions are ever fused (fusion.py gates out the
    nondeterministic/task-state readers via _gate_ok) and XLA CSE dedups the
    duplicated work inside the one traced program."""
    if cur_exprs is None:
        return e

    def rule(x: Expression):
        if isinstance(x, AttributeReference):
            if x.ordinal is None or not (0 <= x.ordinal < len(cur_exprs)):
                raise ValueError(f"unbound reference {x.name} in segment")
            return strip_alias(cur_exprs[x.ordinal])
        return None

    return e.transform(rule)


def is_passthrough(e: Expression) -> bool:
    """A segment output that is just a (possibly aliased) input column: it
    bypasses the traced program entirely — any dtype, including strings —
    and is spliced from the input batch into the assembled output."""
    return _passthrough(e) is not None


def fusable_expr(e: Expression) -> bool:
    """May this (input-schema) expression participate in a fused segment?
    Either it bypasses as a passthrough column or it traces via the gate."""
    return is_passthrough(e) or _gate_ok(e)


def segment_gate_ok(e: Expression) -> bool:
    """Public gate for fusion.py (filters must trace; no bypass option)."""
    return _gate_ok(e)


def segment_inputs_ok(exprs: Sequence[Expression],
                      batch: TpuColumnarBatch) -> bool:
    return _inputs_ok(exprs, batch)


def _segment_body(out_exprs, out_dtypes, filters, sig, src_dtypes,
                  n_cols: int, cap: int, tctx, flat):
    """Single-batch segment evaluation — shared by the per-batch program and
    the grouped (multi-partition) program so the two are bit-identical."""
    rowmask = jnp.arange(cap) < flat[0]
    tb = _rebuild_batch(flat, sig, src_dtypes, n_cols, cap, rowmask)
    keep = rowmask
    for f in filters:
        c = to_column(f.eval_tpu(tb, tctx), tb)
        m = c.data.astype(jnp.bool_)
        if c.validity is not None:
            m = m & c.validity  # null predicate → drop row
        keep = keep & m
    outs = []
    for e, dt in zip(out_exprs, out_dtypes):
        c = to_column(e.eval_tpu(tb, tctx), tb, dt)
        outs.append((c.data, c.validity))
    return tuple(outs), (keep if filters else None)


def segment_program(out_exprs: Sequence[Expression],
                    out_dtypes: Sequence[DataType],
                    filters: Sequence[Expression],
                    batch: TpuColumnarBatch, eval_ctx: EvalContext,
                    metrics=None):
    """A whole stage segment as ONE executable: every computed output column
    of the collapsed projection pipeline plus the AND of every filter
    predicate (null predicate → drop, exactly the eager filter semantics),
    evaluated over the segment's input batch in a single dispatch. Filters
    do NOT compact inside the trace — rows stay in place under a keep mask
    and the caller compacts once at the segment end, which is bit-identical
    for the row-wise expressions the gate admits. Returns (cols, keep) where
    keep is None when the segment has no filters, or None when the
    fingerprint is pinned eager (caller degrades to per-operator programs)."""
    cap = batch.capacity
    out_exprs = list(out_exprs)
    out_dtypes = list(out_dtypes)
    filters = list(filters)
    all_exprs = out_exprs + filters
    sig = _input_sig(all_exprs, batch)
    key = ("segment", tuple(_fp(e) for e in out_exprs),
           tuple(_fp(f) for f in filters),
           tuple(type(d).__name__ for d in out_dtypes), cap,
           len(batch.columns), sig, _conf_fp(eval_ctx))
    src_dtypes = {o: batch.columns[o].dtype for (o, _, _, _) in sig}
    n_cols = len(batch.columns)
    has_filters = bool(filters)

    tctx = _trace_ctx(eval_ctx)

    def build():
        def fn(*flat):
            return _segment_body(out_exprs, out_dtypes, filters, sig,
                                 src_dtypes, n_cols, cap, tctx, flat)
        return fn

    out = _cached_call(key, build, tuple(_flat_args(batch, sig)),
                       eval_ctx, metrics)
    if out is _FAILED:
        return None
    outs, keep = out
    cols = [TpuColumnVector(dt, d, v, batch.rows_lazy)
            for (d, v), dt in zip(outs, out_dtypes)]
    return cols, keep


def segment_program_grouped(out_exprs: Sequence[Expression],
                            out_dtypes: Sequence[DataType],
                            filters: Sequence[Expression],
                            batches: Sequence[TpuColumnarBatch],
                            eval_ctx: EvalContext, metrics=None):
    """Batched multi-partition dispatch of one fused segment: N partitions'
    batches run the SAME flattened segment in ONE launch ("segmentg"),
    reusing _segment_body per member so results are bit-identical to N
    single-batch "segment" dispatches. Member batches may differ in bucketed
    capacity (the cache key covers the capacity tuple); they must share the
    input layout (callers group by layout). Returns a list of (cols, keep)
    per member, or None when the fingerprint is pinned eager."""
    out_exprs = list(out_exprs)
    out_dtypes = list(out_dtypes)
    filters = list(filters)
    all_exprs = out_exprs + filters
    sig = _input_sig(all_exprs, batches[0])
    caps = tuple(b.capacity for b in batches)
    key = ("segmentg", tuple(_fp(e) for e in out_exprs),
           tuple(_fp(f) for f in filters),
           tuple(type(d).__name__ for d in out_dtypes), caps,
           len(batches[0].columns), sig, _conf_fp(eval_ctx))
    src_dtypes = {o: batches[0].columns[o].dtype for (o, _, _, _) in sig}
    n_cols = len(batches[0].columns)

    tctx = _trace_ctx(eval_ctx)

    def build():
        def fn(member_flats):
            return tuple(
                _segment_body(out_exprs, out_dtypes, filters, sig,
                              src_dtypes, n_cols, cap, tctx, flat)
                for cap, flat in zip(caps, member_flats))
        return fn

    args = tuple(tuple(_flat_args(b, sig)) for b in batches)
    out = _cached_call(key, build, (args,), eval_ctx, metrics)
    if out is _FAILED:
        return None
    results = []
    for b, (outs, keep) in zip(batches, out):
        cols = [TpuColumnVector(dt, d, v, b.rows_lazy)
                for (d, v), dt in zip(outs, out_dtypes)]
        results.append((cols, keep))
    return results


# ---------------------------------------------------------------------------
# grouped hash-partition split (shuffle/partitioner.py, shuffle/exchange.py):
# the encode+split plans of a whole partition GROUP in one launch
# ---------------------------------------------------------------------------


def partition_split_plan_grouped(batches: Sequence[TpuColumnarBatch],
                                 key_exprs_per_lane, n: int,
                                 eval_ctx: EvalContext, seed: int,
                                 metrics=None):
    """N lanes' (key eval → murmur3 → pmod → stable sort → bounds) split
    plans as ONE executable ("exchsplitg") — the batched multi-partition
    form of partition_split_plan. Each lane's plan is computed with exactly
    the single-lane composition, so slices are bit-identical to per-lane
    dispatch; only the launch count (and the bounds readback, which the
    caller batches into one transfer) changes. Lanes may carry distinct key
    expressions (the join sub-partitioner splits both sides in one launch).
    Returns (orders, bounds) lists of device arrays, or None."""
    if not enabled(eval_ctx):
        return None
    lanes = list(zip(batches, key_exprs_per_lane))
    for b, keys in lanes:
        if not all(_gate_ok(k) for k in keys) or not _inputs_ok(keys, b):
            return None
    sigs = tuple(_input_sig(keys, b) for b, keys in lanes)
    caps = tuple(b.capacity for b, _ in lanes)
    key = ("exchsplitg",
           tuple(tuple(_fp(k) for k in keys) for _, keys in lanes),
           caps, tuple(len(b.columns) for b, _ in lanes), sigs, int(n),
           int(seed), _conf_fp(eval_ctx))
    lane_meta = []
    for (b, keys), sig in zip(lanes, sigs):
        lane_meta.append((list(keys), sig,
                          {o: b.columns[o].dtype for (o, _, _, _) in sig},
                          len(b.columns), b.capacity))

    tctx = _trace_ctx(eval_ctx)

    def build():
        def fn(lane_flats):
            from ..expressions.hashexprs import murmur3_batch
            orders, bounds = [], []
            for (keys, sig_l, dt_l, ncol_l, cap_l), flat in zip(lane_meta,
                                                                lane_flats):
                rowmask = jnp.arange(cap_l) < flat[0]
                tb = _rebuild_batch(flat, sig_l, dt_l, ncol_l, cap_l,
                                    rowmask)
                cols = [to_column(k.eval_tpu(tb, tctx), tb, k.dtype)
                        for k in keys]
                h = murmur3_batch(cols, cap_l, cap_l, seed)
                pid = h % n
                pid = jnp.where(pid < 0, pid + n, pid).astype(jnp.int32)
                sort_key = jnp.where(rowmask, pid, n)  # padding last
                order = jnp.argsort(sort_key, stable=True)
                sorted_pid = jnp.take(sort_key, order)
                orders.append(order)
                bounds.append(jnp.searchsorted(sorted_pid,
                                               jnp.arange(n + 1)))
            return tuple(orders), tuple(bounds)
        return fn

    args = tuple(tuple(_flat_args(b, sig)) for (b, _), sig in zip(lanes,
                                                                  sigs))
    out = _cached_call(key, build, (args,), eval_ctx, metrics)
    if out is _FAILED:
        return None
    return list(out[0]), list(out[1])


# ---------------------------------------------------------------------------
# fused join probe (execs/fusion.py): the streamed side of an inner equi-join
# absorbed into a stage segment. "joinbuild" once a build (key encode + hash
# + sort + bucket directory), then two programs a probe batch split at the
# inherent candidate-count sync: "joinprobe" (upstream chain + key encode +
# directory probe) and "joinemit" (pair expansion + verify + both-side
# gather + downstream chain + one compaction).
# ---------------------------------------------------------------------------


def join_probe_gate_ok(key_exprs, filters, out_exprs) -> bool:
    return all(_gate_ok(e) for e in list(key_exprs) + list(filters)
               + list(out_exprs))


def plain_device_col(col) -> bool:
    """Fixed-width single-vector device layout — the only layout the fused
    join can pass through its traced gather."""
    return (col.offsets is None and col.host_data is None
            and col.child is None and col.children is None
            and getattr(col.data, "ndim", 1) == 1)


def _key_cols_sig(cols) -> Tuple:
    return tuple((str(c.data.dtype), c.validity is not None) for c in cols)


def join_build_program(build_keys, build_rows, eval_ctx: EvalContext,
                       metrics=None):
    """The build half of a fused join in ONE launch, once a build: encode
    the build's key columns, composite-hash, sort and lay the bucket
    directory over the sorted hashes (joins._join_prepare_build — the same
    traced code the unfused join runs a pair). Every probe batch of the
    build takes the result as operands. Returns a joins.PreparedBuild, or
    None when pinned eager."""
    b_cap = build_keys[0].capacity
    key = ("joinbuild", b_cap, _key_cols_sig(build_keys), _conf_fp(eval_ctx))
    b_dtypes = [c.dtype for c in build_keys]

    def build():
        def fn(bkeys, b_rows):
            from .aggregates import _sortable_bits
            from .joins import (_join_prepare_build, dir_bits,
                                encode_fixed_key)
            b_vals, b_valids = [], []
            for dt, (data, valid) in zip(b_dtypes, bkeys):
                bv = TpuColumnVector(dt, data, valid, b_cap)
                b_vals.append(encode_fixed_key(_sortable_bits(bv)))
                b_valids.append(valid if valid is not None
                                else jnp.ones((b_cap,), jnp.bool_))
            return _join_prepare_build(b_vals, b_valids, jnp.int32(b_rows),
                                       bits=dir_bits(b_cap))
        return fn

    out = _cached_call(
        key, build,
        (tuple((c.data, c.validity) for c in build_keys), build_rows),
        eval_ctx, metrics)
    return None if out is _FAILED else out


def join_probe_program(out_exprs, out_dtypes, filters, key_exprs,
                       batch: TpuColumnarBatch, prepared,
                       eval_ctx: EvalContext, metrics=None):
    """The probe half of a fused join in ONE launch: apply the flattened
    upstream projection/filter chain to the probe batch, evaluate+encode the
    probe keys (joins.encode_fixed_key, the build's own encode),
    composite-hash them and read each lane's candidate range from the
    prepared build's directory (joins._join_probe_ranges — the same traced
    code the unfused join runs, so candidates are bit-identical). Upstream
    filters do not compact: failing rows are masked out of p_ok, which
    produces the same candidate set and pair order the compact-then-probe
    path does.

    Returns (state, jit_cols) where state carries everything the emit
    program needs (counts/lo/p_ok and the probe's encoded values beside the
    prepared build's order/b_ok/values, and total), or None when pinned
    eager."""
    cap = batch.capacity
    out_exprs = list(out_exprs)
    out_dtypes = list(out_dtypes)
    filters = list(filters)
    key_exprs = list(key_exprs)
    all_exprs = out_exprs + filters + key_exprs
    sig = _input_sig(all_exprs, batch)
    key = ("joinprobe", tuple(_fp(e) for e in out_exprs),
           tuple(_fp(f) for f in filters),
           tuple(_fp(k) for k in key_exprs),
           tuple(type(d).__name__ for d in out_dtypes), cap,
           prepared.directory.shape[0], len(batch.columns), sig,
           _conf_fp(eval_ctx))
    src_dtypes = {o: batch.columns[o].dtype for (o, _, _, _) in sig}
    n_cols = len(batch.columns)

    tctx = _trace_ctx(eval_ctx)

    def build():
        def fn(flat, directory):
            from .aggregates import _sortable_bits
            from .joins import _join_probe_ranges, encode_fixed_key
            rowmask = jnp.arange(cap) < flat[0]
            tb = _rebuild_batch(flat, sig, src_dtypes, n_cols, cap, rowmask)
            keep = rowmask
            for f in filters:
                c = to_column(f.eval_tpu(tb, tctx), tb)
                m = c.data.astype(jnp.bool_)
                if c.validity is not None:
                    m = m & c.validity
                keep = keep & m
            p_vals, p_valids = [], []
            for k in key_exprs:
                pc = to_column(k.eval_tpu(tb, tctx), tb, k.dtype)
                p_vals.append(encode_fixed_key(_sortable_bits(pc)))
                p_valids.append((pc.validity & keep)
                                if pc.validity is not None else keep)
            counts, lo, p_ok, total = _join_probe_ranges(
                directory, p_vals, p_valids, jnp.int32(flat[0]))
            outs = []
            for e, dt in zip(out_exprs, out_dtypes):
                c = to_column(e.eval_tpu(tb, tctx), tb, dt)
                outs.append((c.data, c.validity))
            return counts, lo, p_ok, tuple(p_vals), total, tuple(outs)
        return fn

    out = _cached_call(
        key, build, (tuple(_flat_args(batch, sig)), prepared.directory),
        eval_ctx, metrics)
    if out is _FAILED:
        return None
    counts, lo, p_ok, p_vals, total, outs = out
    state = {"counts": counts, "lo": lo, "order": prepared.order,
             "b_ok": prepared.b_ok, "p_ok": p_ok,
             "b_vals": list(prepared.b_vals), "p_vals": list(p_vals),
             "total": total}
    jit_cols = [TpuColumnVector(dt, d, v, batch.rows_lazy)
                for (d, v), dt in zip(outs, out_dtypes)]
    return state, jit_cols


def join_emit_program(post_specs, post_traced, post_dtypes, post_filters,
                      state, probe_cols, build_cols, probe_rows, build_rows,
                      out_cap: int, n_left: int,
                      eval_ctx: EvalContext, metrics=None,
                      want_indices: bool = False):
    """The emit half of a fused join in ONE launch: expand candidate ranges
    into pairs, verify key equality, stable-compact the verified pairs,
    gather BOTH sides' needed columns, run the flattened downstream chain
    over the joined schema and compact once. The pair math reuses
    joins._join_emit_pairs / _compact_pairs_device and the gather reuses
    columnar.batch._gather_fixed_cols, so every intermediate is
    bit-identical to the per-operator join. Returns (cols, n_out_dev,
    probe_idx, build_idx) with the kept count as a DEVICE scalar, or None
    when pinned eager.

    probe_cols/build_cols map joined-schema ordinals (< n_left probe-side,
    >= n_left build-side) to fixed-width device columns; post_specs maps
    each output position to ('pass', joined_ordinal), ('jit', slot) or
    ('host', joined_ordinal) — 'host' outputs (strings and other
    host-layout passthroughs) are NOT produced by the trace; with
    want_indices=True the program also returns the FINAL (post-filter,
    compacted) per-side pair indices, -1-padded, so the caller can gather
    them through columnar.batch.gather exactly like the unfused join."""
    post_traced = list(post_traced)
    post_dtypes = list(post_dtypes)
    post_filters = list(post_filters)
    p_ords = sorted(probe_cols)
    b_ords = sorted(build_cols)
    psig = _key_cols_sig([probe_cols[o] for o in p_ords])
    bsig = _key_cols_sig([build_cols[o] for o in b_ords])
    key = ("joinemit", tuple(post_specs),
           tuple(_fp(e) for e in post_traced),
           tuple(_fp(f) for f in post_filters),
           tuple(type(d).__name__ for d in post_dtypes), out_cap,
           tuple(p_ords), tuple(b_ords), psig, bsig, n_left,
           len(state["b_vals"]), bool(want_indices), _conf_fp(eval_ctx))
    p_dtypes = {o: probe_cols[o].dtype for o in p_ords}
    b_dtypes = {o: build_cols[o].dtype for o in b_ords}
    n_joined = max([n_left] + [o + 1 for o in p_ords + b_ords])
    # dtype per TRACED slot: post_dtypes is positional over ALL outputs
    jit_dtypes = [post_dtypes[pos] for pos, (kind, _) in enumerate(post_specs)
                  if kind == "jit"]

    tctx = _trace_ctx(eval_ctx)

    def build():
        def fn(counts, lo, order, b_ok, p_ok, b_vals, p_vals, total,
               p_flat, b_flat, p_rows, b_rows):
            from ..columnar.batch import _compact_plan, _gather_fixed_cols
            from .joins import _compact_pairs_device, _join_emit_pairs
            pi, bi, ok, n_ok = _join_emit_pairs(
                counts, lo, order, b_ok, p_ok, list(b_vals), list(p_vals),
                total, out_cap=out_cap)
            cpi, cbi, slot_ok = _compact_pairs_device(pi, bi, ok, n_ok)
            pair_mask = jnp.arange(out_cap) < n_ok

            def gather_side(flat, idx, rows):
                datas = [d for d, _ in flat]
                valids = [v for _, v in flat]
                return _gather_fixed_cols(datas, valids,
                                          jnp.where(slot_ok, idx, -1),
                                          jnp.int32(rows), n_ok)
            pg_d, pg_v = gather_side(p_flat, cpi, p_rows) if p_flat \
                else ([], [])
            bg_d, bg_v = gather_side(b_flat, cbi, b_rows) if b_flat \
                else ([], [])
            # joined-schema batch for the downstream chain: unreferenced
            # ordinals get typed dummies (never read)
            # in-trace batch convention (_rebuild_batch): num_rows == cap, a
            # CONCRETE int — the pair mask (slot < n_ok) already lives in
            # every gathered validity, so expressions see padding slots as
            # invalid and never need the traced count as a host int
            cols: List[Optional[TpuColumnVector]] = [None] * n_joined
            for o, d, v in zip(p_ords, pg_d, pg_v):
                cols[o] = TpuColumnVector(p_dtypes[o], d, v, out_cap)
            for o, d, v in zip(b_ords, bg_d, bg_v):
                cols[o] = TpuColumnVector(b_dtypes[o], d, v, out_cap)
            for o in range(n_joined):
                if cols[o] is None:
                    cols[o] = TpuColumnVector(
                        IntegerT, jnp.zeros((out_cap,), jnp.int32),
                        jnp.zeros((out_cap,), jnp.bool_), out_cap)
            jb = TpuColumnarBatch(cols, out_cap)
            keep = pair_mask
            for f in post_filters:
                c = to_column(f.eval_tpu(jb, tctx), jb)
                m = c.data.astype(jnp.bool_)
                if c.validity is not None:
                    m = m & c.validity
                keep = keep & m
            outs = []
            jit_res = [to_column(e.eval_tpu(jb, tctx), jb, dt)
                       for e, dt in zip(post_traced, jit_dtypes)]
            for kind, spec in post_specs:
                if kind == "pass":
                    outs.append((cols[spec].data, cols[spec].validity))
                elif kind == "jit":
                    outs.append((jit_res[spec].data, jit_res[spec].validity))
                # 'host' outputs gather outside the trace
            fpi_raw = jnp.where(slot_ok, cpi, -1).astype(jnp.int32)
            fbi_raw = jnp.where(slot_ok, cbi, -1).astype(jnp.int32)
            if not post_filters:
                if not want_indices:
                    return tuple(outs), n_ok, ()
                return tuple(outs), n_ok, (fpi_raw, fbi_raw)
            idx2, n_out = _compact_plan(keep, n_ok)
            datas = [d for d, _ in outs]
            valids = [v for _, v in outs]
            g_d, g_v = _gather_fixed_cols(datas, valids, idx2,
                                          jnp.int32(n_ok), n_out)
            if not want_indices:
                return tuple(zip(g_d, g_v)), n_out, ()
            # thread the filter compaction through the pair indices so the
            # host gather sees exactly the surviving pairs, in order
            ok2 = (idx2 < n_ok) & (jnp.arange(out_cap) < n_out)
            safe2 = jnp.where(ok2, idx2, 0)
            fpi = jnp.where(ok2, jnp.take(fpi_raw, safe2), -1)
            fbi = jnp.where(ok2, jnp.take(fbi_raw, safe2), -1)
            return tuple(zip(g_d, g_v)), n_out, (fpi, fbi)
        return fn

    args = (state["counts"], state["lo"], state["order"], state["b_ok"],
            state["p_ok"], tuple(state["b_vals"]), tuple(state["p_vals"]),
            state["total"],
            tuple((probe_cols[o].data, probe_cols[o].validity)
                  for o in p_ords),
            tuple((build_cols[o].data, build_cols[o].validity)
                  for o in b_ords),
            probe_rows, build_rows)
    out = _cached_call(key, build, args, eval_ctx, metrics)
    if out is _FAILED:
        return None
    outs, n_out, idxs = out
    return list(outs), n_out, (tuple(idxs) if idxs else None)


# ---------------------------------------------------------------------------
# fused aggregate stage (execs/aggregates.py): the sort-based grouped
# aggregate's whole update — key sort, segment boundaries, every measure
# update + finalization, group-key gather — as ONE launch with a
# capacity-bucketed group table, so the group count never syncs mid-query
# ---------------------------------------------------------------------------


def agg_stage_program(grouping, agg_fns, batch: TpuColumnarBatch,
                      eval_ctx: EvalContext, metrics=None):
    """One launch for the whole grouped-aggregate update (the "fixed-size
    hash-table" form of partial aggregation: the group table is sized to the
    batch's capacity bucket — an upper bound on distinct keys — so no
    phase-boundary n_groups sync is needed; padding groups carry validity
    False exactly like padding rows). Reuses encode_group_keys /
    lex_sort_permutation / segment_boundaries / _segment_update /
    _evaluate_agg, the same code the two-phase aggsort/aggreduce path runs,
    so results are bit-identical. Returns (key_cols, agg_cols, n_groups_dev)
    or None when unsupported/pinned (caller runs the two-phase path)."""
    if not enabled(eval_ctx) or not grouping:
        return None
    if not all(_gate_ok(g) for g in grouping) \
            or not all(_agg_fn_ok(f) for f in agg_fns):
        return None
    in_exprs = list(grouping) + [c for f in agg_fns for c in f.children]
    if not _inputs_ok(in_exprs, batch):
        return None
    cap = batch.capacity
    sig = _input_sig(in_exprs, batch)
    key = ("aggstage", tuple(_fp(g) for g in grouping),
           tuple(_fp(f) for f in agg_fns), cap, len(batch.columns), sig,
           _conf_fp(eval_ctx))
    src_dtypes = {o: batch.columns[o].dtype for (o, _, _, _) in sig}
    n_cols = len(batch.columns)
    grouping = list(grouping)
    agg_fns = list(agg_fns)

    tctx = _trace_ctx(eval_ctx)

    def build():
        def fn(*flat):
            from .aggregates import (_evaluate_agg, _segment_update,
                                     encode_group_keys, lex_sort_permutation,
                                     segment_boundaries)
            n_rows = flat[0]
            rowmask = jnp.arange(cap) < n_rows
            tb = _rebuild_batch(flat, sig, src_dtypes, n_cols, cap, rowmask)
            key_cols = [to_column(g.eval_tpu(tb, tctx), tb, g.dtype)
                        for g in grouping]
            enc = encode_group_keys(key_cols, cap, cap)
            perm = lex_sort_permutation(enc, n_rows, cap)
            is_new, seg_ids, ng = segment_boundaries(enc, perm, rowmask)
            outs = []
            for f in agg_fns:
                if len(f.children) >= 2:
                    col = tuple(to_column(c.eval_tpu(tb, tctx), tb, c.dtype)
                                for c in f.children)
                elif f.children:
                    col = to_column(f.children[0].eval_tpu(tb, tctx), tb,
                                    f.children[0].dtype)
                else:
                    col = None
                st = _segment_update(f, col, seg_ids, cap, cap, n_rows, perm)
                c = _evaluate_agg(f, st, ng, cap)
                outs.append((c.data, c.validity))
            # group keys: first sorted row of each segment
            first_pos = jnp.zeros((cap,), jnp.int32).at[
                jnp.where(is_new, seg_ids, cap)].set(
                jnp.arange(cap, dtype=jnp.int32), mode="drop")
            key_rows = jnp.take(perm, first_pos)
            gmask = jnp.arange(cap) < ng
            keys_out = []
            for c in key_cols:
                d = jnp.take(c.data, key_rows, axis=0)
                v = (jnp.take(c.validity, key_rows) if c.validity is not None
                     else jnp.ones((cap,), jnp.bool_)) & gmask
                vb = v[:, None] if d.ndim == 2 else v
                keys_out.append((jnp.where(vb, d, jnp.zeros((), d.dtype)), v))
            return tuple(keys_out), tuple(outs), ng
        return fn

    out = _cached_call(key, build, tuple(_flat_args(batch, sig)),
                       eval_ctx, metrics)
    if out is _FAILED:
        return None
    keys_out, outs, ng = out
    key_cols = [TpuColumnVector(g.dtype, d, v, ng)
                for g, (d, v) in zip(grouping, keys_out)]
    agg_cols = [TpuColumnVector(agg_out_dtype(f), d, v, ng)
                for f, (d, v) in zip(agg_fns, outs)]
    return key_cols, agg_cols, ng
