"""Physical-plan base classes: CpuExec (host Arrow path) and TpuExec (device path).

Reference: the `GpuExec` trait (/root/reference/sql-plugin/.../GpuExec.scala:236,
doExecuteColumnar:387) producing RDD[ColumnarBatch]. Here a physical operator
produces an iterator of batches per partition; the CPU flavor streams
pyarrow Tables (standing in for Spark's row/columnar CPU operators and serving as
the parity oracle), the TPU flavor streams TpuColumnarBatch.

Metrics follow the reference's GpuMetric scheme (GpuExec.scala:41-61):
ESSENTIAL/MODERATE/DEBUG levels, standard names (numOutputRows, numOutputBatches,
opTime, ...).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from ..config import RapidsConf, default_conf
from ..expressions.base import AttributeReference, EvalContext, Expression
from ..serving.query_context import checkpoint as _cancel_checkpoint
from ..types import StructField, StructType

ESSENTIAL = "ESSENTIAL"
MODERATE = "MODERATE"
DEBUG = "DEBUG"


class TpuMetric:
    """Accumulator metric (reference GpuMetric). Thread-safe: pipelined
    exchange map tasks and shuffle prefetch threads (shuffle/exchange.py)
    accumulate into one operator's metrics concurrently, and an unguarded
    `+=` from pool threads loses updates.

    Count reads are LAZY-friendly: `add_lazy` accepts a device int scalar
    (a deferred-compaction batch's pending row count) and parks it without
    blocking; the pending scalars materialize in one device_get at the
    first `value` read — metric bookkeeping itself never forces a per-batch
    device→host sync mid-query."""

    __slots__ = ("name", "level", "_value", "_pending", "_lock")

    #: parked device scalars fold into one at this depth — each is a live
    #: (padded) device buffer invisible to HbmBudget, so an unbounded list
    #: over operators×batches is a slow HBM leak until the query-end read
    _FOLD_AT = 64

    def __init__(self, name: str, level: str = MODERATE):
        self.name = name
        self.level = level
        self._value = 0
        self._pending: list = []
        self._lock = threading.Lock()

    def add(self, v: int) -> None:
        with self._lock:
            self._value += v

    def add_lazy(self, v) -> None:
        """Accumulate an int OR a device int scalar without syncing."""
        if isinstance(v, int):
            self.add(v)
            return
        with self._lock:
            self._pending.append(v)
            if len(self._pending) < self._FOLD_AT:
                return
            pending, self._pending = self._pending, []
        # fold outside the lock: one stacked device-side sum (an async
        # dispatch, NOT a blocking sync) frees the parked buffers
        import jax.numpy as jnp
        pending = [jnp.asarray(p) for p in pending]
        if len({frozenset(p.devices()) for p in pending}) > 1:
            # a mesh session's scalars from several chips cannot stack, and
            # a fold a chip would compile a program a group size: read them
            # (one sync a _FOLD_AT deferred batches)
            from ..columnar.vector import audited_device_get
            got = audited_device_get(pending, "metric")
            self.add(sum(int(x) for x in got))
            return
        folded = jnp.sum(jnp.stack(pending))
        with self._lock:
            self._pending.append(folded)

    @property
    def value(self) -> int:
        with self._lock:
            pending, self._pending = self._pending, []
        if pending:
            from ..columnar.vector import audited_device_get
            got = audited_device_get(pending, "metric")
            with self._lock:
                self._value += sum(int(x) for x in got)
        with self._lock:
            return self._value

    @value.setter
    def value(self, v: int) -> None:
        with self._lock:
            self._value = v
            self._pending = []

    @contextmanager
    def timed(self):
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            dt = time.perf_counter_ns() - t0
            with self._lock:
                self._value += dt

    # plans (and their metric dicts) ship to worker processes by pickle
    # (parallel/executors.py): the lock can't cross, and parked device
    # scalars are process-local — materialize them into the value first
    # (plan shipping happens once per stage, never per batch)
    def __getstate__(self):
        return (self.name, self.level, self.value)

    def __setstate__(self, state):
        self.name, self.level, self._value = state
        self._pending = []
        self._lock = threading.Lock()


_UNSET = object()


class TaskContext:
    """Per-task execution context (partition id, conf, metric sink).
    Reference analogue: Spark TaskContext + GpuTaskMetrics."""

    def __init__(self, partition_id: int = 0, conf: Optional[RapidsConf] = None):
        self.partition_id = partition_id
        self.conf = conf or default_conf()
        self.eval_ctx = EvalContext(self.conf, partition_id=partition_id)
        self.task_metrics: Dict[str, int] = {}
        self._completion_listeners = []
        self._chips = _UNSET

    @property
    def chips(self) -> Optional[tuple]:
        """The session's chips in mesh order, None outside a mesh session
        (parallel/mesh.py "Placement"): partition `p` belongs to chip
        `p % len(chips)`."""
        if self._chips is _UNSET:
            from ..parallel.mesh import session_chips
            self._chips = session_chips(self.conf)
        return self._chips

    def add_completion_listener(self, cb) -> None:
        """Register a callback run at task end (reference ScalableTaskCompletion)."""
        self._completion_listeners.append(cb)

    def complete(self) -> None:
        for cb in reversed(self._completion_listeners):
            try:
                cb()
            except Exception:  # noqa: BLE001 - completion must not mask results
                pass
        self._completion_listeners.clear()


import threading as _threading

#: lock flavors replaced wholesale on clone (a clone must never serialize
#: on — or deadlock with — the template's locks)
_LOCK_TYPES = (type(_threading.Lock()), type(_threading.RLock()))


def _rebind_value(v, rebind: dict):
    """Parameter-slot re-binding for ONE attribute value: replace template
    Literal objects (matched by identity) with this submission's literals,
    recursing through lists/tuples/SortOrder. Expression.transform
    preserves unchanged subtrees, so attributes and non-parameter
    expressions stay shared with the template."""

    def rule(e: Expression):
        return rebind.get(id(e))

    def walk(v):
        if isinstance(v, Expression):
            return v.transform(rule)
        if isinstance(v, list):
            return [walk(x) for x in v]
        if isinstance(v, tuple):
            return tuple(walk(x) for x in v)
        if type(v).__name__ == "SortOrder":
            nc = walk(v.child)
            if nc is v.child:
                return v
            import copy
            nv = copy.copy(v)
            nv.child = nc
            return nv
        return v

    return walk(v)


def _rebind_plan_exprs(node: "PhysicalPlan", rebind: dict) -> None:
    """Re-bind every expression attribute of one cloned node — projections,
    filter conditions, pushed parquet filters, join keys, sort orders."""
    for k, v in list(node.__dict__.items()):
        if k in ("children", "metrics") or isinstance(v, dict):
            continue
        node.__dict__[k] = _rebind_value(v, rebind)


def _clone_spec(spec, rebind, memo):
    """Clone a compiled-stage spec object (classes marked ``_PLAN_SPEC``:
    the compiled agg/join-agg stage patterns). Specs capture BOTH
    expressions (filter/project layers, grouping, agg fns — which must see
    re-bound literals, or a cache hit would execute the template
    submission's parameter values) and nested PhysicalPlans (a join dim's
    build subtree — which EXECUTES, so it must be this clone's copy, not
    the template's). Nested plans go through the shared memo so spec links
    and plan-tree links land on the same clones."""
    import copy

    def walk(v):
        if isinstance(v, PhysicalPlan):
            return v.clone_for_execution(rebind, memo)
        if getattr(v, "_PLAN_SPEC", False):
            nv = copy.copy(v)
            for k, x in list(nv.__dict__.items()):
                nv.__dict__[k] = walk(x)
            return nv
        if isinstance(v, (list, tuple)):
            return type(v)(walk(x) for x in v)
        if rebind:
            return _rebind_value(v, rebind)
        return v

    return walk(spec)


class PhysicalPlan:
    """Base physical operator."""

    children: List["PhysicalPlan"]

    def __init__(self, children: Sequence["PhysicalPlan"]):
        self.children = list(children)
        self.metrics: Dict[str, TpuMetric] = {}
        self._register_metrics()

    # --- metadata ---------------------------------------------------------
    @property
    def output(self) -> List[AttributeReference]:
        raise NotImplementedError

    def schema(self) -> StructType:
        return StructType([StructField(a.name, a.dtype, a.nullable) for a in self.output])

    @property
    def is_tpu(self) -> bool:
        return isinstance(self, TpuExec)

    def node_name(self) -> str:
        return type(self).__name__

    def node_desc(self) -> str:
        return self.node_name()

    # --- metrics ----------------------------------------------------------
    def _register_metrics(self) -> None:
        self.metrics["numOutputRows"] = TpuMetric("numOutputRows", ESSENTIAL)
        self.metrics["numOutputBatches"] = TpuMetric("numOutputBatches", MODERATE)
        self.metrics["opTime"] = TpuMetric("opTime", MODERATE)
        if isinstance(self, TpuExec):
            # general-path executable cache (execs/opjit.py): per-operator
            # compile/reuse accounting, mirrored into process-wide counters
            for name in ("opJitCacheHits", "opJitCacheMisses",
                         "opJitTraceTime"):
                self.metrics[name] = TpuMetric(name, DEBUG)
        for name, level in self.additional_metrics().items():
            self.metrics[name] = TpuMetric(name, level)

    def additional_metrics(self) -> Dict[str, str]:
        return {}

    def mesh_metric(self, name: str) -> TpuMetric:
        """A metric only a mesh session feeds, made at first use (so that
        a one-chip query's summary carries none): `meshRowsMoved`,
        `meshBytesMoved`, `meshReplicatedBytes`, `chipRows.<r>`, ..."""
        m = self.metrics.get(name)
        if m is None:
            m = self.metrics.setdefault(name, TpuMetric(name, DEBUG))
        return m

    def move_to_chip(self, batch, chip):
        """`batch` committed to `chip`, counted as rows and bytes that
        changed chip (`mesh.rows_moved`, `mesh.bytes_moved`)."""
        from ..columnar.batch import batch_to_device
        self.mesh_metric("meshRowsMoved").add_lazy(batch.rows_lazy)
        self.mesh_metric("meshBytesMoved").add(batch.device_memory_size())
        return batch_to_device(batch, chip)

    def mesh_counters(self) -> List[Tuple[str, TpuMetric]]:
        """The `mesh.*` counters of its query's summary this node fed
        (docs/observability.md "Mesh counters"), beside `query_counters`:
        rows and bytes that changed chip when a task pulled a partition of
        another chip through this node, bytes it held on more than one
        chip, and what a subclass adds."""
        return [(counter, self.metrics[key]) for key, counter in (
            ("meshRowsMoved", "mesh.rows_moved"),
            ("meshBytesMoved", "mesh.bytes_moved"),
            ("meshReplicatedBytes", "mesh.replicated_bytes"))
            if key in self.metrics]

    def chip_rows_counters(self) -> List[Tuple[str, TpuMetric]]:
        """`mesh.task_rows.chip<r>`: the rows this node put out on chip r —
        named by the nodes whose input is a partition task's intake (a
        scan its own, a join its probe side's)."""
        return [("mesh.task_rows.chip" + key.split(".", 1)[1], m)
                for key, m in list(self.metrics.items())
                if key.startswith("chipRows.")]

    def query_counters(self) -> List[Tuple[str, TpuMetric]]:
        """The counters of its query's summary this node feeds
        (docs/observability.md "Span model"), each with the metric that
        holds the count: a node that joins, exchanges or can fall back
        silently names them here. `profiling.plan_query_counters` reads
        them once the plan has run, a metric named twice (two joins over
        one child, as a stage's fallback shares its source's) once."""
        return []

    # --- execution --------------------------------------------------------
    def num_partitions(self) -> int:
        return self.children[0].num_partitions() if self.children else 1

    def execute_partition(self, idx: int, ctx: TaskContext) -> Iterator:
        raise NotImplementedError

    def execute_partitions(self, ids: Sequence[int], ctx_of) -> Iterator:
        """Multi-partition entry point (batched multi-partition dispatch,
        spark.rapids.tpu.dispatch.partitionBatch): yield (partition_id,
        batch) for every partition in `ids`, in id order. `ctx_of(i)`
        supplies the per-partition TaskContext (partition-id-dependent
        expressions must see their own id). The default runs partitions
        one at a time; operators that can batch a whole partition group
        into one device launch override it (TpuFusedSegmentExec)."""
        for i in ids:
            for batch in self.execute_partition(i, ctx_of(i)):
                yield i, batch

    # --- plan-cache clone protocol ----------------------------------------
    def clone_for_execution(self, rebind: Optional[dict] = None,
                            memo: Optional[dict] = None) -> "PhysicalPlan":
        """Structural clone of the plan for ONE execution.

        The plan cache (serving/plan_cache.py) stores a physical TEMPLATE
        that never executes; every submission — hit or miss — runs a clone,
        so per-query mutable state (metrics, shuffle ids, broadcast/
        subquery memos, AQE specs) never crosses queries and cached plans
        never pin device buffers. ``rebind`` maps ``id(template_literal)``
        → replacement Literal (parameter-slot re-binding); ``memo`` keeps
        shared subtrees (a reused exchange, the two sides of an AQE
        coordinator) shared in the clone. Immutable planning products —
        expressions, output attributes, conf snapshots — are shared with
        the template; only execution state is fresh."""
        if memo is None:
            memo = {}
        got = memo.get(id(self))
        if got is not None:
            return got
        import copy
        new = copy.copy(self)
        memo[id(self)] = new
        new.children = [c.clone_for_execution(rebind, memo)
                        for c in self.children]
        # plan-valued attrs OUTSIDE children carry expressions + execution
        # state too: a fused segment's absorbed operator chain (`_ops`), a
        # compiled stage's `fallback` subtree. The memo keeps nodes shared
        # with the children (a fused join's rewired child links, a
        # fallback's exchanges) pointing at the SAME clones.
        for k, v in list(new.__dict__.items()):
            if k == "children":
                continue
            if isinstance(v, PhysicalPlan):
                new.__dict__[k] = v.clone_for_execution(rebind, memo)
            elif isinstance(v, (list, tuple)) and v \
                    and all(isinstance(x, PhysicalPlan) for x in v):
                new.__dict__[k] = type(v)(
                    x.clone_for_execution(rebind, memo) for x in v)
            elif getattr(v, "_PLAN_SPEC", False):
                # compiled-stage spec: expressions + nested dim plans live
                # OUTSIDE the node's own attrs — clone/rebind through the
                # same memo (see _clone_spec)
                new.__dict__[k] = _clone_spec(v, rebind, memo)
        new.metrics = {}
        new._register_metrics()
        if rebind:
            _rebind_plan_exprs(new, rebind)
        new._reset_execution_state(memo, rebind)
        return new

    def _reset_execution_state(self, memo: dict,
                               rebind: Optional[dict] = None) -> None:
        """Drop every piece of per-execution state copy.copy carried over.
        Centralized by attribute convention rather than per-class overrides:
        the attrs below are the complete set of cross-query memos in the
        exec layer (exchange materialization, broadcast/subquery builds,
        compiled-join dim caches, AQE reader specs, DPP subqueries)."""
        import threading
        d = self.__dict__
        for k, v in list(d.items()):
            if isinstance(v, _LOCK_TYPES):
                d[k] = threading.Lock()
        d.pop("_last_batch", None)
        if "_shuffle_id" in d:           # _ExchangeBase materialization
            d["_shuffle_id"] = None
            d["_n_maps"] = 0
            for k in ("_obs_parent", "_query_ctx", "_collective_rows",
                      "_collective_sizes", "_close_dicts"):
                d.pop(k, None)
        if "_broadcast_done" in d:       # broadcast build-side memo
            d["_broadcast_done"] = False
            d["_broadcast_batch"] = None
            d["_broadcast_on"] = {}
            d["_broadcast_prepared"] = {}
        if "_values" in d:               # subquery value memo
            d["_values"] = None
        if "_dims_built" in d:           # compiled-join dim-side memo
            d["_dims_built"] = None
        for k in ("_run_memo", "_join_memo"):
            if k in d:                   # fused-segment planned-run memos:
                d[k] = {}                # cached runs hold pre-rebind exprs
        coord = d.get("coordinator")
        if coord is not None and hasattr(coord, "_specs"):
            # AQE join-reader coordinator: shared by BOTH sibling readers;
            # clone it once (memo) pointing at the cloned exchanges
            key = ("coordinator", id(coord))
            nc = memo.get(key)
            if nc is None:
                import copy
                nc = copy.copy(coord)
                nc.left = coord.left.clone_for_execution(rebind, memo)
                nc.right = coord.right.clone_for_execution(rebind, memo)
                nc._specs = None
                nc._lock = threading.Lock()
                nc.skew_splits = 0
                memo[key] = nc
            d["coordinator"] = nc
        if rebind and "pushed_filters" in d and "_arrow_filter" in d:
            # pushed parquet filters were re-bound above, but the derived
            # pyarrow filter bakes the literal VALUES — recompute it, or a
            # hit would prune files/row groups with the PREVIOUS
            # submission's probe values
            from ..io.base_scan import arrow_filter_from_condition
            d["_arrow_filter"] = arrow_filter_from_condition(
                d["pushed_filters"])
        opts = d.get("options")
        if isinstance(opts, dict) and opts.get("__dpp_filters__"):
            # DPP subqueries reference the join's build subtree: clone via
            # the same memo so they execute the rebound build side, not the
            # template's
            opts = dict(opts)
            opts["__dpp_filters__"] = [
                (col, sq.clone_for_execution(rebind, memo))
                for col, sq in opts["__dpp_filters__"]]
            d["options"] = opts

    # --- plan utilities ---------------------------------------------------
    def tree_string(self, indent: int = 0) -> str:
        lines = ["  " * indent + ("*" if self.is_tpu else " ") + " " + self.node_desc()]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    def collect_nodes(self) -> List["PhysicalPlan"]:
        out = [self]
        for c in self.children:
            out.extend(c.collect_nodes())
        return out


class CpuExec(PhysicalPlan):
    """Host operator over pyarrow Tables (stands in for Spark's CPU operators —
    the thing the reference falls back TO)."""


class TpuExec(PhysicalPlan):
    """Device operator over TpuColumnarBatch (reference GpuExec).
    Subclasses implement internal_do_execute_columnar per partition."""

    def execute_partition(self, idx: int, ctx: TaskContext) -> Iterator:
        from .. import profiling
        from ..config import DEBUG_DUMP_PATH
        from ..obs import tracer as obs
        out_rows = self.metrics["numOutputRows"]
        out_batches = self.metrics["numOutputBatches"]
        dump = ctx.conf.get(DEBUG_DUMP_PATH)
        keep_last = bool(dump)
        self._last_batch = None  # don't attribute a prior partition's batch
        it = self.internal_do_execute_columnar(idx, ctx)
        chips = ctx.chips
        if chips is not None:
            it = self._placed(it, chips, idx)
        # the query tracer (obs) rides the same slow path as xprof tracing:
        # the untraced hot loop below stays free of per-batch span setup.
        # thread_traced: tracing is per-query now — a query that is NOT
        # being traced stays on the fast loop even while a concurrent
        # session's query is traced on another thread
        tracing = profiling._PROFILING_ACTIVE or (obs._ACTIVE and
                                                  obs.thread_traced())
        name = self.node_name()
        if not (tracing or keep_last):
            # hot path: each pull runs under this operator's sync-ledger
            # scope (a thread-local tuple push — nanoseconds) so blocking
            # device→host transfers attribute to the operator that caused
            # them; row counts accumulate lazily (a deferred batch's pending
            # device count must not sync here)
            while True:
                # cooperative cancellation (docs/robustness.md "Query
                # lifecycle"): one thread-local read when no query
                # lifecycle is bound — the hot loop stays hot
                _cancel_checkpoint(name)
                with profiling.sync_scope(name):
                    batch = next(it, None)
                if batch is None:
                    return
                out_rows.add_lazy(batch.rows_lazy)
                out_batches.add(1)
                yield batch
            return
        while True:
            _cancel_checkpoint(name)
            # NVTX-range analogue: each batch pull is one named scope in the
            # xprof timeline (reference NvtxWithMetrics around operator work)
            # AND one operator span in the obs query timeline — upstream
            # operators' pulls run inside this generator frame on the same
            # thread stack, so the span tree nests exactly like the plan
            with profiling.trace_scope(name), profiling.sync_scope(name), \
                    obs.span(name, cat="op", partition=idx):
                try:
                    batch = next(it)
                except StopIteration:
                    return
                except Exception:
                    self._dump_on_failure(ctx)
                    raise
            out_rows.add_lazy(batch.rows_lazy)
            out_batches.add(1)
            if keep_last:
                self._last_batch = batch
            yield batch

    def _placed(self, it: Iterator, chips: tuple, idx: int) -> Iterator:
        """Mesh session: partition `idx` is computed on chip `idx % n`.
        A task that runs there already (`run_chip_tasks`) pulls straight
        through. A task of ANOTHER chip that pulls this partition — an
        operator that collects all its child's partitions: a broadcast
        build, a top-N, a stage — gets it computed where it lives and the
        batches moved to its own chip: rows and bytes that changed chip."""
        from ..parallel.mesh import chip_iter, current_chip
        r = idx % len(chips)
        rows = self.mesh_metric(f"chipRows.{r}")
        here = current_chip()
        if here is None or here == chips[r]:
            for batch in it:
                rows.add_lazy(batch.rows_lazy)
                yield batch
            return
        for batch in chip_iter(it, chips[r]):
            rows.add_lazy(batch.rows_lazy)
            yield self.move_to_chip(batch, here)

    def _dump_on_failure(self, ctx: TaskContext) -> None:
        """Dump the operator's last good output batch for offline repro when
        spark.rapids.sql.debug.dumpPath is set (reference DumpUtils)."""
        from ..config import DEBUG_DUMP_PATH
        path = ctx.conf.get(DEBUG_DUMP_PATH)
        batch = getattr(self, "_last_batch", None)
        if not path or batch is None:
            return
        try:
            from ..profiling import dump_batch
            dump_batch(batch, str(path), self.node_name())
        except Exception:  # noqa: BLE001 — dumping must not mask the error
            pass

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        raise NotImplementedError


def bind_references(expr: Expression, inputs: List[AttributeReference]) -> Expression:
    """Rewrite AttributeReferences to carry the ordinal of the matching input
    (reference GpuBindReferences, GpuBoundAttribute.scala)."""
    by_id = {a.expr_id: i for i, a in enumerate(inputs)}

    def rule(e: Expression):
        if isinstance(e, AttributeReference):
            if e.expr_id not in by_id:
                raise ValueError(
                    f"cannot bind {e.name}#{e.expr_id}; inputs: "
                    f"{[f'{a.name}#{a.expr_id}' for a in inputs]}")
            return AttributeReference(e.name, e.dtype, e.nullable,
                                      ordinal=by_id[e.expr_id], expr_id=e.expr_id)
        return None

    return expr.transform(rule)


def bind_all(exprs: Sequence[Expression],
             inputs: List[AttributeReference]) -> List[Expression]:
    return [bind_references(e, inputs) for e in exprs]
