"""Whole-stage segment fusion for the GENERAL execution path.

PR 1 (execs/opjit.py) collapsed the general path's dispatch count from
O(expression nodes) to O(operators): each operator's per-batch transform runs
as one cached executable. But every operator boundary still materializes a
batch and pays the fixed per-launch cost (PERF.md has its value), so a
scan→filter→project→project pipeline still costs one launch PER OPERATOR per
batch. The compiled whole-stage paths (compiled.py, compiled_join.py) prove
the fix — fuse the chain into one program — but only inside a narrow
eligibility window.

This module closes the gap for everything else: a plan-level pass (wired
through TpuOverrides after the compiled-stage passes) finds maximal chains of
adjacent general-path operators and collapses each into a
TpuFusedSegmentExec. Per batch, the segment flattens its operator pipeline by
ordinal substitution (classic projection collapse): every output column
becomes one expression over the segment's INPUT schema, and every filter
becomes one input-schema predicate. The whole flattened forest plus the AND
of the filter masks then traces into ONE cached executable
(opjit.segment_program) — a batch flows through the entire chain in a single
dispatch, with one compaction at the segment end when filters are present
(bit-identical to compacting at each filter, because the fusion gate only
admits row-wise deterministic expressions).

Beyond project/filter chains, a segment can absorb two more operator kinds
(the reference's whole-query device residency, GpuExec.scala:387):

* **A streamed-side inner equi-join** (spark.rapids.tpu.opjit.fuseJoins):
  the join terminates the chain bottom-wards — its build side becomes an
  extra segment child, materialized ONCE per partition through the PR 5
  `require_single` coalesce goal and prepared ONCE a build
  (opjit.join_build_program: key encode, hash, sort, and a directory of
  hash-prefix buckets over the sorted rows; a broadcast build's is made
  once a query and shared by every probe partition) — and each probe batch
  runs TWO launches (opjit.join_probe_program / join_emit_program) split
  at the inherent candidate-count sync: key encode + a directory look-up
  of each lane's candidate range, then pair expansion + verification +
  both-side gather + the entire flattened downstream projection/filter
  chain + one compaction. All three programs call the very traced
  functions the standalone join runs (joins._join_prepare_build/
  _join_probe_ranges/_join_emit_pairs/_compact_pairs_device), so results
  are bit-identical. String keys, non-inner join types, oversized
  build sides (which need sub-partitioning) and host-assisted expressions
  delegate the partition to the original join operator unchanged.
* **A trailing grouped aggregate** (spark.rapids.tpu.opjit.fuseAggs): a
  hash-aggregate at the TOP of the chain consumes the segment's streamed
  output and runs its whole update as one launch with a capacity-bucketed
  group table (opjit.agg_stage_program via
  TpuHashAggregateExec.aggregate_batches) — the partial-aggregation form
  whose group count stays a device scalar.

The segment also grows the **batched multi-partition entry point**
(`execute_partitions`, spark.rapids.tpu.dispatch.partitionBatch): when a
pure row-wise segment is pulled for a GROUP of partitions (the exchange map
side schedules partition groups), same-layout member batches run ONE
grouped launch (opjit.segment_program_grouped) instead of one per
partition.

Degradation mirrors PR 1 exactly:

* passthrough columns (including strings and other host-layout columns) are
  spliced around the program straight from the input batch;
* a host-assisted or otherwise untraceable operator splits the segment at
  the operator boundary — the device-pure prefix and suffix stay fused, the
  offending operator runs its existing per-operator program (which itself
  splits host-assisted expressions at the host boundary, opjit.eval_exprs);
* a segment whose first trace fails is pinned eager and every batch after
  that degrades to the per-operator programs — results are bit-identical
  either way.

Toggled by spark.rapids.tpu.opjit.fuseStages (requires opjit.enabled).
"""

from __future__ import annotations

import copy
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from ..columnar.batch import TpuColumnarBatch, compact, concat_batches
from ..config import (DISPATCH_PARTITION_BATCH, OPJIT_ENABLED,
                      OPJIT_FUSE_AGGS, OPJIT_FUSE_JOINS, OPJIT_FUSE_STAGES,
                      RapidsConf)
from ..config import TASK_RETRY_LIMIT as _TRL
from ..expressions.base import Expression, to_column
from ..obs import tracer as _obs
from .base import PhysicalPlan, TaskContext, TpuExec
from .basic import TpuFilterExec, TpuProjectExec


_MEMO_MISS = object()

#: Cap on a flattened expression's node count. Projection collapse duplicates
#: shared subtrees symbolically (XLA CSE dedups them in-trace), but a chain
#: where each column references the previous computed column k times grows
#: k^depth host-side — Spark's CollapseProject guards the same shape. Sizes
#: are PROJECTED before any tree is built, so the blowup never materializes;
#: an over-budget operator just breaks the run and executes per-op.
_MAX_FUSED_NODES = 512


def _projected_size(e: Expression, cur_sizes) -> int:
    """Node count `e` WOULD have after substitution against a schema whose
    producing expressions have `cur_sizes` nodes each — computed without
    building the substituted tree."""
    from ..expressions.base import AttributeReference
    if isinstance(e, AttributeReference):
        if cur_sizes is None:
            return 1
        if e.ordinal is None or not (0 <= e.ordinal < len(cur_sizes)):
            raise ValueError(f"unbound reference {e.name} in segment")
        return cur_sizes[e.ordinal]
    return 1 + sum(_projected_size(c, cur_sizes) for c in e.children)


def _layout_sig(batch: TpuColumnarBatch):
    """Everything the run planner's gates read off a batch: column count,
    carrier dtype, validity presence, and buffer layout (the _inputs_ok
    fields). Capacity is deliberately absent — the plan is shape-agnostic;
    only the compiled program (opjit key) specializes on it."""
    out = []
    for c in batch.columns:
        d = c.data
        out.append((type(c.dtype).__name__,
                    str(d.dtype) if hasattr(d, "dtype") else None,
                    c.validity is not None, c.offsets is None,
                    c.host_data is None, c.child is None,
                    c.children is None, getattr(d, "ndim", None)))
    return tuple(out)


def _is_join_op(op: PhysicalPlan) -> bool:
    from .joins import TpuShuffledHashJoinExec
    return isinstance(op, TpuShuffledHashJoinExec)


def _is_agg_op(op: PhysicalPlan) -> bool:
    from .aggregates import TpuHashAggregateExec
    return isinstance(op, TpuHashAggregateExec)


class TpuFusedSegmentExec(TpuExec):
    """A maximal chain of adjacent general-path operators executing as one
    stage segment: one cached executable per (segment fingerprint, bucketed
    shape) when the whole chain traces, per-operator programs otherwise.

    `ops` is the fused chain bottom-up (ops[0] consumed `child`'s output);
    the original exec objects are kept for their bound expressions and
    output schemas — their own child links are NOT executed, EXCEPT when a
    join partition delegates to the original operator (the fusion pass
    rewires that operator's children to the segment's own rewritten
    subtrees, so its semantics — sub-partitioning, symmetric build-side
    flips, empty-side fast paths — run verbatim while sharing one exchange
    materialization with the fused partitions).

    A join op may only appear as ops[0] (it terminates the chain downward);
    its build subtree is children[1]. An aggregate may only appear as
    ops[-1] (it consumes the whole streamed segment output)."""

    def __init__(self, ops: Sequence[PhysicalPlan], child: PhysicalPlan,
                 build_children: Sequence[PhysicalPlan] = (),
                 join_builds: Optional[Dict[int, int]] = None):
        super().__init__([child] + list(build_children))
        self._ops = list(ops)
        self._output = self._ops[-1].output
        self._join_builds = dict(join_builds or {})
        self._has_join = _is_join_op(self._ops[0])
        self._has_agg = _is_agg_op(self._ops[-1])
        # partition-collapsing ops (a non-per-partition shuffled join/agg,
        # NOT a broadcast join — its probe side stays per-partition) make
        # the segment single-partition and stream every input partition
        self._collapses = any(
            (_is_join_op(o) or _is_agg_op(o)) and not o.per_partition
            and o.num_partitions() == 1
            for o in self._ops) and self.num_partitions() == 1
        # planned runs memoized by (start op, input-batch layout): the
        # symbolic flatten + gate walk depends only on those, so steady-state
        # batches skip the per-batch expression-tree rebuild entirely
        self._run_memo: dict = {}
        self._join_memo: dict = {}

    @property
    def output(self):
        return self._output

    def num_partitions(self) -> int:
        return self._ops[-1].num_partitions()

    @property
    def build_child_indices(self) -> List[int]:
        """Positions in self.children holding join build sides (the batch
        coalescing pass gives these the require_single goal)."""
        return sorted(self._join_builds.values())

    def node_desc(self) -> str:
        inner = "+".join(
            type(o).__name__.replace("Tpu", "").replace("Exec", "")
            for o in self._ops)
        return f"TpuFusedSegment[{inner}]"

    def additional_metrics(self):
        return {"opFusedBatches": "DEBUG", "opFusedFallbackOps": "DEBUG",
                "opFusedJoinBatches": "DEBUG", "opFusedGroupedBatches": "DEBUG",
                "buildTime": "MODERATE", "numPairs": "DEBUG",
                "joinOutputRows": "DEBUG", "buildsIndexed": "DEBUG",
                "probesIndexed": "DEBUG"}

    def query_counters(self):
        # an absorbed aggregate's own, and the join's from the nodes the
        # segment pulls (_stream, _collect_build): its own children, which a
        # later pass (coalesce) wraps and the join operator's links pass
        # over, but the broadcast operator's own build child
        from .broadcast import TpuBroadcastHashJoinExec
        from .joins import index_counters
        out = [c for op in self._ops[self._has_join:]
               for c in op.query_counters()]
        if self._has_join:
            join = self._ops[0]
            build = join.children[1] \
                if isinstance(join, TpuBroadcastHashJoinExec) \
                else self.children[self._join_builds[0]]
            out += [("join.rows_left",
                     self.children[0].metrics["numOutputRows"]),
                    ("join.rows_right", build.metrics["numOutputRows"]),
                    ("join.rows_out", self.metrics["joinOutputRows"]),
                    ("join.subpartitioned",
                     join.metrics["subPartitionedJoins"])]
            # the fused probes' and a delegated partition's
            out += index_counters(self.metrics) + index_counters(join.metrics)
        return out

    def mesh_counters(self):
        # the probe side a chip's task took in, beside the node's own
        # and an absorbed broadcast join's copies of its build
        out = super().mesh_counters() \
            + [c for op in self._ops for c in op.mesh_counters()
               if c[0] == "mesh.broadcast_bytes"]
        if self._has_join:
            out += self.children[0].chip_rows_counters()
        return out

    # --- execution --------------------------------------------------------
    def _input_partitions(self, idx: int):
        if self._collapses:
            return range(self.children[0].num_partitions())
        return [idx]

    def internal_do_execute_columnar(self, idx: int,
                                     ctx: TaskContext) -> Iterator:
        if self._has_agg:
            agg = self._ops[-1]
            batches = [b for b in self._stream(idx, ctx)
                       if b.has_pending_rows or b.num_rows]
            names = [a.name for a in self._output]
            for out in agg.aggregate_batches(batches, ctx):
                yield out.rename(names)
            return
        yield from self._stream(idx, ctx)

    def _stream(self, idx: int, ctx: TaskContext) -> Iterator:
        """The segment's per-batch pipeline: ops[0:] minus a trailing agg."""
        from ..memory.retry import with_retry
        from ..memory.spill import SpillableColumnarBatch
        op_time = self.metrics["opTime"]
        n_stream = len(self._ops) - (1 if self._has_agg else 0)
        out_attrs = self._ops[n_stream - 1].output if n_stream else None
        names = [a.name for a in out_attrs] if out_attrs else None
        join_state: dict = {}
        # phase `segment.launch`: one lap a batch put through the chain (the
        # absorbed join's probe included), never open across a yield, and
        # flushed however the consumer leaves the generator. The child's pull
        # lies outside it, and so does the fused join's build side; a
        # delegated join's laps cover the tail alone, its own `join.collect`
        # and `join.probe` run in the pull between them
        laps = _obs.PhaseLaps()
        try:
            if self._has_join:
                delegated = self._join_delegation(idx, ctx, join_state)
                if delegated is not None:
                    # original join operator runs the partition (oversized /
                    # untraceable builds, non-inner types kept for safety);
                    # remaining ops apply per output batch
                    for batch in delegated:
                        self.metrics["joinOutputRows"].add_lazy(
                            batch.rows_lazy)
                        with op_time.timed(), laps.lap("segment.launch"):
                            out = self._apply_tail(batch, 1, n_stream, ctx)
                        if out is not None:
                            yield out.rename(names)
                    return

            def transform(batch: TpuColumnarBatch):
                with laps.lap("segment.launch"):
                    out = self._transform(batch, ctx, join_state, n_stream)
                    return out.rename(names) if out is not None else None

            for p in self._input_partitions(idx):
                for batch in self.children[0].execute_partition(p, ctx):
                    with op_time.timed():
                        # the streamed segment is row-wise over probe rows,
                        # so the operator-level retry-with-split contract
                        # holds for the fused chain (incl. the inner-join
                        # probe) too
                        for out in with_retry(SpillableColumnarBatch(batch),
                                              transform,
                                              max_retries=ctx.conf.get(_TRL)):
                            if out is not None:
                                yield out
        finally:
            laps.flush()

    def _apply_tail(self, batch: TpuColumnarBatch, start: int, end: int,
                    ctx: TaskContext) -> Optional[TpuColumnarBatch]:
        from . import opjit
        cur = batch
        i = start
        while i < end:
            run = self._planned_run(i, cur, ctx, end) \
                if opjit.enabled(ctx.eval_ctx) else None
            if run is not None:
                out = self._run_fused(run, cur, ctx)
                if out is not None:
                    cur = out
                    i = run[0]
                    self.metrics["opFusedBatches"].add(1)
                    continue
            cur = self._apply_op(self._ops[i], cur, ctx)
            self.metrics["opFusedFallbackOps"].add(1)
            i += 1
        return cur

    def _transform(self, batch: TpuColumnarBatch, ctx: TaskContext,
                   join_state: dict,
                   n_stream: int) -> Optional[TpuColumnarBatch]:
        from . import opjit
        cur = batch
        start = 0
        if self._has_join:
            bstate = join_state.get("state")
            if bstate is None or bstate[0] is None:
                return None  # empty build side: inner join emits nothing
            jr = self._planned_join_run(cur, bstate, ctx, n_stream) \
                if opjit.enabled(ctx.eval_ctx) else None
            fused = self._run_join_fused(jr, cur, bstate, ctx) \
                if jr is not None else None
            if fused is None:
                # per-batch fallback (no plan, or the probe/emit program
                # pinned eager): the original operator's pairwise join
                # against the materialized build batch (bit-identical)
                op = self._ops[0]
                names = [a.name for a in op.output]
                cur = op._join_pair(cur, bstate[0], names, ctx)
                self.metrics["opFusedFallbackOps"].add(1)
                if cur is None:
                    return None
                start = 1
            else:
                cur = fused
                self.metrics["opFusedJoinBatches"].add(1)
                start = jr["end"]
            self.metrics["joinOutputRows"].add_lazy(cur.rows_lazy)
        return self._apply_tail(cur, start, n_stream, ctx)

    # --- join stage -------------------------------------------------------
    def _collect_build(self, idx: int, ctx: TaskContext):
        from .broadcast import TpuBroadcastHashJoinExec
        join = self._ops[0]
        if isinstance(join, TpuBroadcastHashJoinExec):
            # the broadcast operator's once-per-query cached build (every
            # probe partition shares ONE materialization, as unfused)
            with self.metrics["buildTime"].timed():
                return join._build_side(ctx)
        child = self.children[self._join_builds[0]]
        with self.metrics["buildTime"].timed(), _obs.phase("join.collect"):
            batches = []
            if join.per_partition:
                batches.extend(child.execute_partition(idx, ctx))
            else:
                for p in range(child.num_partitions()):
                    batches.extend(child.execute_partition(p, ctx))
            batches = [b for b in batches if b.has_pending_rows or b.num_rows]
            return concat_batches(batches) if batches else None

    def _join_delegation(self, idx: int, ctx: TaskContext,
                         join_state: dict) -> Optional[Iterator]:
        """Decide fused-vs-delegated for this partition. Returns the
        original operator's batch iterator to delegate, or None to run the
        fused probe (join_state then carries the materialized build)."""
        from ..config import BATCH_SIZE_ROWS
        from . import opjit
        from .broadcast import TpuBroadcastHashJoinExec
        join = self._ops[0]
        fuse = (opjit.enabled(ctx.eval_ctx)
                and bool(ctx.conf.get(OPJIT_FUSE_JOINS))
                and join.join_type == "inner" and join.left_keys
                and opjit.join_probe_gate_ok(
                    join.left_keys + join.right_keys,
                    [join.condition] if join.condition is not None else [],
                    []))
        if not fuse:
            return join.execute_partition(idx, ctx)
        build = self._collect_build(idx, ctx)
        if build is not None and not build.has_pending_rows \
                and build.num_rows == 0:
            build = None
        if build is not None \
                and build.num_rows > int(ctx.conf.get(BATCH_SIZE_ROWS)):
            # oversized build: the original operator's sub-partitioning
            # machinery (GpuSubPartitionHashJoin analogue) handles it
            return join.execute_partition(idx, ctx)
        prepared = None
        if build is not None:
            def prepare():
                key_cols = opjit.eval_exprs(
                    join.right_keys, [k.dtype for k in join.right_keys],
                    build, ctx.eval_ctx, self.metrics)
                if not all(opjit.plain_device_col(c) for c in key_cols):
                    return None
                out = opjit.join_build_program(key_cols, build.rows_arg,
                                               ctx.eval_ctx, self.metrics)
                if out is not None:
                    self.metrics["buildsIndexed"].add(1)
                return out
            # once a build: the broadcast operator keeps its build's beside
            # the build, for every probe partition of the query
            prepared = join.prepared_build(prepare) \
                if isinstance(join, TpuBroadcastHashJoinExec) else prepare()
            if prepared is None:
                return join.execute_partition(idx, ctx)
        join_state["state"] = (build, prepared)
        return None

    def _planned_join_run(self, batch: TpuColumnarBatch, bstate,
                          ctx: TaskContext, n_stream: int):
        key = (bool(ctx.eval_ctx.ansi), _layout_sig(batch),
               _layout_sig(bstate[0]))
        hit = self._join_memo.get(key, _MEMO_MISS)
        if hit is not _MEMO_MISS:
            return hit
        run = self._plan_join_run(batch, bstate, ctx, n_stream)
        if len(self._join_memo) > 64:
            self._join_memo.clear()
        self._join_memo[key] = run
        return run

    def _plan_join_run(self, batch: TpuColumnarBatch, bstate,
                       ctx: TaskContext, n_stream: int):
        """Plan the fused probe: flatten ops[1:] over the JOINED schema
        (probe child columns ++ build child columns) into output specs and
        filters, and verify every referenced column is a plain fixed-width
        device vector on its side. Returns a run dict or None (per-batch
        fallback)."""
        from ..expressions.base import AttributeReference
        from . import opjit
        join = self._ops[0]
        build = bstate[0]
        if not opjit.segment_inputs_ok(join.left_keys, batch):
            return None
        n_l = len(join.children[0].output)
        n_r = len(join.children[1].output)
        joined_attrs = list(join.children[0].output) \
            + list(join.children[1].output)
        post_filters: List[Expression] = []
        if join.condition is not None:
            if not opjit.segment_gate_ok(join.condition):
                return None
            post_filters.append(join.condition)
        cur_exprs: Optional[List[Expression]] = None
        cur_sizes: Optional[List[int]] = None
        end = 1
        try:
            for op in self._ops[1:n_stream]:
                if isinstance(op, TpuProjectExec):
                    sizes = [_projected_size(e, cur_sizes)
                             for e in op.exprs]
                    if max(sizes, default=0) > _MAX_FUSED_NODES:
                        break
                    subd = [opjit.substitute(e, cur_exprs) for e in op.exprs]
                    if not all(opjit.fusable_expr(e) for e in subd):
                        break
                    cur_exprs = subd
                    cur_sizes = sizes
                elif isinstance(op, TpuFilterExec):
                    if _projected_size(op.condition,
                                       cur_sizes) > _MAX_FUSED_NODES:
                        break
                    cond = opjit.substitute(op.condition, cur_exprs)
                    if not opjit.segment_gate_ok(cond):
                        break
                    post_filters.append(cond)
                else:
                    break
                end += 1
        except ValueError:
            pass
        out_attrs = self._ops[end - 1].output
        if cur_exprs is None:
            cur_exprs = [
                AttributeReference(a.name, a.dtype, a.nullable, ordinal=o,
                                   expr_id=a.expr_id)
                for o, a in enumerate(joined_attrs)]
        specs: List[Tuple[str, object]] = []
        traced: List[Expression] = []
        for e, attr in zip(cur_exprs, out_attrs):
            p = opjit.is_passthrough(e)
            if p:
                a = opjit.strip_alias(e)
                if a.ordinal is None or not (0 <= a.ordinal < n_l + n_r):
                    return None
                specs.append(("pass", a.ordinal))
            else:
                if not opjit.segment_gate_ok(opjit.strip_alias(e)):
                    return None
                specs.append(("jit", len(traced)))
                traced.append(opjit.strip_alias(e))
        pass_ords = set(o for kind, o in specs if kind == "pass")
        trace_ords = set()
        for e in traced + post_filters:
            for a in e.collect(
                    lambda x: isinstance(x, AttributeReference)):
                if a.ordinal is None or a.ordinal < 0:
                    return None
                trace_ords.add(a.ordinal)

        def _col(o):
            if o < n_l:
                return batch.columns[o] if o < len(batch.columns) else None
            bo = o - n_l
            return build.columns[bo] if bo < len(build.columns) else None

        host_ords = set()
        for o in pass_ords | trace_ords:
            c = _col(o)
            if c is None:
                return None
            if not opjit.plain_device_col(c):
                # host-layout column (strings/lists/structs): legal only as
                # a pure PASSTHROUGH — the emit program returns the final
                # pair indices and the caller gathers it with the same
                # columnar.batch.gather the unfused join uses (q3's
                # customer strings ride the fused probe this way); anything
                # an expression actually reads must be a plain device vector
                if o in trace_ords:
                    return None
                host_ords.add(o)
        specs = [("host", v) if kind == "pass" and v in host_ords
                 else (kind, v) for kind, v in specs]
        device_ords = (pass_ords | trace_ords) - host_ords
        probe_ords = sorted(o for o in device_ords if o < n_l)
        build_ords = sorted(o for o in device_ords if o >= n_l)
        return {"end": end, "specs": specs, "traced": traced,
                "filters": post_filters, "out_attrs": out_attrs,
                "probe_ords": probe_ords, "build_ords": build_ords,
                "n_l": n_l, "has_host": bool(host_ords)}

    def _run_join_fused(self, jr, batch: TpuColumnarBatch, bstate,
                        ctx: TaskContext) -> Optional[TpuColumnarBatch]:
        from ..columnar.vector import audited_sync_int, bucket_capacity
        from ..config import DEFERRED_COMPACTION
        from . import opjit
        join = self._ops[0]
        build, prepared = bstate
        res = opjit.join_probe_program(
            [], [], [], join.left_keys, batch, prepared, ctx.eval_ctx,
            self.metrics)
        if res is None:
            return None
        state, _ = res
        self.metrics["probesIndexed"].add(1)
        # host sync: candidate-pair count sizes the static emit shape — the
        # same inherent sync the standalone join pays (joins._device_equi_join)
        total = audited_sync_int(state["total"], "pairs")
        self.metrics["numPairs"].add(total)
        out_cap = bucket_capacity(max(total, 1))
        state["total"] = jnp.int32(total)
        probe_cols = {o: batch.columns[o] for o in jr["probe_ords"]}
        build_cols = {o: build.columns[o - jr["n_l"]]
                      for o in jr["build_ords"]}
        out_dtypes = [a.dtype for a in jr["out_attrs"]]
        emit = opjit.join_emit_program(
            [tuple(s) for s in jr["specs"]], jr["traced"], out_dtypes,
            jr["filters"], state, probe_cols, build_cols, batch.rows_arg,
            build.rows_arg, out_cap, jr["n_l"], ctx.eval_ctx, self.metrics,
            want_indices=jr["has_host"])
        if emit is None:
            return None
        outs, n_out, idxs = emit
        if not ctx.conf.get(DEFERRED_COMPACTION):
            n_out = audited_sync_int(n_out, "pairs")
        host_cols = {}
        if jr["has_host"]:
            # host-layout passthroughs (strings etc.): gather by the final
            # pair indices with the SAME columnar gather the unfused join
            # uses — device offsets math + one `chars` sync per column
            from ..columnar.batch import gather
            fpi, fbi = idxs
            for kind, o in jr["specs"]:
                if kind != "host":
                    continue
                if o < jr["n_l"]:
                    src, idx, rows = batch.columns[o], fpi, batch.rows_lazy
                else:
                    src, idx, rows = (build.columns[o - jr["n_l"]], fbi,
                                      build.rows_lazy)
                g = gather(TpuColumnarBatch([src], rows), idx, n_out,
                           out_cap)
                host_cols[o] = g.columns[0]
        from ..columnar.vector import TpuColumnVector
        cols = []
        dev = iter(outs)
        for (kind, v), a in zip(jr["specs"], jr["out_attrs"]):
            if kind == "host":
                cols.append(host_cols[v])
            else:
                d, vv = next(dev)
                cols.append(TpuColumnVector(a.dtype, d, vv, n_out))
        return TpuColumnarBatch(cols, n_out,
                                [a.name for a in jr["out_attrs"]])

    # --- project/filter runs ---------------------------------------------
    def _planned_run(self, start: int, batch: TpuColumnarBatch,
                     ctx: TaskContext, end: Optional[int] = None):
        """Memoized _plan_run: keyed by (start, conf fingerprint, layout of
        the current batch) — everything the plan decision reads. A benign
        compute-twice race under concurrent partitions lands the same value."""
        if end is None:
            end = len(self._ops) - (1 if self._has_agg else 0)
        key = (start, end, bool(ctx.eval_ctx.ansi), _layout_sig(batch))
        hit = self._run_memo.get(key, _MEMO_MISS)
        if hit is not _MEMO_MISS:
            return hit
        run = self._plan_run(start, batch, ctx, end)
        if len(self._run_memo) > 64:  # distinct layouts are few; stay bounded
            self._run_memo.clear()
        self._run_memo[key] = run
        return run

    def _plan_run(self, start: int, batch: TpuColumnarBatch,
                  ctx: TaskContext, stop: int):
        """Greedy maximal fusable run of ops[start:stop] against `batch`:
        flatten each operator by ordinal substitution and stop at the first
        operator whose flattened expressions cannot fuse (not a passthrough
        and outside the trace gate). Returns (end, out_specs, filters) where
        out_specs maps each final output position to ('pass', input_attr) or
        ('jit', input_expr), or None when fewer than two ops fuse."""
        from . import opjit
        cur_exprs: Optional[List[Expression]] = None  # None == identity
        cur_sizes: Optional[List[int]] = None
        filters: List[Expression] = []
        end = start
        try:
            for op in self._ops[start:stop]:
                if isinstance(op, TpuProjectExec):
                    sizes = [_projected_size(e, cur_sizes)
                             for e in op.exprs]
                    if max(sizes, default=0) > _MAX_FUSED_NODES:
                        break  # shared-subtree blowup: stop before building
                    subd = [opjit.substitute(e, cur_exprs) for e in op.exprs]
                    if not all(opjit.fusable_expr(e) for e in subd):
                        break
                    cur_exprs = subd
                    cur_sizes = sizes
                elif isinstance(op, TpuFilterExec):
                    if _projected_size(op.condition,
                                       cur_sizes) > _MAX_FUSED_NODES:
                        break
                    cond = opjit.substitute(op.condition, cur_exprs)
                    if not opjit.segment_gate_ok(cond):
                        break
                    filters.append(cond)
                else:  # unknown fusable marker: never absorb blindly
                    break
                end += 1
        except ValueError:  # unbound reference: not fusable past this point
            pass
        if end - start < 2 and not (end > start
                                    and (self._has_join or self._has_agg)):
            return None
        if end == start:
            return None
        if cur_exprs is None:  # filters only: output schema == input schema
            from ..expressions.base import AttributeReference
            cur_exprs = [
                AttributeReference(a.name, a.dtype, a.nullable, ordinal=o,
                                   expr_id=a.expr_id)
                for o, a in enumerate(self._ops[end - 1].output)]
        out_attrs = self._ops[end - 1].output
        specs: List[Tuple[str, object]] = []
        traced: List[Expression] = []
        for e, attr in zip(cur_exprs, out_attrs):
            p = opjit.is_passthrough(e)
            if p:
                specs.append(("pass", opjit.strip_alias(e)))
            else:
                specs.append(("jit", (len(traced), attr.dtype)))
                traced.append(e)
        if (traced or filters) and not opjit.segment_inputs_ok(
                traced + filters, batch):
            return None
        return end, specs, traced, filters, out_attrs

    def _run_fused(self, run, batch: TpuColumnarBatch,
                   ctx: TaskContext) -> Optional[TpuColumnarBatch]:
        from . import opjit
        end, specs, traced, filters, out_attrs = run
        names = [a.name for a in out_attrs]
        if not traced and not filters:
            # pure column shuffle (select/reorder): no dispatch at all
            cols = [batch.columns[spec.ordinal] for _, spec in specs]
            return TpuColumnarBatch(cols, batch.rows_lazy, names)
        dtypes = [spec[1] for kind, spec in specs if kind == "jit"]
        res = opjit.segment_program(traced, dtypes, filters, batch,
                                    ctx.eval_ctx, self.metrics)
        if res is None:
            return None
        jit_cols, keep = res
        return self._assemble(specs, jit_cols, keep, batch, names, ctx)

    def _assemble(self, specs, jit_cols, keep, batch, names,
                  ctx) -> TpuColumnarBatch:
        cols = []
        for kind, spec in specs:
            if kind == "pass":
                cols.append(batch.columns[spec.ordinal])
            else:
                cols.append(jit_cols[spec[0]])
        out = TpuColumnarBatch(cols, batch.rows_lazy, names)
        if keep is not None:
            # ONE compaction for the whole segment; with deferred compaction
            # the kept count stays a device scalar until the exchange/collect
            # boundary needs a host int (it rides the boundary device_get)
            from ..config import DEFERRED_COMPACTION
            out = compact(out, keep,
                          deferred=bool(ctx.conf.get(DEFERRED_COMPACTION)))
        return out

    def _apply_op(self, op: PhysicalPlan, batch: TpuColumnarBatch,
                  ctx: TaskContext) -> TpuColumnarBatch:
        """One operator on its existing per-operator path (PR 1 semantics:
        jittable forests/predicates still run as cached programs, the rest
        eagerly — identical results to the standalone exec)."""
        from . import opjit
        if isinstance(op, TpuProjectExec):
            out_dtypes = [a.dtype for a in op.output]
            cols = opjit.eval_exprs(op.exprs, out_dtypes, batch,
                                    ctx.eval_ctx, self.metrics)
            return TpuColumnarBatch(cols, batch.rows_lazy,
                                    [a.name for a in op.output])
        mask = opjit.filter_mask(op.condition, batch, ctx.eval_ctx,
                                 self.metrics)
        if mask is None:
            mask_col = to_column(op.condition.eval_tpu(batch, ctx.eval_ctx),
                                 batch)
            mask = mask_col.data.astype(jnp.bool_)
            if mask_col.validity is not None:
                mask = mask & mask_col.validity  # null predicate → drop
        return compact(batch, mask)

    # --- batched multi-partition dispatch ---------------------------------
    def execute_partitions(self, ids, ctx_of) -> Iterator:
        """Multi-partition entry point (spark.rapids.tpu.dispatch.
        partitionBatch): a pure row-wise segment runs same-layout member
        batches of a whole partition group as ONE grouped launch
        (opjit.segment_program_grouped), bit-identical to per-partition
        dispatch. Segments with join/agg stages (whose per-partition build/
        group state cannot merge) and non-groupable batches fall back to
        per-partition execution, preserving order either way."""
        from . import opjit
        ids = list(ids)
        if not ids:
            return
        first_ctx = ctx_of(ids[0])
        group_size = 1
        if first_ctx is not None:
            try:
                group_size = max(1, int(first_ctx.conf.get(
                    DISPATCH_PARTITION_BATCH)))
            except Exception:  # noqa: BLE001
                group_size = 1
        if (len(ids) <= 1 or group_size <= 1 or self._has_join
                or self._has_agg or self._collapses
                or not opjit.enabled(first_ctx.eval_ctx)):
            yield from super().execute_partitions(ids, ctx_of)
            return
        from .. import profiling
        out_rows = self.metrics["numOutputRows"]
        out_batches = self.metrics["numOutputBatches"]
        op_time = self.metrics["opTime"]
        name = self.node_name()
        names = [a.name for a in self._output]
        n_stream = len(self._ops)
        # pull every member's inputs (buffered per member, original order)
        members: List[Tuple[int, TaskContext, List[TpuColumnarBatch]]] = []
        for i in ids:
            if i == ids[0] and first_ctx is not None:
                ctx = first_ctx
            else:
                ctx = ctx_of(i)
            with profiling.sync_scope(name):
                members.append((i, ctx,
                                list(self.children[0].execute_partition(
                                    i, ctx))))
        # lanes grouped by (layout, whole-chain run): a grouped launch only
        # fires when one planned run covers the ENTIRE chain for the layout.
        # Each batch carries its sequence number within its partition so the
        # final emit restores the per-partition batch order exactly as the
        # degraded (per-partition) path would produce it — lane-vs-single
        # routing must not reorder an ordered upstream (sorted input)
        results: Dict[int, List[Tuple[int, TpuColumnarBatch]]] = {
            i: [] for i in ids}
        pending: Dict[Tuple, List[Tuple[int, int, TaskContext,
                                        TpuColumnarBatch]]] = {}
        singles: List[Tuple[int, int, TaskContext, TpuColumnarBatch]] = []
        for i, ctx, batches in members:
            for seq, b in enumerate(batches):
                run = self._planned_run(0, b, ctx)
                if run is not None and run[0] == n_stream:
                    pending.setdefault(_layout_sig(b), []).append(
                        (i, seq, ctx, b))
                else:
                    singles.append((i, seq, ctx, b))
        laps = _obs.PhaseLaps()  # `segment.launch`: one lap a grouped launch
        try:
            with profiling.sync_scope(name), op_time.timed():
                for lanes in pending.values():
                    pos = 0
                    while pos < len(lanes):
                        chunk = lanes[pos:pos + group_size]
                        pos += group_size
                        with laps.lap("segment.launch"):
                            self._run_group(chunk, results, names)
                for i, seq, ctx, b in singles:
                    with laps.lap("segment.launch"):
                        out = self._transform_single(b, ctx, names)
                    if out is not None:
                        results[i].append((seq, out))
        finally:
            laps.flush()
        for i in ids:
            for _, out in sorted(results[i], key=lambda so: so[0]):
                out_rows.add_lazy(out.rows_lazy)
                out_batches.add(1)
                yield i, out

    def _run_group(self, lanes, results, names) -> None:
        from ..memory.hbm import TpuOOM
        from . import opjit
        if len(lanes) == 1:
            i, seq, ctx, b = lanes[0]
            out = self._transform_single(b, ctx, names)
            if out is not None:
                results[i].append((seq, out))
            return
        ctx = lanes[0][2]
        run = self._planned_run(0, lanes[0][3], ctx)
        end, specs, traced, filters, out_attrs = run
        res = None
        if traced or filters:
            try:
                res = opjit.segment_program_grouped(
                    traced, [s[1] for k, s in specs if k == "jit"], filters,
                    [b for _, _, _, b in lanes], ctx.eval_ctx, self.metrics)
            except TpuOOM:
                res = None  # degrade to per-member (full retry/spill path)
        if res is None and (traced or filters):
            for i, seq, lctx, b in lanes:
                out = self._transform_single(b, lctx, names)
                if out is not None:
                    results[i].append((seq, out))
            return
        if res is not None:
            # only count batches an actual grouped launch covered — pure
            # column shuffles below dispatch nothing at all
            self.metrics["opFusedGroupedBatches"].add(len(lanes))
        emitted: List[Tuple[int, Tuple[int, TpuColumnarBatch]]] = []
        try:
            for (i, seq, lctx, b), member in zip(
                    lanes,
                    res if res is not None else [(None, None)] * len(lanes)):
                if traced or filters:
                    jit_cols, keep = member
                    out = self._assemble(specs, jit_cols, keep, b, names,
                                         lctx)
                else:  # pure column shuffle
                    cols = [b.columns[spec.ordinal] for _, spec in specs]
                    out = TpuColumnarBatch(cols, b.rows_lazy, names)
                emitted.append((i, (seq, out)))
        except TpuOOM:
            # assembly OOM after a successful grouped launch: drop the
            # grouped outputs and reprocess the whole lane per member
            # through the full retry/spill path (bit-identical results)
            for i, seq, lctx, b in lanes:
                out = self._transform_single(b, lctx, names)
                if out is not None:
                    results[i].append((seq, out))
            return
        for i, so in emitted:
            results[i].append(so)

    def _transform_single(self, batch, ctx,
                          names) -> Optional[TpuColumnarBatch]:
        from ..memory.retry import with_retry
        from ..memory.spill import SpillableColumnarBatch
        outs = [o for o in with_retry(
            SpillableColumnarBatch(batch),
            lambda b: self._transform(b, ctx, {}, len(self._ops)),
            max_retries=ctx.conf.get(_TRL)) if o is not None]
        if not outs:
            return None
        out = outs[0] if len(outs) == 1 else concat_batches(outs)
        return out.rename(names)


# ---------------------------------------------------------------------------
# plan pass
# ---------------------------------------------------------------------------

#: general-path operators a segment may absorb (marked in execs/basic.py)
def _fusable(node: PhysicalPlan) -> bool:
    return getattr(node, "fusable_segment_op", False)


def _absorbable_join(node: PhysicalPlan) -> bool:
    """Joins a segment may take over: inner equi-joins (any residual
    condition folds into the post-join filter chain). The symmetric variant
    is absorbed too — the fused probe pins build=right, which is a per-
    partition perf heuristic, never a semantic choice; delegated partitions
    keep the flip."""
    from .joins import TpuShuffledHashJoinExec
    return (isinstance(node, TpuShuffledHashJoinExec)
            and node.join_type == "inner" and bool(node.left_keys))


def _absorbable_agg(node: PhysicalPlan) -> bool:
    from .aggregates import TpuHashAggregateExec
    return (isinstance(node, TpuHashAggregateExec)
            and node.mode == "complete" and bool(node.grouping))


def fuse_stage_segments(plan: PhysicalPlan, conf: RapidsConf) -> PhysicalPlan:
    """Collapse maximal chains of adjacent fusable general-path operators
    into TpuFusedSegmentExec nodes. Runs AFTER the compiled-stage passes
    (they pattern-match the raw project/filter chains) and is a no-op when
    fusion or the opjit cache is disabled. Compiled-stage FALLBACK subtrees
    are rewritten too (q3's near-unique group keys trip the agg stage's
    fallback on every run, so the fallback path IS the general path there);
    an id-memo keeps subtrees shared between a stage's children and its
    fallback pointing at the SAME fused nodes: the agg stage's fallback
    stands over the stage's own source (TpuStageSourceExec, which takes
    over what the stage pulled), and exchanges materialize once."""
    if not (conf.get(OPJIT_ENABLED) and conf.get(OPJIT_FUSE_STAGES)):
        return plan
    return _fuse(plan, bool(conf.get(OPJIT_FUSE_JOINS)),
                 bool(conf.get(OPJIT_FUSE_AGGS)), {})


def _collect_chain(plan: PhysicalPlan, fuse_joins: bool, fuse_aggs: bool):
    """Maximal absorbable chain starting at `plan`, walking child 0.
    Returns (top-down chain, build plan or None, node below the chain).
    A join terminates the chain (it becomes ops[0], bottom-up); an
    aggregate may only start it (it becomes ops[-1], the consumer)."""
    chain: List[PhysicalPlan] = []
    build: Optional[PhysicalPlan] = None
    node = plan
    while True:
        if _fusable(node):
            chain.append(node)
            node = node.children[0]
            continue
        if fuse_joins and _absorbable_join(node):
            chain.append(node)
            build = node.children[1]
            node = node.children[0]
            break  # the join is the chain's bottom operator
        if fuse_aggs and not chain and _absorbable_agg(node):
            chain.append(node)
            node = node.children[0]
            continue
        break
    return chain, build, node


def _fuse(plan: PhysicalPlan, fuse_joins: bool, fuse_aggs: bool,
          memo: dict) -> PhysicalPlan:
    cached = memo.get(id(plan))
    if cached is not None:
        return cached
    out = _fuse_node(plan, fuse_joins, fuse_aggs, memo)
    memo[id(plan)] = out
    return out


def _fuse_node(plan: PhysicalPlan, fuse_joins: bool, fuse_aggs: bool,
               memo: dict) -> PhysicalPlan:
    chain, build, below = _collect_chain(plan, fuse_joins, fuse_aggs)
    has_join = build is not None
    # a lone project/filter or a lone aggregate is not worth a segment (the
    # aggregate's own fused update covers it — a lone agg never satisfies
    # this condition since an absorbed join implies len(chain) >= 1 with
    # the join at chain's end); a join always is — its probe fuses with
    # whatever sits above it, even nothing
    if len(chain) >= 2 or has_join:
        child = _fuse(below, fuse_joins, fuse_aggs, memo)
        ops = list(reversed(chain))
        build_children = []
        join_builds: Dict[int, int] = {}
        if has_join:
            join_builds[0] = 1
            fused_build = _fuse(build, fuse_joins, fuse_aggs, memo)
            build_children.append(fused_build)
            # delegated partitions run the original operator: point it at
            # the SAME rewritten subtrees the segment executes, so a join
            # with mixed fused/delegated partitions (oversized builds,
            # non-device key columns) shares one exchange materialization
            # instead of re-running the whole map side on the stale copy
            join = ops[0]
            if join.children[0] is not child \
                    or join.children[1] is not fused_build:
                join.children = [child, fused_build]
        return TpuFusedSegmentExec(ops, child, build_children,
                                   join_builds)
    new_children = [_fuse(c, fuse_joins, fuse_aggs, memo)
                    for c in plan.children]
    # a compiled stage's fallback subtree executes whenever the stage bails
    # (oversized group domain, trace failure): fuse it too, through the
    # same memo so nodes shared with children stay the same objects
    fb = getattr(plan, "fallback", None)
    new_fb = _fuse(fb, fuse_joins, fuse_aggs, memo) \
        if isinstance(fb, PhysicalPlan) else fb
    if all(a is b for a, b in zip(new_children, plan.children)) \
            and new_fb is fb:
        return plan
    new = copy.copy(plan)
    new.children = new_children
    if new_fb is not fb:
        new.fallback = new_fb
    return new
