"""Batch coalescing for the general path.

Reference: GpuCoalesceBatches.scala (CoalesceGoal hierarchy :110-248,
GpuCoalesceIterator:697) and GpuShuffleCoalesceExec. The reference treats
small batches as a first-class performance bug: every batch-hungry operator
gets its input concatenated up to `spark.rapids.sql.batchSizeBytes` first,
because per-batch launch overhead dominates otherwise: every program launch
has a fixed dispatch+sync cost (PERF.md has its value on the attached chip),
so an operator fed N undersized batches pays it N times where once would do.

Two coordinated layers, one toggle (`spark.rapids.tpu.coalesce.enabled`):

* **Device-side** (`TpuCoalesceBatchesExec`, the GpuCoalesceBatches
  analogue): concatenate device batches up to batchSizeBytes/batchSizeRows
  before joins, aggregates, sorts and fused segments. Pending inputs are
  held as `SpillableColumnarBatch` so HBM pressure can evict them
  mid-concat; the `require_single` goal (reference RequireSingleBatch,
  used for join build sides) concatenates everything regardless of target.
  `insert_coalesce` is the plan pass wiring it in (plan/overrides.py).
* **Host-side** (`coalesce_arrow_stream`, the GpuShuffleCoalesceExec
  analogue): concatenate fetched shuffle blocks / scan tables to the same
  targets BEFORE the host→device upload, so one upload and one downstream
  dispatch replace one per block. Used by the exchange reduce read
  (shuffle/exchange.py) and `HostToDeviceExec` (execs/transitions.py).
"""

from __future__ import annotations

import copy
from typing import Iterator, List, Optional

import numpy as np

from ..columnar.batch import TpuColumnarBatch, concat_batches
from ..config import BATCH_SIZE_BYTES, BATCH_SIZE_ROWS, COALESCE_ENABLED
from .base import PhysicalPlan, TaskContext, TpuExec


def coalesce_enabled(conf) -> bool:
    return bool(conf.get(COALESCE_ENABLED))


def coalesce_targets(conf) -> tuple:
    """(target_rows, target_bytes) both layers coalesce toward."""
    return int(conf.get(BATCH_SIZE_ROWS)), int(conf.get(BATCH_SIZE_BYTES))


# ---------------------------------------------------------------------------
# host-side: concat Arrow tables to target size before the H→D upload
# (reference GpuShuffleCoalesceExec — the concat is cheap host memcpy; the
# upload and every downstream dispatch then run once per TARGET-sized batch)
# ---------------------------------------------------------------------------


def coalesce_arrow_stream(tables, target_rows: int,
                          target_bytes: int) -> Iterator:
    """Concatenate a stream of pyarrow Tables up to the row/byte targets
    (whichever trips first closes the batch, like GpuCoalesceIterator
    honoring both goals). Empty/None tables are dropped."""
    import pyarrow as pa
    pend: List = []
    rows = 0
    nbytes = 0
    for t in tables:
        if t is None or t.num_rows == 0:
            continue
        pend.append(t)
        rows += t.num_rows
        nbytes += t.nbytes
        if rows >= target_rows or (target_bytes and nbytes >= target_bytes):
            yield pa.concat_tables(pend) if len(pend) > 1 else pend[0]
            pend, rows, nbytes = [], 0, 0
    if pend:
        yield pa.concat_tables(pend) if len(pend) > 1 else pend[0]


# ---------------------------------------------------------------------------
# device-side: the coalesce exec
# ---------------------------------------------------------------------------


class TpuCoalesceBatchesExec(TpuExec):
    """Concatenate small device batches up to a target size (reference
    CoalesceGoal / GpuCoalesceIterator, GpuCoalesceBatches.scala:110-248,697).

    Pending inputs are spillable: a coalesce staging N batches is exactly
    the window where HBM pressure from sibling tasks peaks, so each input
    registers with the buffer catalog and unspills on concat. The
    `require_single` goal (reference RequireSingleBatch — join build sides)
    ignores the targets and emits one batch per partition."""

    def __init__(self, child: PhysicalPlan, goal: str = "target",
                 target_rows: Optional[int] = None):
        super().__init__([child])
        self.goal = goal  # "target" | "require_single"
        self.target_rows = target_rows

    @property
    def output(self):
        return self.children[0].output

    def node_desc(self) -> str:
        return f"TpuCoalesceBatches[{self.goal}]"

    def additional_metrics(self):
        return {"concatTime": "MODERATE", "numInputBatches": "DEBUG"}

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        target = self.target_rows or ctx.conf.batch_size_rows
        target_bytes = ctx.conf.batch_size_bytes
        pending: List = []
        rows = 0          # exact, unless `estimated` (then an upper bound)
        size = 0
        estimated = False
        concat_time = self.metrics["concatTime"]
        n_in = self.metrics["numInputBatches"]
        from ..memory.spill import (SpillableColumnarBatch,
                                    materialize_spillable_counts)

        def concat_spillables(spillables):
            if len(spillables) == 1:
                out = spillables[0].get_batch()
                spillables[0].close()
                return out
            batches = [sp.get_batch() for sp in spillables]
            out = concat_batches(batches)
            for sp in spillables:
                sp.close()
            return out

        try:
            for b in self.children[0].execute_partition(idx, ctx):
                n_in.add(1)
                pending.append(SpillableColumnarBatch(b))
                # a deferred row count (compact(deferred=True) upstream) must
                # NOT be forced here — one sync per input batch is exactly the
                # round trip this layer exists to amortize. Count the padded
                # capacity as an upper bound instead.
                rl = b.rows_lazy
                if isinstance(rl, (int, np.integer)):
                    rows += int(rl)
                else:
                    rows += b.capacity
                    estimated = True
                size += pending[-1].size_bytes
                if self.goal == "require_single":
                    continue
                # whichever target trips first closes the batch (reference
                # GpuCoalesceIterator honors both GPU_BATCH_SIZE_BYTES and the
                # row cap). Padded bytes are real HBM occupancy, so the byte
                # target closes on the estimate; the row target needs exact
                # counts — a capacity-counted window of heavily-filtered
                # batches may hold far fewer rows than its buckets suggest,
                # and closing early would defeat the merge. Materializing is
                # ONE batched transfer for the whole window, not one sync per
                # batch.
                size_tripped = bool(target_bytes) and size >= target_bytes
                if not size_tripped and estimated and rows >= target:
                    rows = materialize_spillable_counts(pending)
                    estimated = False
                if size_tripped or rows >= target:
                    with concat_time.timed():
                        out = concat_spillables(pending)
                    # rebind BEFORE the yield: concat_spillables closed every
                    # staged input, and the unwind finally below must only
                    # ever see still-open ones
                    pending, rows, size, estimated = [], 0, 0, False
                    yield out
            if pending:
                with concat_time.timed():
                    out = concat_spillables(pending)
                pending = []
                yield out
        finally:
            # a cancel/shed/deadline trip (or any error) raised from the
            # child's next pull lands exactly while this window is staged —
            # the spillables registered above must not outlive the unwind
            # (close discipline; the serving shed soak caught this as a
            # per-shed SpillableColumnarBatch leak)
            for sp in pending:
                sp.close()


# ---------------------------------------------------------------------------
# plan pass: insert coalesce ahead of batch-hungry operators
# ---------------------------------------------------------------------------


def _batch_hungry_children(node: PhysicalPlan):
    """(child_index, goal) pairs this node wants coalesced inputs for."""
    from .aggregates import TpuHashAggregateExec
    from .fusion import TpuFusedSegmentExec
    from .joins import TpuShuffledHashJoinExec
    from .sort import TpuSortExec
    if isinstance(node, TpuShuffledHashJoinExec):
        # build side (right; the symmetric join may flip per partition, but
        # both sides are fully collected either way) wants ONE batch
        return [(0, "target"), (1, "require_single")]
    if isinstance(node, TpuFusedSegmentExec):
        # a segment that absorbed a join materializes each build child ONCE
        # per partition (the fused probe needs a single build batch)
        return [(0, "target")] + [(i, "require_single")
                                  for i in node.build_child_indices]
    if isinstance(node, (TpuHashAggregateExec, TpuSortExec)):
        return [(0, "target")]
    return []


def _already_coalesced(child: PhysicalPlan, exchanges_host_coalesced: bool) -> bool:
    """Children whose output is already target-sized: another coalesce, a
    device-cached scan (one resident batch per partition), a host→device
    transition (which coalesces its Arrow input itself), or — only in
    shuffle modes whose reduce read concatenates fetched blocks HOST-side
    before upload — an exchange/shuffle reader. The ICI reduce read yields
    one device batch per map block with no host concat, so its consumers
    still want a device-side coalesce."""
    from ..shuffle.aqe import TpuCoordinatedShuffleReaderExec
    from ..shuffle.exchange import _ExchangeBase, TpuShuffleReaderExec
    from .transitions import HostToDeviceExec, TpuDeviceScanExec
    if isinstance(child, (_ExchangeBase, TpuShuffleReaderExec,
                          TpuCoordinatedShuffleReaderExec)):
        return exchanges_host_coalesced
    return isinstance(child, (TpuCoalesceBatchesExec,
                              TpuDeviceScanExec, HostToDeviceExec))


def insert_coalesce(plan: PhysicalPlan, conf) -> PhysicalPlan:
    """Wrap batch-hungry operators' device inputs in TpuCoalesceBatchesExec
    (reference GpuTransitionOverrides inserting GpuCoalesceBatches per
    CoalesceGoal). Runs after the fusion pass so fused segments are targets
    too; compiled-stage fallback subtrees are rewritten through the same
    id-memo (they execute whenever a stage bails, and must see the same
    coalesced inputs — sharing the memo keeps exchanges shared between a
    stage's children and its fallback). No-op when
    spark.rapids.tpu.coalesce.enabled is off."""
    if not coalesce_enabled(conf):
        return plan
    from ..config import SHUFFLE_MODE
    exchanges_host_coalesced = str(conf.get(SHUFFLE_MODE)).upper() != "ICI"
    return _insert(plan, exchanges_host_coalesced, {})


def _insert(plan: PhysicalPlan, exchanges_host_coalesced: bool,
            memo: dict) -> PhysicalPlan:
    cached = memo.get(id(plan))
    if cached is not None:
        return cached
    new_children = [_insert(c, exchanges_host_coalesced, memo)
                    for c in plan.children]
    fb = getattr(plan, "fallback", None)
    new_fb = _insert(fb, exchanges_host_coalesced, memo) \
        if isinstance(fb, PhysicalPlan) else fb
    wants = dict(_batch_hungry_children(plan))
    wrapped = []
    for i, c in enumerate(new_children):
        goal = wants.get(i)
        if goal is not None and isinstance(c, TpuExec) \
                and not _already_coalesced(c, exchanges_host_coalesced):
            c = TpuCoalesceBatchesExec(c, goal=goal)
        wrapped.append(c)
    if all(a is b for a, b in zip(wrapped, plan.children)) \
            and new_fb is fb:
        memo[id(plan)] = plan
        return plan
    new = copy.copy(plan)
    new.children = wrapped
    if new_fb is not fb:
        new.fallback = new_fb
    memo[id(plan)] = new
    return new
