"""TPU sort exec.

Reference: GpuSortExec.scala (in-core sort:86; out-of-core GpuOutOfCoreSortIterator:281).
Device algorithm: order-preserving integer encoding per key (float bit tricks,
host dense-rank for strings) + iterated stable argsort (LSD style) + one gather.
Out-of-core spill-merge arrives with the memory runtime.
"""

from __future__ import annotations

from typing import Iterator, List

import jax.numpy as jnp
import numpy as np

from ..columnar.batch import TpuColumnarBatch, concat_batches, gather
from ..columnar.vector import TpuColumnVector
from ..expressions.base import to_column
from ..obs import tracer as _obs
from ..plan.logical import SortOrder
from ..types import StringType
from .aggregates import _sortable_bits, lex_sort_permutation
from .base import PhysicalPlan, TaskContext, TpuExec, bind_references


def encode_sort_keys(cols: List[TpuColumnVector], num_rows: int, capacity: int):
    """(sortable_int_values, validity) per key; strings get order-preserving
    dense ranks computed host-side (priced as host-assisted)."""
    out = []
    for c in cols:
        if isinstance(c.dtype, StringType):
            import pyarrow as pa
            import pyarrow.compute as pc
            arr = c.to_arrow()
            # arrow ≥25 wants null_placement per sort key; older arrows
            # only accept an order string plus the kwarg
            try:
                ranks = pc.rank(arr, sort_keys=[("", "ascending", "at_end")],
                                tiebreaker="dense")
            except (ValueError, TypeError):
                ranks = pc.rank(arr, sort_keys="ascending",
                                null_placement="at_end", tiebreaker="dense")
            vals = np.asarray(ranks.to_numpy(zero_copy_only=False)).astype(np.int64)
            buf = np.zeros(capacity, np.int64)
            buf[:num_rows] = vals
            out.append((jnp.asarray(buf), c.validity))
        else:
            out.append((_sortable_bits(c), c.validity))
    return out


def sort_batch(batch: TpuColumnarBatch, order: List[SortOrder],
               ctx: TaskContext) -> TpuColumnarBatch:
    cap = batch.capacity
    n = batch.num_rows
    key_cols = [to_column(o.child.eval_tpu(batch, ctx.eval_ctx), batch, o.child.dtype)
                for o in order]
    enc = encode_sort_keys(key_cols, n, cap)
    orders = [(o.ascending, o.nulls_first) for o in order]
    perm = lex_sort_permutation(enc, n, cap, orders)
    return gather(batch, perm, n, out_capacity=cap)


class TpuTopNExec(TpuExec):
    """Top-N: per-partition sort+slice with a running top-N, then one final
    merge — avoids the global sort exchange (reference GpuTopN, limit.scala:
    sort+slice fusion of TakeOrderedAndProject)."""

    def __init__(self, n: int, order: List[SortOrder], child: PhysicalPlan,
                 offset: int = 0):
        super().__init__([child])
        self.n = n
        self.offset = offset
        self.order = [SortOrder(bind_references(o.child, child.output),
                                o.ascending, o.nulls_first) for o in order]

    @property
    def output(self):
        return self.children[0].output

    def num_partitions(self) -> int:
        return 1

    def node_desc(self) -> str:
        keys = ", ".join(o.pretty() for o in self.order)
        return f"TpuTopN[n={self.n}, {keys}]"

    def additional_metrics(self):
        return {"sortTime": "MODERATE"}

    def _topn_of_partition(self, p: int, ctx: TaskContext, keep: int, laps):
        from ..columnar.batch import slice_batch
        running = None
        for b in self.children[0].execute_partition(p, ctx):
            with laps.lap("sort.topn"):
                cand = b if running is None else concat_batches([running, b])
                with self.metrics["sortTime"].timed():
                    s = sort_batch(cand, self.order, ctx)
                running = slice_batch(s, 0, min(keep, s.num_rows))
        return running

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        from ..columnar.batch import slice_batch
        keep = self.offset + self.n
        tops = []
        # phase `sort.topn`: one lap a sort + slice (a batch into the running
        # top-N, then the merge); the child's pull lies outside it
        laps = _obs.PhaseLaps()
        try:
            for p in range(self.children[0].num_partitions()):
                t = self._topn_of_partition(p, ctx, keep, laps)
                if t is not None:
                    tops.append(t)
            if tops:
                with laps.lap("sort.topn"):
                    whole = concat_batches(tops)
                    with self.metrics["sortTime"].timed():
                        s = sort_batch(whole, self.order, ctx)
                    out = slice_batch(s, self.offset, self.n)
        finally:
            laps.flush()
        if tops and out.num_rows:
            yield out


class TpuSortExec(TpuExec):
    def __init__(self, order: List[SortOrder], global_sort: bool,
                 child: PhysicalPlan):
        super().__init__([child])
        self.order = [SortOrder(bind_references(o.child, child.output), o.ascending,
                                o.nulls_first) for o in order]
        self.global_sort = global_sort

    @property
    def output(self):
        return self.children[0].output

    def num_partitions(self) -> int:
        return 1 if self.global_sort else self.children[0].num_partitions()

    def node_desc(self) -> str:
        return f"TpuSort[{', '.join(o.pretty() for o in self.order)}]"

    def additional_metrics(self):
        return {"sortTime": "MODERATE"}

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        from ..config import BATCH_SIZE_ROWS
        child = self.children[0]
        if self.global_sort:
            max_rows = ctx.conf.get(BATCH_SIZE_ROWS)
            batches: List[TpuColumnarBatch] = []
            total = 0
            ooc = None
            # the sorter owns spillable runs from its very first add_batch:
            # a failure while LATER batches stream in (device error, chaos
            # spill fault) must still close the parked runs, so the whole
            # ingest+emit window sits under one finally (TL020)
            try:
                for p in range(child.num_partitions()):
                    for b in child.execute_partition(p, ctx):
                        total += b.num_rows
                        if ooc is not None:
                            ooc.add_batch(b)
                            continue
                        batches.append(b)
                        if total > max_rows:
                            # input exceeds one device batch → out-of-core
                            # path (reference GpuOutOfCoreSortIterator)
                            from .oocsort import OutOfCoreSorter
                            ooc = OutOfCoreSorter(self.order, ctx)
                            with self.metrics["sortTime"].timed():
                                for queued in batches:
                                    ooc.add_batch(queued)
                            batches = []
                if ooc is not None:
                    with self.metrics["sortTime"].timed():
                        yield from ooc.iter_sorted(max_rows)
                    return
            finally:
                if ooc is not None:
                    ooc.close()
            if not batches:
                return
            whole = concat_batches(batches)
            with self.metrics["sortTime"].timed():
                yield sort_batch(whole, self.order, ctx)
        else:
            for b in child.execute_partition(idx, ctx):
                with self.metrics["sortTime"].timed():
                    yield sort_batch(b, self.order, ctx)
