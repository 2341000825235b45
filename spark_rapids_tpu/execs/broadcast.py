"""Broadcast exchange + broadcast hash join.

Reference: GpuBroadcastExchangeExecBase (execution/GpuBroadcastExchangeExec.scala:352
— driver-side collect to host-serialized batches, Torrent broadcast) and
GpuBroadcastHashJoinExecBase (deserialize once per executor, build once, stream
probe side). Single-process analogue: the build side materializes ONCE
(memoized, like the broadcast relation future) and every stream partition
probes it — so the stream side keeps its partitioning, no exchange needed.

Spark's broadcast-side restrictions apply: BuildRight supports inner/cross/
left-outer/left-semi/left-anti; BuildLeft supports inner/cross/right-outer.
"""

from __future__ import annotations

import threading
from typing import Iterator, List, Optional, Sequence

from ..columnar.batch import TpuColumnarBatch, concat_batches
from ..expressions.base import AttributeReference, Expression
from ..obs import tracer as _obs
from .base import CpuExec, PhysicalPlan, TaskContext, TpuExec
from .joins import CpuShuffledHashJoinExec, TpuShuffledHashJoinExec

BROADCAST_RIGHT_TYPES = ("inner", "cross", "leftouter", "left", "leftsemi",
                         "semi", "leftanti", "anti")


class TpuBroadcastHashJoinExec(TpuShuffledHashJoinExec):
    """Equi-join with a broadcast (collected-once) build side = right."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan, join_type: str,
                 left_keys, right_keys, condition, output):
        super().__init__(left, right, join_type, left_keys, right_keys,
                         condition, output, per_partition=False)
        assert join_type in BROADCAST_RIGHT_TYPES, \
            f"broadcast-right does not support {join_type}"
        self._broadcast_lock = threading.Lock()
        self._broadcast_batch: Optional[TpuColumnarBatch] = None
        self._broadcast_done = False
        #: mesh session: the build's copy on each chip that probes it
        self._broadcast_on: dict = {}
        #: a fused probe's prepared form of the build (a copy's, by chip)
        self._broadcast_prepared: dict = {}

    def node_desc(self) -> str:
        return f"TpuBroadcastHashJoin[{self.join_type}]"

    def num_partitions(self) -> int:
        return self.children[0].num_partitions()

    def _build_side(self, ctx: TaskContext) -> Optional[TpuColumnarBatch]:
        with self._broadcast_lock:
            if not self._broadcast_done:
                # mesh session: collected on the mesh's first chip whichever
                # chip's task asks first, so that no program's chip hangs on
                # the order the chips arrive in (a new chip is a compile)
                from ..parallel.mesh import on_chip
                chips = ctx.chips
                with _obs.phase("join.collect"), \
                        on_chip(chips[0] if chips else None):
                    batches = []
                    child = self.children[1]
                    for p in range(child.num_partitions()):
                        batches.extend(child.execute_partition(p, ctx))
                    self._broadcast_batch = concat_batches(batches) \
                        if batches else None
                self._broadcast_done = True
            return self._on_probing_chip(self._broadcast_batch)

    def _on_probing_chip(self, build: Optional[TpuColumnarBatch]):
        """Mesh session: every partition needs the build, so it is the one
        operand replicated — collected on the mesh's first chip, a copy on
        each other chip that probes, made once (under the broadcast lock)
        and counted as
        `mesh.broadcast_bytes`, apart from `mesh.replicated_bytes`."""
        from ..parallel.mesh import current_chip
        here = current_chip()
        if build is None or here is None:
            return build
        got = self._broadcast_on.get(here)
        if got is None:
            if not build.columns or here in build.columns[0].data.devices():
                got = build
            else:
                from ..columnar.batch import batch_to_device
                got = batch_to_device(build, here)
                self.mesh_metric("meshBroadcastBytes").add(
                    got.device_memory_size())
            self._broadcast_on[here] = got
        return got

    def prepared_build(self, prepare):
        """What `prepare()` makes of the build (a fused segment's
        joins.PreparedBuild), once a query like the build itself — in a mesh
        session once for each chip's copy, by that chip's own task, so the
        program runs where its build lives."""
        from ..parallel.mesh import current_chip
        here = current_chip()
        with self._broadcast_lock:
            if here not in self._broadcast_prepared:
                self._broadcast_prepared[here] = prepare()
            return self._broadcast_prepared[here]

    def mesh_counters(self):
        out = super().mesh_counters()
        if "meshBroadcastBytes" in self.metrics:
            out.append(("mesh.broadcast_bytes",
                        self.metrics["meshBroadcastBytes"]))
        return out

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        right = self._build_side(ctx)
        names = [a.name for a in self._output]
        stream_batches = list(self.children[0].execute_partition(idx, ctx))
        if not stream_batches:
            return
        left = concat_batches(stream_batches)
        if left.num_rows == 0:
            return
        jt = self.join_type
        if right is None or right.num_rows == 0:
            if jt in ("inner", "cross", "leftsemi", "semi"):
                return
            if jt in ("leftanti", "anti"):
                yield left.rename(names)
                return
            from .joins import _all_null_cols
            nulls_r = _all_null_cols(self.children[1].output, left.num_rows,
                                     left.capacity)
            yield TpuColumnarBatch(left.columns + nulls_r, left.num_rows, names)
            return
        with self.metrics["joinTime"].timed(), _obs.phase("join.probe"):
            out = self._join(left, right, ctx)
        yield out


class CpuBroadcastHashJoinExec(CpuShuffledHashJoinExec):
    """CPU oracle counterpart; collect-based join is already the behavior."""

    def node_desc(self) -> str:
        return f"CpuBroadcastHashJoin[{self.join_type}]"


def estimated_size_bytes(plan) -> Optional[int]:
    """Static size estimate for broadcast decisions (reference: Spark stats +
    sized-build heuristics, GpuShuffledHashJoinExec sized-build)."""
    import os
    from ..execs.cpu import CpuLocalTableScanExec
    from ..io.parquet import CpuFileScanExec
    if isinstance(plan, CpuLocalTableScanExec):
        return plan.table.nbytes
    if isinstance(plan, CpuFileScanExec):
        try:
            return sum(os.path.getsize(p) for p in plan.paths) * 3  # decode blowup
        except OSError:
            return None
    if len(plan.children) == 1:
        return estimated_size_bytes(plan.children[0])
    return None
