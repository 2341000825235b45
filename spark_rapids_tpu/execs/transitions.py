"""CPU↔TPU transition operators.

Reference: GpuRowToColumnarExec / GpuColumnarToRowExec / HostColumnarToGpu
(/root/reference/sql-plugin/.../GpuColumnarToRowExec.scala:129,
HostColumnarToGpu.scala). Our host substrate is already columnar (Arrow), so the
transitions are H→D upload and D→H download of Arrow batches; the row↔columnar
leg of the reference collapses away.
"""

from __future__ import annotations

from typing import Iterator, List

from ..columnar.batch import TpuColumnarBatch
from .base import CpuExec, PhysicalPlan, TaskContext, TpuExec


class HostToDeviceExec(TpuExec):
    """Upload host Arrow batches to device columns (reference GpuRowToColumnarExec
    + HostColumnarToGpu). With spark.rapids.tpu.coalesce.enabled, small host
    tables concatenate up to the batch-size targets BEFORE the upload
    (host-side coalescing, reference GpuShuffleCoalesceExec applied at the
    transition): one H→D transfer and one downstream dispatch chain per
    target-sized batch instead of one per source table."""

    def __init__(self, child: PhysicalPlan):
        super().__init__([child])

    @property
    def output(self):
        return self.children[0].output

    def additional_metrics(self):
        return {"uploadTime": "MODERATE", "numInputBatches": "DEBUG"}

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        from .coalesce import (coalesce_arrow_stream, coalesce_enabled,
                               coalesce_targets)
        names = [a.name for a in self.output]
        with_time = self.metrics["uploadTime"]
        n_in = self.metrics["numInputBatches"]

        def counted():
            for t in self.children[0].execute_partition(idx, ctx):
                n_in.add(1)
                yield t

        tables = counted()
        if coalesce_enabled(ctx.conf):
            target_rows, target_bytes = coalesce_targets(ctx.conf)
            tables = coalesce_arrow_stream(tables, target_rows, target_bytes)
        for t in tables:
            with with_time.timed():
                b = TpuColumnarBatch.from_arrow(t)
            yield b.rename(names)


class CpuDeviceScanExec(CpuExec):
    """CPU view of a device-cached relation (downloads per batch); converts
    to TpuDeviceScanExec under the override engine — the reference's
    InMemoryTableScan over the cached-batch serializer."""

    def __init__(self, batches, output):
        super().__init__([])
        self.batches = list(batches)
        self._output = list(output)

    @property
    def output(self):
        return self._output

    def num_partitions(self) -> int:
        return max(1, len(self.batches))

    def node_desc(self) -> str:
        held = sum(b is not None for b in self.batches)
        return f"CpuDeviceScan[{held} batches]"

    def execute_partition(self, idx: int, ctx: TaskContext) -> Iterator:
        if idx < len(self.batches) and self.batches[idx] is not None:
            yield self.batches[idx].to_arrow()


class TpuDeviceScanExec(TpuExec):
    """Serve device-resident cached batches with zero upload cost; column
    objects are stable across runs, so memoized per-column statistics
    (group-by dictionaries/ranges) survive between queries."""

    def __init__(self, batches, output):
        super().__init__([])
        self.batches = list(batches)
        self._output = list(output)

    @property
    def output(self):
        return self._output

    def num_partitions(self) -> int:
        return max(1, len(self.batches))

    def node_desc(self) -> str:
        held = [b for b in self.batches if b is not None]
        rows = sum(b.num_rows for b in held)
        return f"TpuDeviceScan[{len(held)} batches, {rows} rows]"

    def mesh_counters(self):
        return super().mesh_counters() + self.chip_rows_counters()

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        names = [a.name for a in self._output]
        if idx < len(self.batches) and self.batches[idx] is not None:
            batch = self.batches[idx]
            if ctx.chips is not None:
                from ..parallel.mesh import replicated_bytes
                self.mesh_metric("meshReplicatedBytes").add(
                    replicated_bytes(batch))
            yield batch.rename(names)


class DeviceToHostExec(CpuExec):
    """Download device batches to host Arrow (reference GpuColumnarToRowExec)."""

    def __init__(self, child: PhysicalPlan):
        super().__init__([child])

    @property
    def output(self):
        return self.children[0].output

    def additional_metrics(self):
        return {"downloadTime": "MODERATE"}

    def execute_partition(self, idx: int, ctx: TaskContext) -> Iterator:
        from .. import profiling
        with_time = self.metrics["downloadTime"]
        name = self.node_name()
        for b in self.children[0].execute_partition(idx, ctx):
            # the result download is THE boundary sync of the chain (a
            # deferred row count rides it); attribute it in the ledger
            with with_time.timed(), profiling.sync_scope(name):
                t = b.to_arrow()
            yield t

    def execute_partitions(self, ids, ctx_of) -> Iterator:
        """Grouped root pull (mesh sessions): forward the whole partition
        group to the device child in ONE multi-partition pull, so a fused
        top stage runs every chip's partition in a single grouped launch
        (spark.rapids.tpu.dispatch.partitionBatch) instead of one launch
        per partition. Emission order matches the per-partition path."""
        from .. import profiling
        with_time = self.metrics["downloadTime"]
        name = self.node_name()
        for i, b in self.children[0].execute_partitions(ids, ctx_of):
            with with_time.timed(), profiling.sync_scope(name):
                t = b.to_arrow()
            yield i, t
