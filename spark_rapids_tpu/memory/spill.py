"""Spill framework: tiered buffer catalog DEVICE → HOST → DISK.

Reference: RapidsBufferCatalog.scala (1018; handle-based), RapidsBufferStore /
RapidsDeviceMemoryStore / RapidsHostMemoryStore / RapidsDiskStore,
SpillPriorities.scala, SpillableColumnarBatch.scala:29,90. Device batches
register for a handle; under HBM pressure the catalog spills lowest-priority
buffers to host Arrow tables, then to Arrow IPC files on disk; `get_batch`
unspills on demand. jax.Arrays are immutable so "spill" = materialize to host
and drop the device reference (XLA frees it), accounting via HbmBudget.
"""

from __future__ import annotations

import os
import tempfile
import threading
from typing import Dict, List, Optional

from ..columnar.batch import TpuColumnarBatch
from ..config import HOST_SPILL_STORAGE_SIZE, RapidsConf, default_conf
from .hbm import HbmBudget

TIER_DEVICE = "DEVICE"
TIER_HOST = "HOST"
TIER_DISK = "DISK"


class SpillCorruptionError(IOError):
    """A disk-spilled buffer failed its integrity check on unspill (bit rot,
    truncation, or chaos-injected corruption). For ICI shuffle blocks the
    catalog converts this into FetchFailedError so lineage recompute heals
    it; anywhere else it surfaces as the storage fault it is."""

# Spill priorities (reference SpillPriorities.scala): lower value spills first
ACTIVE_ON_DECK_PRIORITY = -100
ACTIVE_BATCHING_PRIORITY = 0
OUTPUT_FOR_SHUFFLE_PRIORITY = 100


class _Entry:
    __slots__ = ("handle", "tier", "priority", "batch", "host_table",
                 "disk_path", "disk_checksum", "nbytes", "names")

    def __init__(self, handle: int, batch: TpuColumnarBatch, priority: int):
        self.handle = handle
        self.tier = TIER_DEVICE
        self.priority = priority
        self.batch = batch
        self.host_table = None
        self.disk_path: Optional[str] = None
        self.disk_checksum: Optional[int] = None
        self.nbytes = batch.device_memory_size()
        self.names = batch.names


class TpuBufferCatalog:
    """Handle-based spillable-buffer registry (reference RapidsBufferCatalog)."""

    _instance: Optional["TpuBufferCatalog"] = None
    _lock = threading.Lock()

    def __init__(self, conf: Optional[RapidsConf] = None):
        conf = conf or default_conf()
        self._entries: Dict[int, _Entry] = {}
        self._next_handle = 0
        self._reg_lock = threading.RLock()
        self._disk_dir = tempfile.mkdtemp(prefix="tpu_spill_")
        self.host_limit = conf.get(HOST_SPILL_STORAGE_SIZE)
        self.host_used = 0
        self.spilled_to_host = 0
        self.spilled_to_disk = 0
        HbmBudget.get(conf).set_spill_callback(self.synchronous_spill)

    @classmethod
    def get(cls, conf: Optional[RapidsConf] = None) -> "TpuBufferCatalog":
        with cls._lock:
            if cls._instance is None:
                cls._instance = TpuBufferCatalog(conf)
            return cls._instance

    @classmethod
    def reset_for_tests(cls) -> "TpuBufferCatalog":
        with cls._lock:
            cls._instance = TpuBufferCatalog()
            return cls._instance

    # --- registration ------------------------------------------------------
    def add_batch(self, batch: TpuColumnarBatch,
                  priority: int = ACTIVE_BATCHING_PRIORITY) -> int:
        with self._reg_lock:
            self._next_handle += 1
            h = self._next_handle
            e = _Entry(h, batch, priority)
            self._entries[h] = e
            HbmBudget.get().allocate(e.nbytes)
            return h

    def remove(self, handle: int) -> None:
        with self._reg_lock:
            e = self._entries.pop(handle, None)
            if e is None:
                return
            if e.tier == TIER_DEVICE:
                HbmBudget.get().free(e.nbytes)
            elif e.tier == TIER_HOST:
                self.host_used -= e.nbytes
            elif e.disk_path and os.path.exists(e.disk_path):
                os.unlink(e.disk_path)

    # --- access ------------------------------------------------------------
    def get_batch(self, handle: int) -> TpuColumnarBatch:
        with self._reg_lock:
            e = self._entries[handle]
            if e.tier == TIER_DEVICE:
                return e.batch
            self._unspill(e)
            return e.batch

    def _unspill(self, e: _Entry) -> None:
        import pyarrow as pa
        import time as _time
        from ..obs import tracer as _obs
        from ..profiling import TaskMetricsRegistry
        t0 = _time.perf_counter_ns()
        self._unspill_inner(e, pa)
        dt = _time.perf_counter_ns() - t0
        TaskMetricsRegistry.get().add("readSpillTimeNs", dt)
        from ..obs import metrics as _metrics
        _metrics.counter_inc("spill.read_bytes", e.nbytes)
        if _obs._ACTIVE:
            _obs.event("spill.read", cat="memory", bytes=e.nbytes,
                       wait_ns=dt)

    def _unspill_inner(self, e: _Entry, pa) -> None:
        if e.tier == TIER_DISK:
            import io
            from ..shuffle.serializer import xxhash64_bytes
            with open(e.disk_path, "rb") as f:
                data = f.read()
            if e.disk_checksum is not None \
                    and xxhash64_bytes(data) != e.disk_checksum:
                raise SpillCorruptionError(
                    f"spill file {e.disk_path} failed its xxhash64 "
                    f"integrity check on unspill ({len(data)} bytes)")
            with pa.ipc.open_file(io.BytesIO(data)) as r:
                e.host_table = r.read_all()
            os.unlink(e.disk_path)
            e.disk_path = None
            e.disk_checksum = None
            e.tier = TIER_HOST
            self.host_used += e.nbytes
        if e.tier == TIER_HOST:
            HbmBudget.get().allocate(e.nbytes)
            batch = TpuColumnarBatch.from_arrow(e.host_table)
            if e.names:
                batch = batch.rename(e.names)
            e.batch = batch
            e.host_table = None
            self.host_used -= e.nbytes
            e.tier = TIER_DEVICE

    # --- spilling ----------------------------------------------------------
    def synchronous_spill(self, bytes_needed: int) -> int:
        """Spill lowest-priority device buffers until bytes_needed freed
        (reference: RMM alloc-failure drains the device store)."""
        freed = 0
        with self._reg_lock:
            device_entries = sorted(
                (e for e in self._entries.values() if e.tier == TIER_DEVICE),
                key=lambda e: e.priority)
            for e in device_entries:
                if freed >= bytes_needed:
                    break
                freed += self._spill_entry_to_host(e)
        return freed

    def _spill_entry_to_host(self, e: _Entry) -> int:
        from ..chaos import inject
        from ..obs import tracer as _obs
        inject("spill.to_host")  # before any state mutation: a raised fault
        # must leave the entry intact on its current tier
        from ..obs import metrics as _metrics
        _metrics.counter_inc("spill.to_host_bytes", e.nbytes)
        if _obs._ACTIVE:
            _obs.event("spill.to_host", cat="memory", bytes=e.nbytes)
        e.host_table = e.batch.to_arrow()
        e.batch = None
        e.tier = TIER_HOST
        HbmBudget.get().free(e.nbytes)
        self.host_used += e.nbytes
        self.spilled_to_host += e.nbytes
        from ..profiling import TaskMetricsRegistry
        TaskMetricsRegistry.get().add("spillToHostBytes", e.nbytes)
        if self.host_used > self.host_limit:
            self._spill_host_to_disk()
        return e.nbytes

    def _spill_host_to_disk(self) -> None:
        import pyarrow as pa
        with self._reg_lock:
            host_entries = sorted(
                (e for e in self._entries.values() if e.tier == TIER_HOST),
                key=lambda e: e.priority)
            for e in host_entries:
                if self.host_used <= self.host_limit:
                    break
                import io
                from ..chaos import corrupt_bytes, inject
                from ..shuffle.serializer import xxhash64_bytes
                inject("spill.to_disk")  # pre-mutation, like spill.to_host
                from ..obs import flight as _flight
                from ..obs import metrics as _metrics
                from ..obs import tracer as _obs
                _metrics.counter_inc("spill.to_disk_bytes", e.nbytes)
                # disk spill is rare and a pressure signal: flight-note it
                _flight.note("spill.to_disk", bytes=e.nbytes)
                if _obs._ACTIVE:
                    _obs.event("spill.to_disk", cat="memory",
                               bytes=e.nbytes)
                path = os.path.join(self._disk_dir, f"buf_{e.handle}.arrow")
                buf = io.BytesIO()
                with pa.ipc.new_file(buf, e.host_table.schema) as w:
                    w.write_table(e.host_table)
                data = buf.getvalue()
                # checksum BEFORE the chaos mangle: injected corruption must
                # be detectable on unspill, exactly like real bit rot
                e.disk_checksum = xxhash64_bytes(data)
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(corrupt_bytes("spill.to_disk", data))
                os.replace(tmp, path)  # atomic: no truncated spill files
                e.host_table = None
                e.disk_path = path
                e.tier = TIER_DISK
                self.host_used -= e.nbytes
                self.spilled_to_disk += e.nbytes
                from ..profiling import TaskMetricsRegistry
                TaskMetricsRegistry.get().add("spillToDiskBytes", e.nbytes)


class SpillableColumnarBatch:
    """RAII wrapper: batch registered in the catalog, retrievable, closable
    (reference SpillableColumnarBatch.scala)."""

    def __init__(self, batch: TpuColumnarBatch,
                 priority: int = ACTIVE_BATCHING_PRIORITY):
        from .cleaner import MemoryCleaner
        self._catalog = TpuBufferCatalog.get()
        self._handle: Optional[int] = self._catalog.add_batch(batch, priority)
        # a deferred-compaction batch's row count stays a device scalar here:
        # wrapping a batch must not force the sync its producer deferred
        self._rows_lazy = batch.rows_lazy
        self.size_bytes = batch.device_memory_size()
        rows_label = self._rows_lazy if isinstance(self._rows_lazy, int) \
            else "?"
        # pin the cleaner INSTANCE: close() must unregister from the same
        # book we registered in, or a reset_for_tests between creation and
        # close (long-lived caches, shutdown hooks) strands the token in the
        # old instance — a phantom "leak" its atexit report shows while the
        # CI gate, checking the current instance, passes
        self._cleaner = MemoryCleaner.get()
        self._cleaner_token = self._cleaner.register(
            f"SpillableColumnarBatch[{rows_label}r "
            f"{self.size_bytes}B]")

    @property
    def num_rows(self) -> int:
        if not isinstance(self._rows_lazy, int):
            from ..columnar.vector import audited_sync_int
            self._rows_lazy = audited_sync_int(self._rows_lazy, "rows")
        return self._rows_lazy

    @property
    def rows_lazy(self):
        """Row count WITHOUT forcing: host int when known, device scalar
        otherwise (see materialize_spillable_counts for the batched force)."""
        return self._rows_lazy

    def get_batch(self) -> TpuColumnarBatch:
        if self._handle is None:
            raise ValueError("spillable batch already closed")
        return self._catalog.get_batch(self._handle)

    def close(self) -> None:
        if self._handle is not None:
            self._catalog.remove(self._handle)
            self._handle = None
        # second unregister of the same token IS the double-close signal
        # (raises in the cleaner's debug mode, counted otherwise)
        self._cleaner.unregister(self._cleaner_token)

    def __enter__(self) -> "SpillableColumnarBatch":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def materialize_spillable_counts(spillables: List[SpillableColumnarBatch]) -> int:
    """Force every pending deferred row count in the list with ONE batched
    transfer and return the exact total. A coalesce window deciding whether
    its row target really tripped pays one sync for the whole window, not
    one per batch."""
    import numpy as np
    dev_ix = [i for i, sp in enumerate(spillables)
              if not isinstance(sp._rows_lazy, (int, np.integer))]
    if dev_ix:
        from ..columnar.vector import audited_device_get
        got = audited_device_get([spillables[i]._rows_lazy for i in dev_ix],
                                 "rows")
        for i, n in zip(dev_ix, got):
            spillables[i]._rows_lazy = int(n)
    return sum(int(sp._rows_lazy) for sp in spillables)
