"""Cached-relation storage: per-batch parquet-compressed spillable entries.

Reference: ParquetCachedBatchSerializer.scala (1407 LoC) — df.cache() encodes
each batch to compressed parquet bytes; batches decode independently on
access, and cold entries can spill to local disk. This replaces the r1
whole-relation blob: a cached relation is now a list of CachedBatch entries,
each one parquet-encoded, individually decodable, and movable HOST→DISK
under a host-memory budget (the host tier of the spill story, SURVEY §5).
"""

from __future__ import annotations

import io
import os
import tempfile
import threading
from typing import Iterator, List, Optional

from ..expressions.base import AttributeReference
from ..plan.logical import LogicalPlan
from ..types import from_arrow


class CachedBatch:
    """One parquet-compressed batch. Blob lives in host memory until spilled
    to a local file; decode works from either tier."""

    def __init__(self, table, compression: str):
        import pyarrow.parquet as pq
        buf = io.BytesIO()
        pq.write_table(table, buf, compression=compression)
        self._blob: Optional[bytes] = buf.getvalue()
        self._path: Optional[str] = None
        self.num_rows = table.num_rows
        self.compressed_bytes = len(self._blob)

    @property
    def on_disk(self) -> bool:
        return self._path is not None

    def spill(self, directory: str) -> int:
        """Move the blob to disk; returns host bytes released."""
        if self._blob is None:
            return 0
        fd, path = tempfile.mkstemp(suffix=".parquet", dir=directory)
        with os.fdopen(fd, "wb") as f:
            f.write(self._blob)
        self._path = path
        released = len(self._blob)
        self._blob = None
        return released

    def table(self):
        import pyarrow.parquet as pq
        if self._blob is not None:
            return pq.read_table(io.BytesIO(self._blob))
        return pq.read_table(self._path)

    def close(self) -> None:
        self._blob = None
        if self._path is not None:
            try:
                os.unlink(self._path)
            except OSError:
                pass
            self._path = None


class CachedRelation(LogicalPlan):
    """In-memory parquet-compressed cache of a materialized result,
    chunked per batch."""

    def __init__(self, table, compression: str = "zstd",
                 batch_rows: Optional[int] = None,
                 host_limit_bytes: Optional[int] = None,
                 spill_dir: Optional[str] = None):
        from ..config import (CACHE_BATCH_ROWS, CACHE_HOST_LIMIT,
                              default_conf)
        conf = default_conf()
        rows = batch_rows or conf.get(CACHE_BATCH_ROWS)
        self._host_limit = (host_limit_bytes if host_limit_bytes is not None
                            else conf.get(CACHE_HOST_LIMIT))
        self._spill_dir = spill_dir or tempfile.gettempdir()
        self._lock = threading.Lock()
        self.batches: List[CachedBatch] = []
        for start in range(0, max(table.num_rows, 1), rows):
            self.batches.append(
                CachedBatch(table.slice(start, rows), compression))
        self.num_rows = table.num_rows
        self._output = [AttributeReference(f.name, from_arrow(f.type), True)
                        for f in table.schema]
        self._enforce_host_limit()

    def _enforce_host_limit(self) -> None:
        """Spill oldest in-memory batches until under the host budget
        (the reference's host-store eviction to disk)."""
        if self._host_limit <= 0:
            return
        with self._lock:
            host_bytes = sum(b.compressed_bytes for b in self.batches
                             if not b.on_disk)
            for b in self.batches:
                if host_bytes <= self._host_limit:
                    break
                if not b.on_disk:
                    host_bytes -= b.spill(self._spill_dir)

    @property
    def output(self) -> List[AttributeReference]:
        return self._output

    @property
    def compressed_bytes(self) -> int:
        return sum(b.compressed_bytes for b in self.batches)

    @property
    def host_bytes(self) -> int:
        return sum(b.compressed_bytes for b in self.batches if not b.on_disk)

    def iter_tables(self) -> Iterator:
        """Decode batch-by-batch — consumers never hold the whole relation
        decompressed (the per-batch contract of the reference serializer)."""
        for b in self.batches:
            yield b.table()

    def table(self):
        import pyarrow as pa
        return pa.concat_tables(list(self.iter_tables()))

    def unpersist(self) -> None:
        for b in self.batches:
            b.close()
        self.batches = []
        _invalidate_cached_plans_for(self)

    def node_desc(self) -> str:
        disk = sum(1 for b in self.batches if b.on_disk)
        return (f"CachedRelation[{self.num_rows} rows, "
                f"{len(self.batches)} batches, {self.compressed_bytes} bytes"
                + (f", {disk} on disk" if disk else "") + "]")


def _invalidate_cached_plans_for(relation) -> None:
    """Cached physical plans capture the relation's batches by reference;
    dropping the relation must drop those plans too or a hit would replay
    freed data."""
    from ..serving.scheduler import QueryScheduler
    inst = QueryScheduler.peek()
    if inst is not None:
        inst.plan_cache.invalidate_relation(id(relation))


def shard_over_chips(table, chips, batch_rows: int) -> List:
    """A mesh session's cached relation: chip r holds rows
    [r * q, (r + 1) * q) of `table`, q = ceil(rows / n), in batches of at
    most `batch_rows`, each committed to that chip alone. The list is
    laid out for the scan, one partition an entry: entry k * n + r is chip
    r's k-th batch (so partition p is on chip p % n), None where a chip
    has fewer batches than the fullest. Read chip by chip in partition
    order the rows come back in the table's order."""
    from ..columnar.batch import TpuColumnarBatch, batch_to_device
    n = len(chips)
    q = -(-table.num_rows // n)
    per_chip = []
    for r, chip in enumerate(chips):
        lo, hi = min(r * q, table.num_rows), min((r + 1) * q, table.num_rows)
        per_chip.append([
            batch_to_device(TpuColumnarBatch.from_arrow(
                table.slice(start, min(batch_rows, hi - start)),
                to_device=False), chip)
            for start in range(lo, hi, batch_rows)])
    depth = max(len(bs) for bs in per_chip)
    return [per_chip[r][k] if k < len(per_chip[r]) else None
            for k in range(depth) for r in range(n)]


class DeviceCachedRelation(LogicalPlan):
    """Device-resident cache: the materialized result is held as
    TpuColumnarBatch partitions in HBM (reference GpuInMemoryTableScanExec
    over the cache serializer). Repeated queries skip the host→device upload
    AND keep per-column memoized stats (group-by dictionaries/ranges), which
    is what lets the compiled aggregation stage hit its compile cache.
    One scan partition an entry of `batches`; an entry is None where a mesh
    session's layout has no batch (`shard_over_chips`)."""

    def __init__(self, batches: List, output):
        self._batches = list(batches)
        self._output = list(output)
        self.num_rows = sum(b.num_rows for b in batches if b is not None)

    @property
    def output(self) -> List[AttributeReference]:
        return self._output

    def batches(self) -> List:
        return self._batches

    def node_desc(self) -> str:
        held = sum(b is not None for b in self._batches)
        return f"DeviceCachedRelation[{self.num_rows} rows, {held} batches]"
