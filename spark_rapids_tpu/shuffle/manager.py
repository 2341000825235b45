"""Multithreaded shuffle manager: thread-pool parallel write/read of shuffle
blocks on local storage.

Reference: RapidsShuffleInternalManagerBase.scala MULTITHREADED mode
(RapidsShuffleThreadedWriterBase:238, ...ReaderBase:569, BytesInFlightLimiter:529).
The ICI mode (device-resident exchange over the interconnect, UCX analogue)
lives in shuffle/ici.py and parallel/mesh.py and is selected via
spark.rapids.shuffle.mode.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterator, List, Optional

from ..config import (RapidsConf, SHUFFLE_CHECKSUM_ENABLED,
                      SHUFFLE_COMPRESSION_CODEC, SHUFFLE_READER_THREADS,
                      SHUFFLE_WRITER_THREADS, default_conf)
from .serializer import deserialize_table, get_codec, serialize_table


class BytesInFlightLimiter:
    """Caps bytes held by in-flight shuffle IO (reference
    RapidsShuffleInternalManagerBase.scala:529)."""

    def __init__(self, limit_bytes: int = 512 * 1024 * 1024):
        self._limit = limit_bytes
        self._in_flight = 0
        self._cv = threading.Condition()

    def acquire(self, n: int) -> None:
        with self._cv:
            while self._in_flight > 0 and self._in_flight + n > self._limit:
                self._cv.wait()
            self._in_flight += n

    def release(self, n: int) -> None:
        with self._cv:
            self._in_flight -= n
            self._cv.notify_all()


class TpuShuffleManager:
    """Per-process shuffle block store (Spark shuffle-files analogue)."""

    _instance: Optional["TpuShuffleManager"] = None
    _lock = threading.Lock()

    def __init__(self, conf: Optional[RapidsConf] = None):
        conf = conf or default_conf()
        self.root = tempfile.mkdtemp(prefix="tpu_shuffle_")
        self.codec_name = conf.get(SHUFFLE_COMPRESSION_CODEC)
        self.checksum = bool(conf.get(SHUFFLE_CHECKSUM_ENABLED))
        self._writers = ThreadPoolExecutor(
            max_workers=conf.get(SHUFFLE_WRITER_THREADS),
            thread_name_prefix="shuffle-writer")
        self._readers = ThreadPoolExecutor(
            max_workers=conf.get(SHUFFLE_READER_THREADS),
            thread_name_prefix="shuffle-reader")
        self._limiter = BytesInFlightLimiter()
        self._next_shuffle_id = 0
        self._id_lock = threading.Lock()
        # byte counters accumulate from writer/reader POOL threads — an
        # unguarded += loses updates under concurrency
        self._stats_lock = threading.Lock()
        self.bytes_written = 0
        self.bytes_read = 0

    @classmethod
    def get(cls, conf: Optional[RapidsConf] = None) -> "TpuShuffleManager":
        with cls._lock:
            if cls._instance is None:
                cls._instance = TpuShuffleManager(conf)
            return cls._instance

    def shutdown(self) -> None:
        """Stop the writer/reader pools and drop the block store. A
        replaced manager instance (tests swap `_instance`) must not keep
        its pool threads and spill directory alive until interpreter
        exit (TL020: the pools are owned resources)."""
        self._writers.shutdown(wait=True)
        self._readers.shutdown(wait=True)
        shutil.rmtree(self.root, ignore_errors=True)

    @classmethod
    def reset_for_tests(cls,
                        conf: Optional[RapidsConf] = None
                        ) -> "TpuShuffleManager":
        with cls._lock:
            old, cls._instance = cls._instance, None
        if old is not None:
            old.shutdown()
        return cls.get(conf)

    def new_shuffle_id(self) -> int:
        with self._id_lock:
            self._next_shuffle_id += 1
            return self._next_shuffle_id

    def _path(self, shuffle_id: int, map_id: int, reduce_id: int) -> str:
        d = os.path.join(self.root, f"shuffle_{shuffle_id}")
        os.makedirs(d, exist_ok=True)
        return os.path.join(d, f"map_{map_id}_reduce_{reduce_id}.block")

    def write_map_output(self, shuffle_id: int, map_id: int,
                         partition_tables: List) -> None:
        """Write one map task's per-reduce-partition tables in parallel.
        Each block lands via write-to-tmp + os.replace, so a crash mid-write
        can never leave a truncated file that `partition_sizes`'s existence
        check would count as a valid block."""
        from ..chaos import corrupt_bytes, inject

        def write_one(reduce_id: int, table) -> None:
            if table is None or table.num_rows == 0:
                return
            # codec per task: zstandard compressor objects are not safe under
            # concurrent use from multiple writer threads
            block = serialize_table(table, get_codec(self.codec_name),
                                    checksum=self.checksum)
            inject("shuffle.write", detail=f"{len(block)}B")
            # chaos corruption AFTER the checksum was embedded: the read
            # side must detect it and heal via lineage recompute
            block = corrupt_bytes("shuffle.write", block)
            self._limiter.acquire(len(block))
            path = self._path(shuffle_id, map_id, reduce_id)
            tmp = path + ".tmp"
            try:
                try:
                    with open(tmp, "wb") as f:
                        f.write(block)
                    os.replace(tmp, path)
                except BaseException:
                    if os.path.exists(tmp):
                        os.unlink(tmp)
                    raise
                with self._stats_lock:
                    self.bytes_written += len(block)
            finally:
                self._limiter.release(len(block))

        futures = [self._writers.submit(write_one, r, t)
                   for r, t in enumerate(partition_tables)]
        for f in futures:
            f.result()

    def iter_partition_sources(self, shuffle_id: int, reduce_id: int,
                               n_maps: int, map_ids=None) -> Iterator:
        """Streaming fetch of one reduce partition's blocks as
        (map_id, table-or-None) pairs in map order: every map's
        read+deserialize is submitted to the reader pool up front — the
        consumer can upload block m while blocks m+1.. are still being read
        (reference RapidsShuffleThreadedReaderBase). `map_ids` restricts to
        a subset of maps (AQE skew slices). None means the map wrote no
        block for this partition (legitimately empty). A corrupted or
        truncated block — or any other deserialization failure — raises
        FetchFailedError naming the producing map so the exchange can
        re-materialize it (SPARK-35275 checksum semantics)."""
        from ..chaos import corrupt_bytes, inject
        from .ici import FetchFailedError

        def read_one(map_id: int):
            p = self._path(shuffle_id, map_id, reduce_id)
            if not os.path.exists(p):
                return None
            try:
                inject("shuffle.read", detail=f"map{map_id}")
                with open(p, "rb") as f:
                    block = f.read()
                block = corrupt_bytes("shuffle.read", block)
                table = deserialize_table(block)
            except Exception as exc:  # noqa: BLE001 — any decode failure is
                # a lost/corrupt block; lineage recompute heals it
                raise FetchFailedError(shuffle_id, [map_id]) from exc
            with self._stats_lock:
                self.bytes_read += len(block)
            return table

        maps = list(range(n_maps)) if map_ids is None else list(map_ids)
        futures = [self._readers.submit(read_one, m) for m in maps]
        for m, f in zip(maps, futures):
            yield m, f.result()

    def iter_partition(self, shuffle_id: int, reduce_id: int,
                       n_maps: int, map_ids=None) -> Iterator:
        """iter_partition_sources without the map ids: yields just the
        non-empty tables in map order."""
        for _, t in self.iter_partition_sources(shuffle_id, reduce_id,
                                                n_maps, map_ids):
            if t is not None:
                yield t

    def read_partition(self, shuffle_id: int, reduce_id: int,
                       n_maps: int, map_ids=None) -> List:
        """Fetch one reduce partition's blocks from all maps in parallel."""
        return list(self.iter_partition(shuffle_id, reduce_id, n_maps,
                                        map_ids))

    def cleanup(self, shuffle_id: int) -> None:
        shutil.rmtree(os.path.join(self.root, f"shuffle_{shuffle_id}"),
                      ignore_errors=True)
