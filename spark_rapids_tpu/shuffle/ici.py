"""ICI shuffle mode: device-resident shuffle catalog + peer heartbeat.

Reference mapping (SURVEY.md §2.7): the UCX mode keeps shuffle blocks
device-resident in a ShuffleBufferCatalog served peer-to-peer over
RDMA/NVLink (RapidsShuffleServer/Client, BufferSendState/BufferReceiveState),
with a driver-coordinated heartbeat discovering peers
(RapidsShuffleHeartbeatManager, Plugin.scala:436-447).

TPU re-design: within one mesh/slice the data plane is XLA's `all_to_all`
over ICI (parallel/mesh.py's collective exchange — the compiler
schedules the interconnect transfers, replacing hand-written UCX
transactions). At the exec layer, ICI mode keeps every shuffle block as a
*spillable device batch* in this catalog — no Arrow serialization, no disk
round trip; reduce tasks concat blocks directly on device (≙ the reference's
RapidsCachingWriter/RapidsCachingReader pair). Blocks are spillable, so HBM
pressure pushes them down the usual HBM→host→disk tiers instead of OOMing.
The heartbeat registry tracks peer liveness; a lost peer invalidates its map
outputs so the exchange re-materializes them (Spark would re-run the map
stage)."""

from __future__ import annotations

import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

from ..columnar.batch import TpuColumnarBatch
from ..memory.spill import SpillableColumnarBatch


class ShuffleHeartbeatManager:
    """Driver-side peer registry (reference RapidsShuffleHeartbeatManager):
    executors announce themselves and heartbeat; peers missing beyond the
    timeout are reported lost exactly once."""

    _instance: Optional["ShuffleHeartbeatManager"] = None
    _lock = threading.Lock()

    def __init__(self, timeout_s: float = 30.0):
        self.timeout_s = timeout_s
        self._peers: Dict[str, float] = {}
        self._registered_order: List[str] = []
        self._mu = threading.Lock()

    @classmethod
    def get(cls) -> "ShuffleHeartbeatManager":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def reset_for_tests(cls) -> "ShuffleHeartbeatManager":
        with cls._lock:
            cls._instance = cls()
            return cls._instance

    def register_peer(self, executor_id: str,
                      now: Optional[float] = None) -> List[str]:
        """Returns the already-known peers (RapidsExecutorStartupMsg reply)."""
        with self._mu:
            known = list(self._registered_order)
            if executor_id not in self._peers:
                self._registered_order.append(executor_id)
            self._peers[executor_id] = now if now is not None else time.time()
            return known

    def heartbeat(self, executor_id: str,
                  now: Optional[float] = None) -> None:
        with self._mu:
            if executor_id in self._peers:
                self._peers[executor_id] = now if now is not None \
                    else time.time()

    def lost_peers(self, now: Optional[float] = None) -> List[str]:
        t = now if now is not None else time.time()
        with self._mu:
            lost = [e for e, last in self._peers.items()
                    if t - last > self.timeout_s]
            for e in lost:
                del self._peers[e]
                self._registered_order.remove(e)
            return lost

    def peers(self) -> List[str]:
        with self._mu:
            return list(self._registered_order)


class FetchFailedError(RuntimeError):
    """A map output is missing (peer lost / invalidated) — the exchange must
    re-materialize those map tasks (Spark: FetchFailed → stage retry)."""

    def __init__(self, shuffle_id: int, map_ids: List[int]):
        super().__init__(f"shuffle {shuffle_id}: missing map output for "
                         f"maps {map_ids}")
        self.shuffle_id = shuffle_id
        self.map_ids = map_ids


class IciShuffleCatalog:
    """Device-resident shuffle block store (reference ShuffleBufferCatalog +
    ShuffleReceivedBufferCatalog): (shuffle_id, map_id, reduce_id) →
    spillable device batch. Map completion is tracked separately so a
    missing block distinguishes 'legitimately empty partition' from
    'lost/invalidated output' (the latter raises FetchFailedError)."""

    _instance: Optional["IciShuffleCatalog"] = None
    _lock = threading.Lock()

    def __init__(self):
        self._blocks: Dict[Tuple[int, int, int], SpillableColumnarBatch] = {}
        self._owner: Dict[Tuple[int, int], str] = {}  # (sid, map_id) → exec
        self._complete: set = set()  # (sid, map_id) with committed output
        self._mu = threading.Lock()

    @classmethod
    def get(cls) -> "IciShuffleCatalog":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
                import atexit
                atexit.register(cls._shutdown_instance)
            return cls._instance

    @classmethod
    def _shutdown_instance(cls) -> None:
        # close-discipline: catalog-held blocks are owned state, released
        # at shutdown so the MemoryCleaner report only shows real leaks
        inst = cls._instance
        if inst is not None:
            inst.close_all()

    @classmethod
    def reset_for_tests(cls) -> "IciShuffleCatalog":
        with cls._lock:
            if cls._instance is not None:
                cls._instance.close_all()
            cls._instance = cls()
            return cls._instance

    def close_all(self) -> None:
        with self._mu:
            closed = list(self._blocks.values())
            self._blocks.clear()
            self._owner.clear()
            self._complete = set()
        for sb in closed:
            sb.close()

    def put_block(self, shuffle_id: int, map_id: int, reduce_id: int,
                  batch: TpuColumnarBatch,
                  owner: Optional[str] = None) -> None:
        from ..memory.spill import OUTPUT_FOR_SHUFFLE_PRIORITY
        sb = SpillableColumnarBatch(batch,
                                    priority=OUTPUT_FOR_SHUFFLE_PRIORITY)
        with self._mu:
            key = (shuffle_id, map_id, reduce_id)
            old = self._blocks.pop(key, None)
            self._blocks[key] = sb
            if owner is not None:
                self._owner[(shuffle_id, map_id)] = owner
        if old is not None:
            old.close()

    def mark_map_complete(self, shuffle_id: int, map_id: int) -> None:
        with self._mu:
            self._complete.add((shuffle_id, map_id))

    def iter_blocks(self, shuffle_id: int, reduce_id: int,
                    n_maps: int, map_ids=None) -> Iterator[TpuColumnarBatch]:
        """Raises FetchFailedError when any map's output was invalidated —
        including a block whose disk-spilled bytes fail their integrity
        check on unspill (the catalog drops that map's output so the
        exchange re-runs it, instead of surfacing a storage error).
        `map_ids` restricts to a subset of maps (AQE skew slices)."""
        from ..chaos import inject
        from ..memory.spill import SpillCorruptionError
        inject("ici.fetch", detail=f"s{shuffle_id}r{reduce_id}")
        with self._mu:
            missing = [m for m in range(n_maps)
                       if (shuffle_id, m) not in self._complete]
        if missing:
            raise FetchFailedError(shuffle_id, missing)
        for map_id in (range(n_maps) if map_ids is None else map_ids):
            with self._mu:
                sb = self._blocks.get((shuffle_id, map_id, reduce_id))
                if sb is None and (shuffle_id, map_id) not in self._complete:
                    # invalidated since the up-front completeness check (a
                    # concurrent reduce task hit corruption / a peer was
                    # lost): silently skipping would DROP this map's rows
                    raise FetchFailedError(shuffle_id, [map_id])
            try:
                # fetch OUTSIDE the catalog lock: get_batch can unspill
                # (disk read + HBM allocation) and holding _mu across it
                # both stalls every concurrent put and inverts the
                # declared lock order (TL022: _mu is a leaf below the
                # spill catalog's _reg_lock). A concurrent invalidate/
                # cleanup closing the spillable after we released _mu
                # surfaces as ValueError/KeyError — the block is GONE,
                # which is exactly a FetchFailed: lineage recovery re-runs
                # the map.
                batch = sb.get_batch() if sb is not None else None
            except SpillCorruptionError as exc:
                with self._mu:
                    self._invalidate_map_locked(shuffle_id, map_id)
                raise FetchFailedError(shuffle_id, [map_id]) from exc
            except (ValueError, KeyError) as exc:
                raise FetchFailedError(shuffle_id, [map_id]) from exc
            if batch is not None:
                yield batch

    def _invalidate_map_locked(self, shuffle_id: int, map_id: int) -> None:
        """Drop one map's blocks + completion (caller holds self._mu)."""
        victims = [k for k in self._blocks
                   if k[0] == shuffle_id and k[1] == map_id]
        for k in victims:
            self._blocks.pop(k).close()
        self._owner.pop((shuffle_id, map_id), None)
        self._complete.discard((shuffle_id, map_id))

    def reduce_sizes(self, shuffle_id: int, n_maps: int,
                     n_reduces: int) -> List[int]:
        """Per-reduce-partition byte totals from catalog metadata alone
        (sizes are tracked at put time from the spillable's device byte
        count — AQE statistics never unspill or fetch a block). Raises
        FetchFailedError for incomplete maps, exactly like the block fetch,
        so the caller's recovery loop re-runs lost maps first."""
        with self._mu:
            missing = [m for m in range(n_maps)
                       if (shuffle_id, m) not in self._complete]
            if missing:
                raise FetchFailedError(shuffle_id, missing)
            out = [0] * n_reduces
            for (sid, _m, r), sb in self._blocks.items():
                if sid == shuffle_id and r < n_reduces:
                    out[r] += sb.size_bytes
            return out

    def invalidate_map(self, shuffle_id: int, map_id: int) -> None:
        """Drop one map's blocks + completion (a lost peer/shard observed
        by a reader): the next fetch raises FetchFailedError and lineage
        recovery re-runs exactly this map."""
        with self._mu:
            self._invalidate_map_locked(shuffle_id, map_id)

    def block_sizes(self, shuffle_id: int, reduce_id: int,
                    n_maps: int) -> List[int]:
        """Per-map device byte sizes of one reduce partition — one lock pass
        (AQE skew planning granularity)."""
        out = [0] * n_maps
        with self._mu:
            for m in range(n_maps):
                sb = self._blocks.get((shuffle_id, m, reduce_id))
                if sb is not None:
                    out[m] = sb.size_bytes
        return out

    def invalidate_owner(self, executor_id: str) -> List[Tuple[int, int]]:
        """Drop all blocks produced by a lost peer; returns the
        (shuffle_id, map_id) pairs that need re-running."""
        with self._mu:
            lost = [sm for sm, o in self._owner.items() if o == executor_id]
            lost_set = set(lost)
            victims = [k for k in self._blocks if (k[0], k[1]) in lost_set]
            closed = [self._blocks.pop(k) for k in victims]
            for sm in lost:
                del self._owner[sm]
                self._complete.discard(sm)
        for sb in closed:
            sb.close()
        return lost

    def cleanup(self, shuffle_id: int) -> None:
        with self._mu:
            victims = [k for k in self._blocks if k[0] == shuffle_id]
            closed = [self._blocks.pop(k) for k in victims]
            self._owner = {sm: o for sm, o in self._owner.items()
                           if sm[0] != shuffle_id}
            self._complete = {sm for sm in self._complete
                              if sm[0] != shuffle_id}
        for sb in closed:
            sb.close()

    def block_count(self) -> int:
        with self._mu:
            return len(self._blocks)
