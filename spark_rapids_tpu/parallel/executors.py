"""Multi-process executors: worker processes own shards of map tasks and
shuffle through the file block store; the driver monitors liveness via
heartbeats and re-runs lost work.

Reference analogues:
  - executor processes + shuffle files: RapidsShuffleInternalManagerBase.scala
    (MULTITHREADED writer :238 / reader :569 run inside separate executor
    JVMs; here each executor is a spawned Python process)
  - heartbeat/lost-peer detection: RapidsShuffleHeartbeatManager.scala (driver
    tracks executor liveness; a dead peer invalidates its blocks)
  - FetchFailed -> re-materialization: Spark's lineage recovery; the reduce
    side raises FetchFailedError for a missing block and the driver re-runs
    the producing map task on a surviving worker.

Workers execute REAL physical-plan partitions (the plan pickles: host-side
exec trees hold Arrow data / file paths, never device arrays), hash-partition
the rows with a process-stable hash, and write blocks under a shared
directory. The TPU chip belongs to the driver process; workers run the host
(CPU) plan path — matching the reference topology where map-side executors
do host shuffle IO while device work stays on the owning executor's device.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import pickle
import queue as pyqueue
import tempfile
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

HB_INTERVAL_S = 0.25
HB_TIMEOUT_S = 3.0


def _atomic_write(path: str, data: bytes) -> None:
    """Write-then-rename so a killed worker never leaves a partial block
    (the reduce side either sees a complete block or FetchFailed)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, path)


class FetchFailedError(RuntimeError):
    """A reduce task could not read a map output block (lost worker)."""

    def __init__(self, shuffle_id: int, map_id: int, reduce_id: int):
        super().__init__(
            f"fetch failed: shuffle={shuffle_id} map={map_id} "
            f"reduce={reduce_id}")
        self.shuffle_id = shuffle_id
        self.map_id = map_id
        self.reduce_id = reduce_id


_INV31 = np.uint32(pow(31, -1, 1 << 32))  # 31 is odd => invertible mod 2^32


def _string_hash_u32(arr) -> np.ndarray:
    """Vectorized per-row polynomial hash over the Arrow string buffers:
    h(row) = sum(byte_i * 31^i) mod 2^32, computed for all rows at once with
    global position weights 31^gpos and a modular-inverse shift (divide by
    31^row_start) — no per-row Python loop. Only determinism matters here
    (bucket assignment), not hash quality."""
    import pyarrow as pa

    from ..columnar.vector import rebase_string_offsets
    arr = arr.cast(pa.string())
    if arr.null_count:
        arr = arr.fill_null("")
    # zero-based offsets + exactly the addressed bytes (the shared
    # offsets-rebase the device decode staging uses too); copy=False —
    # the buffers are only read within this call
    offsets, chars = rebase_string_offsets(arr.buffers(), len(arr),
                                           arr.offset, copy=False)
    if not len(chars):
        return np.zeros(len(arr), np.uint32)
    b = chars.astype(np.uint32)
    with np.errstate(over="ignore"):
        pow31 = np.empty(len(b), np.uint32)
        pow31[0] = 1
        np.cumprod(np.full(len(b) - 1, 31, np.uint32), out=pow31[1:])
        weighted = b * pow31
        csum = np.concatenate([[np.uint32(0)],
                               np.cumsum(weighted, dtype=np.uint32)])
        starts = offsets.astype(np.int64)
        seg = csum[starts[1:]] - csum[starts[:-1]]
        # shift each row's weights back to 31^0: multiply by inv31^row_start
        # (rows starting at data_end are empty; the clipped index is unused
        # because their seg is already 0)
        invpow = np.empty(len(b), np.uint32)
        invpow[0] = 1
        np.cumprod(np.full(len(b) - 1, _INV31, np.uint32), out=invpow[1:])
        inv = invpow[starts[:-1].clip(0, len(invpow) - 1)]
        return (seg * inv).astype(np.uint32)


def _stable_bucket(table, key_ordinals: Sequence[int],
                   num_reduces: int) -> np.ndarray:
    """Process-stable row bucket assignment (numpy for fixed-width, crc32 for
    strings — python's builtin hash is salted per process and must not be
    used here)."""
    n = table.num_rows
    h = np.full(n, 0x9E3779B9, np.uint32)
    for o in key_ordinals:
        col = table.column(o)
        arr = col.combine_chunks() if hasattr(col, "combine_chunks") else col
        import pyarrow as pa
        if pa.types.is_string(arr.type) or pa.types.is_large_string(arr.type):
            vals = _string_hash_u32(arr)
        elif pa.types.is_floating(arr.type):
            f = np.asarray(arr.fill_null(0.0).to_numpy(
                zero_copy_only=False), np.float64)
            f = np.where(f == 0.0, 0.0, f)  # -0.0 == 0.0
            f = np.where(np.isnan(f), np.float64("nan"), f)  # one NaN bits
            vals = f.view(np.uint64).astype(np.uint32) \
                ^ (f.view(np.uint64) >> np.uint64(32)).astype(np.uint32)
        else:
            # pyarrow has no direct date32/time32→int64 cast; hop through
            # int32 (timestamp/date64/time64 cast to int64 directly below)
            if pa.types.is_date32(arr.type) or pa.types.is_time32(arr.type):
                arr = arr.cast(pa.int32())
            iv = np.asarray(arr.cast(pa.int64()).fill_null(0).to_numpy(
                zero_copy_only=False), np.int64)
            u = iv.view(np.uint64)
            vals = u.astype(np.uint32) ^ (u >> np.uint64(32)).astype(
                np.uint32)
        h = (h ^ vals) * np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
    return (h % np.uint32(num_reduces)).astype(np.int64)


def _block_path(root: str, shuffle_id: int, map_id: int,
                reduce_id: int) -> str:
    return os.path.join(root, f"s{shuffle_id}",
                        f"m{map_id}_r{reduce_id}.blk")


def _run_map_task(payload: dict) -> dict:
    """Executes one map task inside a worker: run the plan partition,
    hash-partition rows, write one block file per reduce."""
    import pyarrow as pa

    from ..execs.base import TaskContext
    from ..shuffle.serializer import get_codec, serialize_table

    plan = pickle.loads(payload["plan"])
    map_id = payload["map_id"]
    tables = list(plan.execute_partition(map_id, TaskContext(map_id)))
    table = (pa.concat_tables(tables) if tables
             else pa.schema([]).empty_table())
    num_reduces = payload["num_reduces"]
    buckets = (_stable_bucket(table, payload["key_ordinals"], num_reduces)
               if table.num_rows else np.zeros(0, np.int64))
    codec = get_codec(payload["codec"])
    sizes = []
    os.makedirs(os.path.join(payload["root"], f"s{payload['shuffle_id']}"),
                exist_ok=True)
    for rid in range(num_reduces):
        part = table.filter(buckets == rid) if table.num_rows else table
        blob = serialize_table(part, codec,
                               checksum=payload.get("checksum", True))
        _atomic_write(
            _block_path(payload["root"], payload["shuffle_id"], map_id, rid),
            blob)
        sizes.append(len(blob))
    return {"map_id": map_id, "sizes": sizes}


_TASK_FNS = {"map": _run_map_task}


def _worker_main(worker_id: int, task_q, result_q) -> None:
    """Worker process entry: heartbeat thread + task loop. Workers run the
    host plan path on CPU — the accelerator belongs to the driver process
    (v1; per-worker device ownership is the multi-host mode's job)."""
    from ..utils.hw import pin_worker_to_cpu
    pin_worker_to_cpu()
    stop = threading.Event()

    def beat():
        while not stop.is_set():
            try:
                result_q.put(("hb", worker_id, time.time()))
            except Exception:  # noqa: BLE001 — queue torn down at shutdown
                return
            stop.wait(HB_INTERVAL_S)

    threading.Thread(target=beat, daemon=True).start()
    try:
        while True:
            item = task_q.get()
            if item is None:
                return
            kind, task_id, payload = item
            try:
                out = _TASK_FNS[kind](payload)
                result_q.put(("done", worker_id, task_id, out))
            except Exception as e:  # noqa: BLE001 — report, don't die
                result_q.put(("error", worker_id, task_id, repr(e)))
    finally:
        stop.set()


class ExecutorPool:
    """N spawned worker processes + a shared-file shuffle root.

    The driver submits map tasks, tracks which worker holds which unfinished
    task, and treats a worker as lost when its process dies OR its heartbeat
    goes stale — lost workers' unfinished tasks are reassigned to survivors
    (reference: RapidsShuffleHeartbeatManager + Spark task rescheduling)."""

    def __init__(self, num_workers: int = 2, shuffle_root: Optional[str] = None,
                 codec: str = "zstd", hb_timeout_s: Optional[float] = None,
                 checksum: bool = True):
        if hb_timeout_s is None:
            from ..config import (EXECUTOR_HEARTBEAT_TIMEOUT_SECONDS,
                                  default_conf)
            hb_timeout_s = default_conf().get(
                EXECUTOR_HEARTBEAT_TIMEOUT_SECONDS)
        self.hb_timeout_s = float(hb_timeout_s)
        self._ctx = mp.get_context("spawn")
        self.shuffle_root = shuffle_root or tempfile.mkdtemp(
            prefix="tpu_mp_shuffle_")
        self.codec = codec
        self.checksum = bool(checksum)
        # one result queue PER worker: SIGKILLing a worker mid-put can
        # corrupt a shared queue's pipe for every producer; per-worker
        # queues confine the damage to the dead worker
        self._result_qs: Dict[int, object] = {}
        self._task_qs: Dict[int, object] = {}
        self._procs: Dict[int, object] = {}
        self._last_hb: Dict[int, float] = {}
        self._assigned: Dict[int, Dict[int, tuple]] = {}  # wid -> {tid: task}
        self._next_shuffle = 0
        self._next_task = 0
        for wid in range(num_workers):
            self._spawn(wid)

    def _spawn(self, wid: int) -> None:
        q = self._ctx.Queue()
        rq = self._ctx.Queue()
        self._result_qs[wid] = rq
        p = self._ctx.Process(target=_worker_main,
                              args=(wid, q, rq), daemon=True)
        p.start()
        self._task_qs[wid] = q
        self._procs[wid] = p
        # no heartbeat yet: startup (interpreter + jax import) can exceed the
        # heartbeat timeout, so liveness falls back to is_alive() until the
        # first beat arrives
        self._last_hb[wid] = None
        self._assigned[wid] = {}

    # -- liveness ----------------------------------------------------------
    def _alive(self, wid: int) -> bool:
        p = self._procs.get(wid)
        if p is None or not p.is_alive():
            return False
        hb = self._last_hb[wid]
        return hb is None or (time.time() - hb) < self.hb_timeout_s

    def live_workers(self) -> List[int]:
        return [w for w in self._procs if self._alive(w)]

    def kill_worker(self, wid: int) -> None:
        """Test hook: hard-kill one worker (SIGKILL)."""
        self._procs[wid].kill()

    def heal(self) -> None:
        """Replace dead workers with fresh processes (Spark's executor
        replacement: the cluster manager restarts lost executors)."""
        for wid in list(self._procs):
            if not self._procs[wid].is_alive():
                self._procs[wid].join(timeout=1)
                lost = list(self._assigned[wid].values())
                new_wid = max(self._procs) + 1
                del self._procs[wid], self._task_qs[wid]
                del self._last_hb[wid], self._assigned[wid]
                del self._result_qs[wid]
                self._spawn(new_wid)
                for task in lost:  # in-flight work moves to the replacement
                    self._dispatch(task)

    # -- task scheduling ---------------------------------------------------
    def _dispatch(self, task: tuple, exclude=()) -> int:
        live = [w for w in self.live_workers() if w not in exclude]
        if not live:
            raise RuntimeError("no live workers")
        wid = min(live, key=lambda w: len(self._assigned[w]))
        kind, tid, payload = task
        self._assigned[wid][tid] = task
        self._task_qs[wid].put(task)
        return wid

    def _drain_results(self, timeout: float):
        """Poll every live worker's result queue; heartbeats update liveness
        in passing, the first task result found is returned."""
        deadline = time.time() + timeout
        while True:
            for wid in list(self._result_qs):
                if not self._procs[wid].is_alive() \
                        and self._result_qs[wid].empty():
                    continue
                try:
                    while True:
                        msg = self._result_qs[wid].get_nowait()
                        if msg[0] == "hb":
                            self._last_hb[msg[1]] = msg[2]
                        else:
                            return msg
                except (pyqueue.Empty, OSError, EOFError):
                    continue
            if time.time() >= deadline:
                return None
            time.sleep(0.01)

    def run_map_stage(self, shuffle_id: int, plan_blob: bytes,
                      map_ids: Sequence[int], key_ordinals: Sequence[int],
                      num_reduces: int, deadline_s: float = 120.0) -> None:
        """Run map tasks across workers, reassigning work from lost workers
        until every map output is written (or deadline)."""
        pending: Dict[int, tuple] = {}
        for mid in map_ids:
            tid = self._next_task
            self._next_task += 1
            task = ("map", tid, {
                "plan": plan_blob, "map_id": mid,
                "key_ordinals": list(key_ordinals),
                "num_reduces": num_reduces, "root": self.shuffle_root,
                "shuffle_id": shuffle_id, "codec": self.codec,
                "checksum": self.checksum,
            })
            pending[tid] = task
            self._dispatch(task)
        deadline = time.time() + deadline_s
        while pending:
            if time.time() > deadline:
                raise TimeoutError(f"map stage timed out; pending={pending}")
            msg = self._drain_results(timeout=0.1)
            if msg is not None:
                kind, wid, tid, out = msg
                self._assigned.get(wid, {}).pop(tid, None)
                if kind == "done":
                    pending.pop(tid, None)
                elif kind == "error":
                    raise RuntimeError(f"map task failed on worker {wid}: "
                                       f"{out}")
            # reassign work held by dead workers
            for wid in list(self._procs):
                if not self._alive(wid) and self._assigned[wid]:
                    lost = list(self._assigned[wid].values())
                    self._assigned[wid] = {}
                    for task in lost:
                        if task[1] in pending:
                            self._dispatch(task, exclude=(wid,))

    # -- reduce side -------------------------------------------------------
    def read_reduce(self, shuffle_id: int, reduce_id: int,
                    map_ids: Sequence[int]):
        """Read one reduce partition's blocks; a missing block raises
        FetchFailedError naming the lost map (lineage recovery trigger)."""
        from ..shuffle.serializer import deserialize_table
        out = []
        for mid in map_ids:
            path = _block_path(self.shuffle_root, shuffle_id, mid, reduce_id)
            if not os.path.exists(path):
                raise FetchFailedError(shuffle_id, mid, reduce_id)
            with open(path, "rb") as f:
                out.append(deserialize_table(f.read()))
        return out

    def shuffled_collect(self, plan, key_ordinals: Sequence[int],
                         num_reduces: int):
        """Full shuffle round: map stage in workers (with loss recovery),
        reduce reads in the driver (FetchFailed -> re-run the lost map)."""
        import pyarrow as pa
        sid = self._next_shuffle
        self._next_shuffle += 1
        blob = pickle.dumps(plan)
        map_ids = list(range(plan.num_partitions()))
        self.run_map_stage(sid, blob, map_ids, key_ordinals, num_reduces)
        results = []
        max_heals = len(map_ids) + 1
        for rid in range(num_reduces):
            tables = None
            for _attempt in range(max_heals):
                try:
                    tables = self.read_reduce(sid, rid, map_ids)
                    break
                except FetchFailedError as e:
                    # re-materialize the lost map output then retry the
                    # read; each attempt can surface a DIFFERENT lost map,
                    # so allow one heal per map before giving up
                    self.run_map_stage(sid, blob, [e.map_id], key_ordinals,
                                       num_reduces)
            if tables is None:
                raise RuntimeError(f"reduce {rid} unrecoverable")
            results.append(pa.concat_tables(
                [t for t in tables if t.num_rows]
                or [tables[0]]))
        return results

    def shutdown(self) -> None:
        for wid, q in self._task_qs.items():
            try:
                q.put(None)
            except Exception:  # noqa: BLE001
                pass
        for p in self._procs.values():
            p.join(timeout=2)
            if p.is_alive():
                p.kill()
