"""Python UDF worker pool: pandas/arrow UDFs execute in separate worker
processes with Arrow-IPC argument/result exchange, gated by a
device-admission semaphore.

Reference analogues:
  - worker processes + per-worker channels: GpuArrowEvalPythonExec and the
    forked python workers in python/rapids/worker.py:22-45 (each worker is
    its own interpreter with its own socket, so user UDF code cannot stall
    or crash the executor, and a wedged UDF can be killed without touching
    any other worker)
  - PythonWorkerSemaphore (python/PythonWorkerSemaphore.scala:98): caps how
    many python workers may hold device resources concurrently; here the
    permit is held for the duration of a worker round-trip (the worker's
    results are uploaded to HBM by the caller on return)

Each worker owns a dedicated duplex pipe. A caller acquires an idle worker,
ships one task, and blocks on that worker's pipe alone — there is no shared
task/result queue, so killing a wedged worker (SIGKILL on timeout) can only
tear the pipe of the worker being discarded, never wedge its siblings or a
shared lock.

UDFs that cannot pickle (closures over live objects, lambdas) fall back to
in-process evaluation — the same pricing as the reference's row-based CPU
fallback wrappers.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import pickle
import threading
import time
from typing import List, Optional

from .serving import query_context as _qlc

_POOL_LOCK = threading.Lock()
_POOL: Optional["PythonWorkerPool"] = None


def _ipc_write(arrays) -> bytes:
    import io

    import pyarrow as pa
    names = [f"c{i}" for i in range(len(arrays))]
    table = pa.table(dict(zip(names, arrays))) if arrays else pa.table({})
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, table.schema) as w:
        w.write_table(table)
    return sink.getvalue()


def _ipc_read(blob: bytes):
    import io

    import pyarrow as pa
    with pa.ipc.open_stream(io.BytesIO(blob)) as r:
        t = r.read_all()
    return [t.column(i).combine_chunks() for i in range(t.num_columns)]


def _udf_worker_main(conn) -> None:
    """Worker loop over a dedicated pipe: (fn_blob, args_ipc) ->
    (status, payload). One request in flight at a time, by construction."""
    from .utils.hw import pin_worker_to_cpu
    pin_worker_to_cpu()
    while True:
        try:
            item = conn.recv()
        except (EOFError, OSError):
            return
        if item is None:
            return
        fn_blob, args_blob = item
        try:
            fn = pickle.loads(fn_blob)
            args = _ipc_read(args_blob)
            out = fn(*args)
            import pyarrow as pa
            if not isinstance(out, (pa.Array, pa.ChunkedArray)):
                out = pa.array(out)
            if isinstance(out, pa.ChunkedArray):
                out = out.combine_chunks()
            conn.send(("ok", _ipc_write([out])))
        except Exception as e:  # noqa: BLE001 — report to driver
            conn.send(("error", repr(e)))


class _Worker:
    """One spawned process + the driver's end of its dedicated pipe."""

    def __init__(self, ctx):
        self.conn, child = ctx.Pipe(duplex=True)
        self.proc = ctx.Process(target=_udf_worker_main, args=(child,),
                                daemon=True)
        self.proc.start()
        child.close()

    def kill(self) -> None:
        self.proc.kill()
        self.proc.join(timeout=5)
        try:
            self.conn.close()
        except OSError:
            pass


class PythonWorkerPool:
    """N spawned UDF workers + a driver-side admission semaphore.

    `high_water_mark` reports the peak number of simultaneously in-flight
    worker round-trips, which is what the admission semaphore bounds
    (PythonWorkerSemaphore.scala:98 semantics)."""

    def __init__(self, num_workers: int = 2, permits: Optional[int] = None):
        self._ctx = mp.get_context("spawn")
        self.num_workers = num_workers
        # reference default: concurrentPythonWorkers == pool size unless
        # narrowed (PythonWorkerSemaphore.scala:98)
        self.permits = permits or num_workers
        self.semaphore = threading.Semaphore(self.permits)
        self._lock = threading.Lock()
        self._idle_cv = threading.Condition(self._lock)
        self._idle: List[_Worker] = [_Worker(self._ctx)
                                     for _ in range(num_workers)]
        self._num_workers = num_workers
        self._in_flight = 0
        self._high_water = 0
        self._closed = False

    @property
    def high_water_mark(self) -> int:
        return self._high_water

    def _acquire_worker(self) -> _Worker:
        with self._idle_cv:
            while not self._idle and not self._closed \
                    and self._in_flight >= self._num_workers:
                self._idle_cv.wait()
            if self._closed:
                raise RuntimeError("worker pool is shut down")
            if self._idle:
                w = self._idle.pop()
            else:
                # idle empty but capacity remains: a replacement spawn
                # failed earlier and shrank the pool — respawn lazily so
                # capacity self-heals instead of callers blocking forever
                w = _Worker(self._ctx)
            self._in_flight += 1
            if self._in_flight > self._high_water:
                self._high_water = self._in_flight
            return w

    def _release_worker(self, w: Optional[_Worker]) -> None:
        stray = None
        with self._idle_cv:
            self._in_flight -= 1
            if w is not None:
                if self._closed:
                    stray = w  # pool shut down while this task ran
                else:
                    self._idle.append(w)
            self._idle_cv.notify()
        if stray is not None:
            try:
                stray.conn.send(None)
            except (OSError, BrokenPipeError):
                stray.kill()

    def run(self, fn_blob: bytes, arrays, timeout: float = 120.0):
        """Ship one UDF invocation to a dedicated worker; blocks on the
        admission semaphore, then on that worker's pipe.

        On timeout the wedged worker is killed and replaced — only its own
        pipe is torn, so sibling workers and their callers are unaffected.

        The round-trip is a cooperative cancellation boundary (docs/
        robustness.md "Query lifecycle"): the poll runs in short slices
        re-checking the bound query's cancel token/deadline, so a
        cancelled query abandons the round-trip promptly instead of
        blocking the full timeout. An abandoned worker still computing is
        killed and replaced — its pending result must never be delivered
        to the NEXT caller of a recycled worker."""
        _qlc.checkpoint("udf.run")
        with self.semaphore:
            w = self._acquire_worker()
            replacement: Optional[_Worker] = w

            def discard_and_replace() -> Optional[_Worker]:
                # kill the (wedged/abandoned/dead) worker — never requeue
                # it, its pipe state is stale — and best-effort respawn
                w.kill()
                try:
                    return _Worker(self._ctx)
                except Exception:  # noqa: BLE001
                    return None  # pool self-heals in _acquire_worker

            try:
                try:
                    w.conn.send((fn_blob, _ipc_write(list(arrays))))
                    end = time.monotonic() + timeout
                    while not w.conn.poll(
                            min(0.2, max(0.0, end - time.monotonic()))):
                        try:
                            _qlc.checkpoint("udf.poll")
                        except BaseException:
                            # cancelled mid-round-trip: the in-flight
                            # result is stale — discard the worker, unwind
                            replacement = discard_and_replace()
                            raise
                        if time.monotonic() >= end:
                            replacement = discard_and_replace()
                            raise TimeoutError(
                                "python UDF worker timed out")
                    status, payload = w.conn.recv()
                except TimeoutError:
                    raise  # ours (subclass of OSError — don't swallow below)
                except (EOFError, OSError) as e:
                    # worker died mid-task (crash/OOM): replace it
                    replacement = discard_and_replace()
                    raise RuntimeError(f"python UDF worker died: {e!r}")
            finally:
                self._release_worker(replacement)
        if status == "error":
            raise RuntimeError(f"python UDF worker failed: {payload}")
        return _ipc_read(payload)[0]

    def shutdown(self) -> None:
        with self._idle_cv:
            self._closed = True
            workers = list(self._idle)
            self._idle.clear()
            self._idle_cv.notify_all()
        for w in workers:
            try:
                w.conn.send(None)
            except (OSError, BrokenPipeError):
                pass
        for w in workers:
            w.proc.join(timeout=2)
            if w.proc.is_alive():
                w.proc.kill()


def get_pool(num_workers: int, permits: Optional[int] = None
             ) -> PythonWorkerPool:
    """Process-wide pool (created on first use; resized on config change)."""
    global _POOL
    with _POOL_LOCK:
        want_permits = permits or num_workers
        if _POOL is None or _POOL.num_workers != num_workers \
                or _POOL.permits != want_permits or _POOL._closed:
            if _POOL is not None:
                _POOL.shutdown()
            _POOL = PythonWorkerPool(num_workers, permits)
        return _POOL


def try_pickle(fn) -> Optional[bytes]:
    """Pickled UDF body, or None when the function cannot ship to a worker
    (closure over live state) — caller falls back to in-process eval."""
    try:
        blob = pickle.dumps(fn)
        pickle.loads(blob)
        return blob
    except Exception:  # noqa: BLE001
        return None
