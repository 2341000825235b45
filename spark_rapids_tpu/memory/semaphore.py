"""TpuSemaphore: admission control limiting concurrent tasks holding HBM.

Reference: GpuSemaphore.scala (acquireIfNecessary/releaseIfNecessary; default
concurrency spark.rapids.tpu.concurrentTpuTasks=2, RapidsConf.scala:544-551).
A task acquires once before its first device allocation and releases at task
completion (guaranteed by the TaskContext completion listener); operators may
release around long host-IO waits to let other tasks use the device, exactly
the reference's pattern around shuffle/scan IO.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from ..config import CONCURRENT_TPU_TASKS, RapidsConf, default_conf


class TpuSemaphore:
    _instance: Optional["TpuSemaphore"] = None
    _lock = threading.Lock()

    def __init__(self, permits: int):
        self.permits = permits
        self._sem = threading.BoundedSemaphore(permits)
        self._holders: Dict[int, int] = {}  # task id -> acquire depth
        #: mesh session: the permits are a chip's (the reference's semaphore
        #: is its executor's GPU's) — a task placed on a chip
        #: (parallel/mesh.py::on_chip) draws from that chip's own
        self._chip_sems: Dict[object, threading.BoundedSemaphore] = {}
        self._held_from: Dict[int, threading.BoundedSemaphore] = {}
        self._shared: set = set()  # task ids riding another task's permit
        self._state_lock = threading.Lock()
        self.total_waits_ns = 0

    @classmethod
    def get(cls, conf: Optional[RapidsConf] = None) -> "TpuSemaphore":
        with cls._lock:
            if cls._instance is None:
                conf = conf or default_conf()
                cls._instance = TpuSemaphore(conf.get(CONCURRENT_TPU_TASKS))
            return cls._instance

    @classmethod
    def reset_for_tests(cls) -> None:
        with cls._lock:
            cls._instance = None

    def acquire_if_necessary(self, ctx) -> None:
        """First call for a task blocks for a permit; later calls are no-ops.
        Registers release at task completion (reference: task-completion
        listener guarantees release, GpuSemaphore.scala). Safe when two
        threads share one task context (pipelined exchange map / join side
        collection): the loser of the first-acquire race hands its extra
        permit back — release runs once per task, so a double-acquire would
        otherwise leak a permit permanently."""
        import time
        tid = id(ctx)
        with self._state_lock:
            if tid in self._shared:
                return  # rides its group's permit (adopt)
            if tid in self._holders:
                self._holders[tid] += 1
                return
        sem = self._sem_here()
        t0 = time.perf_counter_ns()
        sem.acquire()
        waited = time.perf_counter_ns() - t0
        from ..obs import metrics as _metrics
        from ..obs import tracer as _obs
        from ..profiling import TaskMetricsRegistry
        TaskMetricsRegistry.get().add("semaphoreWaitNs", waited)
        _metrics.counter_inc("semaphore.waits")
        _metrics.counter_inc("semaphore.wait_ns", waited)
        if _obs._ACTIVE:
            _obs.event("semaphore.wait", cat="memory", wait_ns=waited)
        with self._state_lock:
            self.total_waits_ns += waited
            if tid in self._holders:  # lost the first-acquire race
                self._holders[tid] += 1
                sem.release()
                return
            self._holders[tid] = 1
            self._held_from[tid] = sem
        ctx.add_completion_listener(lambda: self.release_if_necessary(ctx))

    def _sem_here(self) -> threading.BoundedSemaphore:
        from ..parallel.mesh import current_chip
        chip = current_chip()
        if chip is None:
            return self._sem
        with self._state_lock:
            sem = self._chip_sems.get(chip)
            if sem is None:
                sem = self._chip_sems[chip] = threading.BoundedSemaphore(
                    self.permits)
            return sem

    def adopt(self, parent_ctx, child_ctx) -> None:
        """Batched multi-partition dispatch (spark.rapids.tpu.dispatch.
        partitionBatch): a partition GROUP is one unit of device work gated
        by ONE permit, held by the group's context. Member task contexts are
        adopted so their own acquire_if_necessary calls (scans take a permit
        per task) become no-ops — G members each blocking for a permit from
        one pool thread would deadlock the pool against concurrentTpuTasks.
        The parent must already hold; members release nothing at completion
        (the parent's completion releases the one real permit)."""
        ptid, ctid = id(parent_ctx), id(child_ctx)
        with self._state_lock:
            if ptid not in self._holders and ptid not in self._shared:
                return  # parent holds nothing: child acquires normally
            if ctid in self._holders or ctid in self._shared:
                return
            self._shared.add(ctid)
        child_ctx.add_completion_listener(
            lambda: self.release_if_necessary(child_ctx))

    def release_if_necessary(self, ctx) -> None:
        tid = id(ctx)
        with self._state_lock:
            if tid in self._shared:
                self._shared.discard(tid)
                return  # shared rider: the real permit is the parent's
            if tid not in self._holders:
                return
            del self._holders[tid]
            sem = self._held_from.pop(tid, self._sem)
        sem.release()
