"""Python UDF worker pool: process isolation, Arrow-IPC exchange, and the
device-admission semaphore bound (reference
GpuArrowEvalPythonExec + PythonWorkerSemaphore.scala:98)."""

import threading
import time

import pyarrow as pa
import pyarrow.compute as pc
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.types import DoubleType
from spark_rapids_tpu.udf import pandas_udf
from spark_rapids_tpu.udf_workers import PythonWorkerPool, try_pickle


# module-level so the UDF pickles by reference into worker processes
def _double_it(a):
    return pc.multiply(a, 2.0)


def _sleepy(a):
    time.sleep(0.3)
    return a


def _boom(a):
    raise ValueError("udf exploded")


def _wedged(a):
    time.sleep(3600)
    return a


def test_pandas_udf_through_worker_pool_matches_inprocess():
    t = pa.table({"v": [1.0, 2.5, None, 4.0]})
    results = []
    for workers in ("0", "2"):
        s = TpuSession({"spark.rapids.sql.python.numWorkers": workers})
        df = s.createDataFrame(t)
        fn = pandas_udf(DoubleType())(_double_it)
        rows = df.select(fn(F.col("v")).alias("o")).collect()
        results.append([r["o"] for r in rows])
    assert results[0] == results[1] == [2.0, 5.0, None, 8.0]


def test_worker_pool_actually_used():
    pool = PythonWorkerPool(num_workers=1)
    try:
        blob = try_pickle(_double_it)
        assert blob is not None
        out = pool.run(blob, [pa.array([1.0, 2.0])])
        assert out.to_pylist() == [2.0, 4.0]
        assert pool.high_water_mark >= 1
    finally:
        pool.shutdown()


def test_unpicklable_udf_falls_back_inprocess():
    captured = []  # closure over live state -> cannot pickle

    def closure_fn(a):
        captured.append(1)
        return a
    assert try_pickle(closure_fn) is None
    s = TpuSession({"spark.rapids.sql.python.numWorkers": "2"})
    df = s.createDataFrame(pa.table({"v": [1.0, 2.0]}))
    fn = pandas_udf(DoubleType())(closure_fn)
    rows = df.select(fn(F.col("v")).alias("o")).collect()
    assert [r["o"] for r in rows] == [1.0, 2.0]
    assert captured  # proves it ran here, not in a worker


@pytest.mark.parametrize("permits,expected_max", [(1, 1), (2, 2)])
def test_semaphore_bounds_concurrent_workers(permits, expected_max):
    pool = PythonWorkerPool(num_workers=2, permits=permits)
    try:
        blob = try_pickle(_sleepy)
        threads = [threading.Thread(
            target=lambda: pool.run(blob, [pa.array([1.0])]))
            for _ in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert pool.high_water_mark <= permits
        if expected_max > 1:
            assert pool.high_water_mark == expected_max
    finally:
        pool.shutdown()


def test_wedged_udf_killed_on_timeout():
    """Timeout must kill+replace the wedged worker (so the concurrency bound
    holds) and leave the pool fully healthy (r3 advisor finding)."""
    pool = PythonWorkerPool(num_workers=1, permits=1)
    try:
        with pytest.raises(TimeoutError):
            pool.run(try_pickle(_wedged), [pa.array([1.0])], timeout=1.0)
        # the wedged worker was replaced; nothing stays in flight
        assert pool._in_flight == 0
        assert len(pool._idle) == 1
        # pool serves new work on the replacement worker
        out = pool.run(try_pickle(_double_it), [pa.array([5.0])], timeout=60)
        assert out.to_pylist() == [10.0]
    finally:
        pool.shutdown()


def test_sibling_worker_survives_a_kill():
    """A timeout on one worker must not disturb a concurrent task on a
    sibling — the per-worker-pipe design's core guarantee."""
    pool = PythonWorkerPool(num_workers=2, permits=2)
    try:
        results = {}

        def slow_ok():
            out = pool.run(try_pickle(_sleepy), [pa.array([2.0])], timeout=60)
            results["ok"] = out.to_pylist()

        t = threading.Thread(target=slow_ok)
        t.start()
        with pytest.raises(TimeoutError):
            pool.run(try_pickle(_wedged), [pa.array([1.0])], timeout=0.5)
        t.join(timeout=60)
        assert results.get("ok") == [2.0]
        assert pool._in_flight == 0
    finally:
        pool.shutdown()


def test_worker_error_propagates():
    pool = PythonWorkerPool(num_workers=1)
    try:
        with pytest.raises(RuntimeError, match="udf exploded"):
            pool.run(try_pickle(_boom), [pa.array([1.0])])
        # pool survives a failing UDF
        out = pool.run(try_pickle(_double_it), [pa.array([3.0])])
        assert out.to_pylist() == [6.0]
    finally:
        pool.shutdown()
