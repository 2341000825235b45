"""Multi-process executors: real worker processes, file shuffle, heartbeat
liveness, and kill-recovery.

The kill test SIGKILLs a worker mid-query and the job must still return
oracle-equal results — no hand-driven registry mutation anywhere; the pool
observes death via process liveness/heartbeats and re-runs lost maps, and
the reduce side's FetchFailedError path re-materializes missing blocks.
Reference: RapidsShuffleInternalManagerBase.scala:238,569 (executor-process
shuffle), RapidsShuffleHeartbeatManager.scala (lost-peer detection)."""

import pickle
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config import default_conf
from spark_rapids_tpu.parallel.executors import (ExecutorPool,
                                                 FetchFailedError,
                                                 _stable_bucket)
from spark_rapids_tpu.plan.planner import plan_physical
from spark_rapids_tpu.session import TpuSession


def _plan_for(df):
    conf = default_conf()
    return plan_physical(df._plan, conf)


def _table(n=4000, seed=7):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": rng.integers(0, 50, n),
        "s": pa.array(np.array(["x", "y", "zz", "w"])[
            rng.integers(0, 4, n)]),
        "v": rng.random(n),
    })


def _oracle_groupby(table):
    import pyarrow.compute as pc  # noqa: F401
    out = table.group_by(["k"]).aggregate([("v", "sum"), ("v", "count")])
    rows = {r["k"]: (round(r["v_sum"], 6), r["v_count"])
            for r in out.to_pylist()}
    return rows


def _reduce_groupby(tables):
    merged = pa.concat_tables([t for t in tables if t.num_rows]
                              or [tables[0]])
    return _oracle_groupby(merged)


@pytest.fixture(scope="module")
def _pool():
    p = ExecutorPool(num_workers=3)
    yield p
    p.shutdown()


@pytest.fixture()
def pool(_pool):
    _pool.heal()  # replace any worker a previous test killed
    assert len(_pool.live_workers()) == 3
    return _pool


def test_shuffled_collect_matches_oracle(pool):
    t = _table()
    s = TpuSession({"spark.rapids.sql.enabled": "false"})
    df = s.createDataFrame(t, num_partitions=6)
    plan = _plan_for(df)
    k_ord = t.column_names.index("k")
    reduces = pool.shuffled_collect(plan, [k_ord], num_reduces=4)
    assert len(reduces) == 4
    got = {}
    for part in reduces:
        got.update(_oracle_groupby(part))
    assert got == _oracle_groupby(t)
    # co-partitioning: every key lands in exactly one reduce partition
    seen = {}
    for rid, part in enumerate(reduces):
        for k in set(part.column("k").to_pylist()):
            assert seen.setdefault(k, rid) == rid


def test_kill_worker_mid_query_still_correct(pool):
    """SIGKILL a worker while maps are running; heartbeat/liveness detection
    reassigns its tasks and the result is still oracle-equal."""
    t = _table(n=20_000, seed=11)
    s = TpuSession({"spark.rapids.sql.enabled": "false"})
    df = s.createDataFrame(t, num_partitions=12)
    plan = _plan_for(df)
    k_ord = t.column_names.index("k")

    import threading
    killed = threading.Event()
    victim = pool.live_workers()[0]

    def killer():
        time.sleep(0.05)  # let dispatch start
        pool.kill_worker(victim)
        killed.set()

    th = threading.Thread(target=killer)
    th.start()
    reduces = pool.shuffled_collect(plan, [k_ord], num_reduces=3)
    th.join()
    assert killed.is_set()
    deadline = time.time() + 5
    while victim in pool.live_workers() and time.time() < deadline:
        time.sleep(0.05)  # SIGKILL reaping can lag the query's completion
    assert victim not in pool.live_workers()
    got = {}
    for part in reduces:
        got.update(_oracle_groupby(part))
    assert got == _oracle_groupby(t)


def test_fetch_failed_rematerializes_lost_block(pool):
    """Deleting a map output after the stage completes must surface as
    FetchFailedError and be healed by re-running the producing map."""
    import os
    t = _table(n=2000, seed=3)
    s = TpuSession({"spark.rapids.sql.enabled": "false"})
    df = s.createDataFrame(t, num_partitions=4)
    plan = _plan_for(df)
    k_ord = t.column_names.index("k")
    sid = pool._next_shuffle
    blob = pickle.dumps(plan)
    pool._next_shuffle += 1
    pool.run_map_stage(sid, blob, range(4), [k_ord], num_reduces=2)
    # simulate a lost executor's disk: remove one block
    from spark_rapids_tpu.parallel.executors import _block_path
    victim = _block_path(pool.shuffle_root, sid, 2, 1)
    os.remove(victim)
    with pytest.raises(FetchFailedError):
        pool.read_reduce(sid, 1, range(4))
    # heal: re-run map 2, then the read succeeds
    pool.run_map_stage(sid, blob, [2], [k_ord], num_reduces=2)
    tables = pool.read_reduce(sid, 1, range(4))
    assert sum(x.num_rows for x in tables) > 0


def test_string_hash_matches_rowwise_reference():
    from spark_rapids_tpu.parallel.executors import _string_hash_u32
    vals = ["", "a", "abc", None, "x" * 300, "abc", "abé"]
    arr = pa.array(vals, pa.string())
    got = _string_hash_u32(arr)

    def ref(s):
        h = np.uint32(0)
        with np.errstate(over="ignore"):
            for i, byte in enumerate(s.encode()):
                h = h + np.uint32(byte) * np.uint32(pow(31, i, 1 << 32))
        return h

    want = np.array([ref(v if v is not None else "") for v in vals],
                    np.uint32)
    assert (got == want).all()
    assert got[2] == got[5]  # equal strings hash equal


def test_stable_bucket_is_process_stable():
    t = _table(n=500, seed=5)
    b1 = _stable_bucket(t, [0, 1], 8)
    b2 = _stable_bucket(t, [0, 1], 8)
    assert (b1 == b2).all()
    assert set(np.unique(b1)) <= set(range(8))


def test_stable_bucket_temporal_key_types():
    """date32 has no direct pyarrow cast to int64 — shuffling keyed on a
    date/timestamp column must not crash the map task (r3 advisor finding)."""
    import datetime as dt
    n = 64
    days = [dt.date(2020, 1, 1) + dt.timedelta(days=i) for i in range(n)]
    ts = [dt.datetime(2021, 5, 1, 12, 0, 0) + dt.timedelta(hours=i)
          for i in range(n)]
    t = pa.table({
        "d32": pa.array(days, pa.date32()),
        "ts": pa.array(ts, pa.timestamp("us")),
        "t32": pa.array(list(range(n)), pa.time32("s")),
    })
    for ords in ([0], [1], [2], [0, 1, 2]):
        b = _stable_bucket(t, ords, 8)
        assert len(b) == n
        assert set(np.unique(b)) <= set(range(8))
        b2 = _stable_bucket(t, ords, 8)
        assert (b == b2).all()
    # equal keys land in equal buckets
    t2 = pa.table({"d32": pa.array([days[0]] * 4 + [days[1]] * 4,
                                   pa.date32())})
    b = _stable_bucket(t2, [0], 8)
    assert len(set(b[:4])) == 1 and len(set(b[4:])) == 1


def test_dead_worker_detected_by_liveness(pool):
    live = pool.live_workers()
    assert len(live) == 3
    victim = live[0]
    pool.kill_worker(victim)
    deadline = time.time() + 5
    while victim in pool.live_workers() and time.time() < deadline:
        time.sleep(0.05)
    assert victim not in pool.live_workers()


def test_spawned_worker_is_pinned_to_cpu_after_jax_import():
    """A spawned worker has already imported jax (spawn imports the worker's
    module, hence the package) when its entry function runs, so setting
    JAX_PLATFORMS there is too late: utils/hw.pin_worker_to_cpu must also
    set jax's config, or the worker reaches for the chip its parent holds."""
    import os
    import subprocess
    import sys
    code = (
        "import os, jax, spark_rapids_tpu.parallel.executors\n"
        "assert jax.config.jax_platforms is None, jax.config.jax_platforms\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'   # what the workers used to do\n"
        "assert jax.config.jax_platforms is None\n"
        "from spark_rapids_tpu.utils.hw import pin_worker_to_cpu\n"
        "pin_worker_to_cpu()\n"
        "assert jax.config.jax_platforms == 'cpu'\n"
        "assert jax.devices()[0].platform == 'cpu'\n")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
