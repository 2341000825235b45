"""The deployment `tpch-sf1-star-mesh4` (ISSUE 31) on the CPU backend's virtual
devices: TPC-H Q3 over three tables cached row-sharded over a four-chip mesh
session, under the configuration file's own `session_conf`. State and work are
divided (a quarter of each table a chip, every reduce block on its partition's
chip alone, nothing replicated but the broadcast build, a partition task a
chip) and the answer is the plain reference's and, bit for bit, the one-chip
session's. `docs/distributed.md` "Placement and the task model".
"""

import threading

import jax
import numpy as np
import pytest

import spark_rapids_tpu.functions as F
from chipbench import check, datagen, engine, manifest
from chipbench.queries import q3
from spark_rapids_tpu.parallel import mesh as pm
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.shuffle.ici import IciShuffleCatalog

CELL = "mesh-q3-4chip"
ONE_CHIP_CELL = "star-sf1-q3-stream"
N = 4
ROWS = 1 << 16
SEED = 2**31 + 29
#: what gives the plan, at test size, the join strategies it has at SF1 (a
#: broadcast lower join, a shuffled top join): the threshold under the
#: filtered ORDERS' estimated size. Nothing else is set beside the
#: configuration's own session_conf.
CELL_STRATEGIES = {"spark.sql.autoBroadcastJoinThreshold": "40000"}
MESH_PHASES = ("mesh.stage", "mesh.collective", "mesh.wait")
MESH_COUNTERS = ("mesh.exchanges", "mesh.rows_moved", "mesh.bytes_moved",
                 "mesh.per_map_fallbacks", "mesh.replicated_bytes",
                 "mesh.broadcast_bytes")


def _config(cell=CELL):
    return manifest.Cell(cell).config


def _columns(rows=ROWS, seed=SEED):
    return {name: t.generate(seed, t.cached, 0, t.rows)
            for name, t in datagen.tables(_config(), rows).items()}


def _reference(cols, rows=ROWS):
    schema = datagen.tables(_config(), rows)
    return q3.reference({name: schema[name].kept(c, q3.COLUMNS[name])
                         for name, c in cols.items()})


def _tables(session, cols, rows=ROWS):
    schema = datagen.tables(_config(), rows)
    return {name: session.createDataFrame(schema[name].to_arrow(c)).device_cache()
            for name, c in cols.items()}


def _arrays(batch):
    for c in batch.columns:
        for buf in (c.data, c.validity, c.offsets):
            if isinstance(buf, jax.Array):
                yield buf


class _Run:
    """One session's Q3 over the generated tables: the answer, the summary of
    the last (warm) query, the scans' rows and the reduce blocks it put."""

    def __init__(self, conf, cols):
        self.session = TpuSession(conf)
        self.tables = _tables(self.session, cols)
        self.frame = q3.build(F, self.tables)
        self.plan = engine.plan_text(self.frame)
        self.blocks = []          # (reduce partition, owner, batch)
        put = IciShuffleCatalog.put_block
        lock = threading.Lock()

        def spy(cat, sid, map_id, reduce_id, batch, owner=None):
            with lock:
                self.blocks.append((reduce_id, owner, batch))
            return put(cat, sid, map_id, reduce_id, batch, owner=owner)

        IciShuffleCatalog.put_block = spy
        try:
            self.first = self.frame.collect()
            del self.blocks[:]
            self.rows = self.frame.collect()
        finally:
            IciShuffleCatalog.put_block = put
        self.summary = self.session.last_query_phases()
        self.counters = self.summary["counters"]
        self.scanned = sum(
            vals["numOutputRows"]
            for node, vals in self.session.last_query_metrics("DEBUG").items()
            if "TpuDeviceScanExec" in node)


@pytest.fixture(scope="module")
def cols():
    return _columns()


@pytest.fixture(scope="module")
def want(cols):
    rows = _reference(cols)
    assert len(rows) == 10
    return rows


@pytest.fixture(scope="module")
def mesh_run(cols):
    pm.MeshContext.reset_for_tests()
    conf = dict(_config()["session_conf"], **CELL_STRATEGIES)
    run = _Run(conf, cols)
    yield run
    run.session.stop()


@pytest.fixture(scope="module")
def one_chip_run(cols):
    conf = dict(_config(ONE_CHIP_CELL)["session_conf"], **CELL_STRATEGIES)
    run = _Run(conf, cols)
    yield run
    run.session.stop()


# ---------------------------------------------------------------------------
# the configuration, as the benchmark declares it
# ---------------------------------------------------------------------------


def test_the_configuration_is_the_sibling_over_four_chips():
    cell = manifest.Cell(CELL)
    conf, sib = cell.config, _config(ONE_CHIP_CELL)
    assert manifest.validate() == []
    assert cell.chips == 4 and conf["chips"] == 4
    assert cell.entry["traffic"] == "q3-spec-stream"
    assert conf["tables"] == sib["tables"] and conf["rows"] == sib["rows"]
    assert len(conf["source"]) <= 200
    # the three mesh keys and a time limit; nothing else selects a path
    assert conf["session_conf"] == {
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.shuffle.mode": "ICI",
        "spark.rapids.tpu.mesh.enabled": "true",
        "spark.rapids.tpu.mesh.size": "4",
        "spark.rapids.tpu.query.timeoutMs": "1800000"}
    assert set(sib["guarantees"]) < set(conf["guarantees"])
    listed = {m["name"] for m in cell.metrics("per_layer")}
    assert {"mesh_stage_ms_per_query", "mesh_collective_ms_per_exchange",
            "mesh_wait_ms_per_exchange", "mesh_bytes_moved_per_query",
            "mesh_chip_imbalance", "hbm_roofline_share.mesh4",
            "mesh_collective_roofline_share"} <= listed
    assert "hbm_roofline_share" not in listed
    assert {"rows_per_s", "setup_s"} == {m["name"] for m in cell.metrics("end_to_end")}


def test_the_plan_is_the_cells_and_all_on_the_device(mesh_run):
    plan = mesh_run.plan
    assert engine.host_operators(plan) == [], plan
    assert "BroadcastHashJoin+Project" in plan, plan
    assert "TpuFusedSegment[ShuffledSymmetricHashJoin]" in plan, plan
    assert plan.count("TpuShuffleExchange[hash, n=4]") == 2, plan


# ---------------------------------------------------------------------------
# the answer
# ---------------------------------------------------------------------------


def test_q3_on_the_mesh_matches_the_reference(mesh_run, want):
    c = check.compare_rows(mesh_run.rows, want)
    assert c["inexact"] == 0, (c, mesh_run.rows, want)
    assert c["max_rel_err"] <= check.limits()["double_max_rel_err"], c
    assert [r["l_orderkey"] for r in mesh_run.rows] == \
        [r["l_orderkey"] for r in want]


def test_q3_on_the_mesh_is_the_one_chip_answer_bit_for_bit(mesh_run,
                                                           one_chip_run):
    assert mesh_run.rows == one_chip_run.rows
    assert mesh_run.first == mesh_run.rows


def test_q3_under_the_configurations_conf_alone(cols, want):
    """No key beside the file's: at this size both joins broadcast, and the
    answer is the reference's."""
    s = TpuSession(dict(_config()["session_conf"]))
    try:
        frame = q3.build(F, _tables(s, cols))
        assert engine.host_operators(engine.plan_text(frame)) == []
        c = check.compare_rows(frame.collect(), want)
        assert c["inexact"] == 0 and c["max_rel_err"] <= 1e-13, c
        assert s.last_query_phases()["counters"]["mesh.replicated_bytes"] == 0
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# state is divided
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("table", ["customer", "orders", "lineitem"])
def test_each_chip_holds_a_quarter_of_a_cached_table(mesh_run, table):
    chips = jax.devices()[:N]
    rel = mesh_run.tables[table]._plan
    held = {d: 0 for d in chips}
    for p, b in enumerate(rel.batches()):
        if b is None:
            continue
        for arr in _arrays(b):
            assert arr.committed and arr.devices() == {chips[p % N]}, (table, p)
        held[chips[p % N]] += b.num_rows
    assert sum(held.values()) == rel.num_rows
    for d, n in held.items():
        assert abs(n / rel.num_rows - 0.25) <= 0.01, (table, held)


def test_a_cached_table_reads_back_in_row_order(cols):
    """Chip by chip, partitions in order: the table's own rows."""
    s = TpuSession(dict(_config()["session_conf"],
                        **{"spark.rapids.sql.batchSizeRows": "1500"}))
    try:
        rel = _tables(s, cols)["orders"]._plan
        batches = rel.batches()
        assert len(batches) == 3 * N      # 4,095 rows a chip: 1,500 + 1,500 + 1,095
        got = np.concatenate([b.columns[0].to_numpy()
                              for r in range(N) for b in batches[r::N]
                              if b is not None])
        assert np.array_equal(got, cols["orders"]["o_orderkey"])
        assert all(b is None or b.num_rows <= 1500 for b in batches)
    finally:
        s.stop()


def test_outside_a_mesh_session_the_cache_batches_as_before(cols, one_chip_run):
    rel = one_chip_run.tables["lineitem"]._plan
    assert all(b is not None for b in rel.batches())
    assert len(rel.batches()) == 1 and rel.num_rows == ROWS
    assert not any(k.startswith("mesh.") for k in one_chip_run.counters)


def test_every_reduce_block_is_on_its_partitions_chip_alone(mesh_run):
    chips = jax.devices()[:N]
    assert mesh_run.counters["mesh.exchanges"] == 3
    assert len(mesh_run.blocks) >= 2 * N + 1
    for reduce_id, owner, batch in mesh_run.blocks:
        assert owner == "mesh-collective"
        for arr in _arrays(batch):
            assert arr.devices() == {chips[reduce_id]}, (reduce_id, arr.devices())


def test_nothing_is_replicated_but_the_broadcast_build(mesh_run):
    c = mesh_run.counters
    assert c["mesh.replicated_bytes"] == 0
    assert c["mesh.per_map_fallbacks"] == 0
    # CUSTOMER's filtered rows, copied to the three chips that did not
    # collect them
    assert c["mesh.broadcast_bytes"] > 0
    assert c["mesh.bytes_moved"] > 0 and c["mesh.rows_moved"] > 0


# ---------------------------------------------------------------------------
# work is divided, not repeated
# ---------------------------------------------------------------------------


def test_the_chips_task_rows_add_up_to_the_one_chip_runs(mesh_run,
                                                        one_chip_run):
    per_chip = [mesh_run.counters[f"mesh.task_rows.chip{r}"] for r in range(N)]
    one = one_chip_run.counters
    assert sum(per_chip) == one["join.rows_left"] + one_chip_run.scanned
    assert mesh_run.counters["join.rows_left"] == one["join.rows_left"]
    assert mesh_run.counters["join.rows_out"] == one["join.rows_out"]
    # a chip prepares (sorts, lays the directory of) its top-join partition's
    # build and its own copy of the broadcast build, each once, and probes
    # each with one batch
    c = mesh_run.counters
    assert c["join.builds_indexed"] == c["join.probes_indexed"] == 2 * N
    assert c["join.candidate_pairs"] >= c["join.rows_out"]
    # every chip took its share in: within what the hash gives
    assert max(per_chip) / (sum(per_chip) / N) < 1.1, per_chip


def test_partition_tasks_run_on_their_chips_worker_threads():
    seen = {}

    def task(i):
        seen[i] = (threading.current_thread().name, pm.current_chip(),
                   jax.numpy.zeros(4).devices())
        return i * i

    conf = TpuSession(dict(_config()["session_conf"]))._rapids_conf()
    chips = pm.session_chips(conf)
    assert chips == tuple(jax.devices()[:N])
    assert pm.run_chip_tasks(conf, range(6), task) == {i: i * i for i in range(6)}
    for i, (thread, chip, devs) in seen.items():
        assert thread == f"chip-{i % N}" and chip == chips[i % N]
        assert devs == {chips[i % N]}
    assert pm.current_chip() is None


def test_a_failed_chip_task_fails_the_group():
    conf = TpuSession(dict(_config()["session_conf"]))._rapids_conf()
    ran = []

    def task(i):
        ran.append(i)
        if i == 1:
            raise ValueError("chip 1")
        return i

    with pytest.raises(ValueError, match="chip 1"):
        pm.run_chip_tasks(conf, range(8), task)
    assert 5 not in ran            # chip 1's next partition never started


def test_a_task_of_another_chip_gets_the_partition_moved(cols):
    """An operator that collects its child's partitions pulls each where it
    lives; the batches arrive on the task's own chip and are counted."""
    from spark_rapids_tpu.execs.base import TaskContext
    from spark_rapids_tpu.plan.overrides import plan_query
    s = TpuSession(dict(_config()["session_conf"]))
    try:
        df = _tables(s, cols)["orders"]
        conf = s._rapids_conf()
        final, _, _ = plan_query(df._plan, conf)
        scan = [n for n in final.collect_nodes()
                if type(n).__name__ == "TpuDeviceScanExec"][0]
        chips = pm.session_chips(conf)
        with pm.on_chip(chips[0]):
            got = [b for p in range(N)
                   for b in scan.execute_partition(p, TaskContext(0, conf))]
        assert len(got) == N
        for b in got:
            assert all(a.devices() == {chips[0]} for a in _arrays(b))
        moved = dict(scan.mesh_counters())
        assert moved["mesh.rows_moved"].value == sum(b.num_rows for b in got[1:])
        assert moved["mesh.bytes_moved"].value > 0
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# phases and counters
# ---------------------------------------------------------------------------


def test_the_summary_holds_the_mesh_phases_and_counters(mesh_run):
    phases, counters = mesh_run.summary["phases"], mesh_run.counters
    for name in MESH_PHASES:
        assert phases[name]["count"] >= 3 and phases[name]["wall_ns"] > 0, name
    assert phases["mesh.wait"]["cat"] == "wait"
    assert phases["mesh.collective"]["count"] == counters["mesh.exchanges"]
    assert phases["mesh.wait"]["wall_ns"] <= phases["mesh.collective"]["wall_ns"]
    for name in MESH_COUNTERS:
        assert name in counters, (name, counters)
    assert {f"mesh.task_rows.chip{r}" for r in range(N)} <= set(counters)
    # the per-map phases belong to the per-map path
    assert "exchange.map" not in phases
    # phases opened on the chips' worker threads landed in the summary: the
    # probes run there
    assert phases["segment.launch"]["count"] >= N


def test_the_root_phase_covers_the_query(mesh_run, one_chip_run):
    """`phase_unattributed_share` is the root's self time over its wall. On
    the chip a Q3 takes seconds and the share is under 1 % (PERF.md); at
    this size a query takes a tenth of a second, so the bound here is the
    one-chip session's own self time and a few ms for reading four chips'
    parked row counts."""
    def self_ms(run):
        q = run.summary["phases"]["query"]
        return (q["wall_ns"] - q["child_wall_ns"]) / 1e6, q["wall_ns"] / 1e6

    mesh_self, mesh_wall = self_ms(mesh_run)
    one_self, _ = self_ms(one_chip_run)
    assert mesh_self <= one_self + 10.0, (mesh_self, one_self)
    assert mesh_self / mesh_wall < 0.10, (mesh_self, mesh_wall)


def test_no_program_compiles_in_a_third_query(mesh_run):
    """After two queries a third compiles nothing."""
    mesh_run.frame.collect()
    assert mesh_run.session.last_query_phases()["compiles"] == 0


def test_no_programs_chip_hangs_on_which_chip_answers_first(mesh_run,
                                                           monkeypatch):
    """A program compiles for the chip it runs on AND for the default device
    of the thread that launches it, so whatever the first chip to arrive
    does for all (collect the broadcast build, materialize an exchange,
    decide a give-up) must land on the same chip every time. On the v5e host
    one query in 144 compiled inside the window before this held (PERF.md
    section 6, PR 31): the chips' tasks start late by random amounts here."""
    import random
    import time
    rnd = random.Random(31)
    run = pm.run_chip_tasks

    def late(conf, ids, task):
        def t(i):
            time.sleep(rnd.random() * 0.02)
            return task(i)
        return run(conf, ids, t)

    monkeypatch.setattr(pm, "run_chip_tasks", late)
    for _ in range(12):
        assert mesh_run.frame.collect() == mesh_run.rows
        assert mesh_run.session.last_query_phases()["compiles"] == 0


# ---------------------------------------------------------------------------
# the cell's files run by name, and its new metrics read
# ---------------------------------------------------------------------------


def test_the_cell_runs_and_its_metrics_read():
    from chipbench import run
    from spark_rapids_tpu.io import device_decode
    device_decode.reset_for_tests()
    pm.MeshContext.reset_for_tests()
    # 1.5 s: on a loaded machine a window of 0.3 s once closed before the
    # closed loop had sent its first query (tier-1 under six workers)
    r = run.run_cell(CELL, SEED, 1.5, trace=False, rehearsal_rows=1 << 14)
    assert r["correct"] is True and r["attempted"] >= 1, r["checks"]
    assert r["metrics"]["rows_per_s"]["value"] > 0
    ctx = type("Ctx", (), {})()
    ctx.records = [None] * r["attempted"]
    cell = manifest.Cell(CELL)
    for name in ("mesh_bytes_moved_per_query", "mesh_chip_imbalance"):
        read, args = cell.reader(name)
        assert read(ctx, **args) > 0, name
    read, args = cell.reader("mesh_chip_imbalance")
    assert 100.0 <= read(ctx, **args) < 110.0


def test_the_new_readers_find_nothing_in_a_program_without_the_counters():
    """The parent commit has no such counters: the readers return nothing
    and do not raise (the driver lays these files over the parent too)."""
    from chipbench.readers import chip_imbalance, query_counter
    from spark_rapids_tpu.obs import metrics
    s = TpuSession(dict(_config(ONE_CHIP_CELL)["session_conf"]))
    try:
        s.createDataFrame([{"a": 1}]).collect()
    finally:
        s.stop()
    assert "mesh.bytes_moved" not in metrics.recent_queries(1)[0]["counters"]
    ctx = type("Ctx", (), {"records": [None]})()
    assert query_counter.read(ctx, "mesh.bytes_moved") is None
    assert chip_imbalance.read(ctx, "mesh.task_rows.chip") is None


def test_the_collectives_least_time_is_the_longer_of_hbm_and_ici():
    from chipbench import mesh_roofline, roofline
    p = roofline.peaks("TPU v5 lite")
    # 400 MB exchanged, 300 MB of them to another chip, four chips
    assert mesh_roofline.hbm_bytes_per_chip(400e6, 4) == 200e6
    assert mesh_roofline.ici_bytes_per_chip(300e6, 4) == 75e6
    least = mesh_roofline.least_seconds(400e6, 300e6, 4, "TPU v5 lite")
    assert least == pytest.approx(max(200e6 / p["hbm_bytes_per_s"],
                                      75e6 / (p["ici_bits_per_s"] / 8)))
    assert least == pytest.approx(75e6 / 200e9)      # the interconnect bounds it
