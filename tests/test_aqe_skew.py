"""AQE join-input readers: coordinated coalescing + skew splitting.
Reference: GpuCustomShuffleReaderExec with
CoalescedPartitionSpec AND PartialReducerPartitionSpec, planned by
CoalesceShufflePartitions / OptimizeSkewedJoin."""

import random

import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.session import TpuSession


def _data(n, skew_key=0, skew_frac=0.7, seed=5):
    rng = random.Random(seed)
    rows = []
    for i in range(n):
        k = skew_key if rng.random() < skew_frac else rng.randint(1, 19)
        rows.append({"k": k, "v": i})
    return rows


def _dim():
    return [{"k": i, "name": f"n{i}"} for i in range(20)]


def _q(sess, rows, dim, how="inner"):
    a = sess.createDataFrame(rows, num_partitions=4)
    b = sess.createDataFrame(dim, num_partitions=4)
    # keep it a shuffled join (not broadcast)
    return a.join(b, on="k", how=how).orderBy("v")


BASE = {"spark.sql.autoBroadcastJoinThreshold": "-1"}


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti", "right"])
def test_coordinated_coalesce_join(how):
    conf = {**BASE, "spark.sql.adaptive.coalescePartitions.enabled": "true",
            "spark.sql.adaptive.advisoryPartitionSizeInBytes": "4096"}
    tpu = TpuSession({"spark.rapids.sql.enabled": "true", **conf})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false", **BASE})
    rows, dim = _data(400), _dim()
    got = _q(tpu, rows, dim, how).collect()
    want = _q(cpu, rows, dim, how).collect()
    assert got == want
    plan = _q(tpu, rows, dim, how).explain()
    assert "CoordinatedShuffleReader" in plan, plan


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_skew_split_join(how):
    """Tiny threshold/factor force the skewed key's partition to split into
    map slices; results must still match the oracle."""
    conf = {**BASE, "spark.sql.adaptive.skewJoin.enabled": "true",
            "spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes": "512",
            "spark.sql.adaptive.skewJoin.skewedPartitionFactor": "1",
            "spark.sql.adaptive.advisoryPartitionSizeInBytes": "1024"}
    tpu = TpuSession({"spark.rapids.sql.enabled": "true", **conf})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false", **BASE})
    rows, dim = _data(600, skew_frac=0.8), _dim()
    got = _q(tpu, rows, dim, how).collect()
    want = _q(cpu, rows, dim, how).collect()
    assert got == want


def test_skew_split_actually_splits(monkeypatch):
    """Prove slice specs are produced AND executed (not just planned)."""
    from spark_rapids_tpu.shuffle import aqe as aqe_mod
    planned = []
    orig = aqe_mod.JoinReaderCoordinator._plan

    def recording(self, ctx):
        specs = orig(self, ctx)
        planned.append(specs)
        return specs

    monkeypatch.setattr(aqe_mod.JoinReaderCoordinator, "_plan", recording)
    conf = {**BASE, "spark.sql.adaptive.skewJoin.enabled": "true",
            "spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes": "512",
            "spark.sql.adaptive.skewJoin.skewedPartitionFactor": "1",
            "spark.sql.adaptive.advisoryPartitionSizeInBytes": "1024"}
    tpu = TpuSession({"spark.rapids.sql.enabled": "true", **conf})
    rows, dim = _data(600, skew_frac=0.8), _dim()
    _q(tpu, rows, dim, "inner").collect()
    assert planned, "coordinator never planned"
    slices = [s for specs in planned for s in specs if s[0] == "slice"]
    assert slices, planned


def test_collective_skew_split(monkeypatch, collective_spy):
    """ISSUE 16: skew splits on the COLLECTIVE exchange path. The fused
    compact lays each reduce partition out source-contiguously (scatter to
    bases[src]+pos), so map_block_sizes surfaces real per-source sizes
    from the sizing sync and a skewed reduce partition slice-serves — no
    host re-partitioning, results bit-identical to the CPU oracle."""
    from spark_rapids_tpu.shuffle import aqe as aqe_mod
    planned = []
    orig = aqe_mod.JoinReaderCoordinator._plan

    def recording(self, ctx):
        specs = orig(self, ctx)
        planned.append(specs)
        return specs

    monkeypatch.setattr(aqe_mod.JoinReaderCoordinator, "_plan", recording)
    runs = collective_spy
    mesh = {
        "spark.rapids.shuffle.mode": "ICI",
        "spark.rapids.tpu.mesh.enabled": "true",
        "spark.sql.shuffle.partitions": "8",
        "spark.rapids.tpu.dispatch.partitionBatch": "8",
        # the split target is the EXCHANGE; compiled stages would skip it
        "spark.rapids.tpu.agg.compiledStage.enabled": "false",
        "spark.rapids.tpu.join.compiledStage.enabled": "false",
    }
    conf = {**BASE, **mesh,
            "spark.sql.adaptive.skewJoin.enabled": "true",
            "spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes": "512",
            "spark.sql.adaptive.skewJoin.skewedPartitionFactor": "1",
            "spark.sql.adaptive.advisoryPartitionSizeInBytes": "1024"}
    tpu = TpuSession({"spark.rapids.sql.enabled": "true", **conf})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false", **BASE})
    rows, dim = _data(600, skew_frac=0.8), _dim()
    got = _q(tpu, rows, dim, "inner").collect()
    want = _q(cpu, rows, dim, "inner").collect()
    assert got == want
    assert any(runs), "collective data plane never ran"
    slices = [s for specs in planned for s in specs if s[0] == "slice"]
    assert slices, \
        f"no slice specs on the collective path (planned={planned})"


def test_full_outer_never_splits():
    conf = {**BASE, "spark.sql.adaptive.skewJoin.enabled": "true",
            "spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes": "1",
            "spark.sql.adaptive.skewJoin.skewedPartitionFactor": "1"}
    tpu = TpuSession({"spark.rapids.sql.enabled": "true", **conf})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false", **BASE})
    rows, dim = _data(200), _dim()
    got = _q(tpu, rows, dim, "full").collect()
    want = _q(cpu, rows, dim, "full").collect()
    assert got == want
