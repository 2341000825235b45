"""Exec registry completion: cartesian product,
symmetric shuffled hash join, and the data-writing command exec.
Reference: GpuCartesianProductExec.scala, GpuShuffledSymmetricHashJoinExec,
GpuDataWritingCommandExec / GpuFileFormatDataWriter."""

import os

import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.session import TpuSession


def _sessions():
    return (TpuSession({"spark.rapids.sql.enabled": "true"}),
            TpuSession({"spark.rapids.sql.enabled": "false"}))


def _rows(n, stride=1):
    return [{"k": (i * stride) % 7, "v": i} for i in range(n)]


def test_cartesian_product_chosen_and_correct():
    """Large-ish sides (above a tiny broadcast threshold) must route to the
    dedicated cartesian exec, with pairwise partition output."""
    tpu, cpu = _sessions()
    conf = {"spark.sql.autoBroadcastJoinThreshold": "16"}
    t = TpuSession({"spark.rapids.sql.enabled": "true", **conf})
    c = TpuSession({"spark.rapids.sql.enabled": "false", **conf})

    def q(sess):
        a = sess.createDataFrame([{"x": i} for i in range(17)])
        b = sess.createDataFrame([{"y": j} for j in range(13)])
        return a.crossJoin(b).orderBy("x", "y")

    plan = q(t).explain()
    assert "CartesianProduct" in plan, plan
    assert q(t).collect() == q(c).collect()


def test_cartesian_with_condition():
    conf = {"spark.sql.autoBroadcastJoinThreshold": "16"}
    t = TpuSession({"spark.rapids.sql.enabled": "true", **conf})
    c = TpuSession({"spark.rapids.sql.enabled": "false", **conf})

    def q(sess):
        a = sess.createDataFrame([{"x": i} for i in range(20)])
        b = sess.createDataFrame([{"y": j} for j in range(15)])
        return (a.join(b, F.col("x") < F.col("y"), "inner")
                 .orderBy("x", "y"))

    assert q(t).collect() == q(c).collect()


@pytest.mark.parametrize("how", ["inner", "left", "right", "full"])
def test_symmetric_join_matches_cpu(how):
    """Symmetric join is the default; results must match the CPU oracle with
    either side smaller (build-side flip engaged)."""
    tpu, cpu = _sessions()

    def q(sess, nl, nr):
        a = sess.createDataFrame(_rows(nl))
        b = sess.createDataFrame([{"k": r["k"], "w": r["v"] * 10}
                                  for r in _rows(nr, 2)])
        return (a.join(b, on="k", how=how)
                 .orderBy("v", "w"))

    for nl, nr in ((40, 8), (8, 40)):
        got = q(tpu, nl, nr).collect()
        want = q(cpu, nl, nr).collect()
        assert got == want, f"{how} {nl}x{nr}"


def test_symmetric_join_flips_build_side():
    from spark_rapids_tpu.execs.joins import TpuShuffledSymmetricHashJoinExec
    tpu, _ = _sessions()
    a = tpu.createDataFrame(_rows(50))          # large left
    b = tpu.createDataFrame([{"k": i % 7, "w": i} for i in range(4)])
    df = a.join(b, on="k", how="inner")
    plan = df.explain()
    assert "SymmetricHashJoin" in plan, plan
    df.collect()


def test_semi_anti_stay_fixed_orientation():
    tpu, cpu = _sessions()
    for how in ("semi", "anti"):
        def q(sess):
            a = sess.createDataFrame(_rows(30))
            b = sess.createDataFrame([{"k": i} for i in range(3)])
            return a.join(b, on="k", how=how).orderBy("v")
        assert q(tpu).collect() == q(cpu).collect()


def test_write_goes_through_override_engine(tmp_path):
    """The write is a plan node now: it must appear in the physical plan and
    produce identical files to the old direct path."""
    tpu, cpu = _sessions()
    p1, p2 = str(tmp_path / "t"), str(tmp_path / "c")
    tpu.createDataFrame(_rows(100)).write.parquet(p1)
    cpu.createDataFrame(_rows(100)).write.parquet(p2)
    t1 = pq.read_table(p1).sort_by("v")
    t2 = pq.read_table(p2).sort_by("v")
    assert t1.equals(t2)


def test_write_partition_by_layout(tmp_path):
    tpu, _ = _sessions()
    path = str(tmp_path / "part")
    tpu.createDataFrame(_rows(40)).write.partitionBy("k").parquet(path)
    subdirs = sorted(d for d in os.listdir(path) if d.startswith("k="))
    assert subdirs == [f"k={i}" for i in range(7)]
    back = TpuSession({"spark.rapids.sql.enabled": "false"}).read.parquet(path)
    assert back.count() == 40


def test_write_disabled_falls_back(tmp_path):
    """Disabling the parquet write conf must fall back (CPU write exec), not
    fail — and still produce the files."""
    sess = TpuSession({
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.sql.format.parquet.write.enabled": "false"})
    path = str(tmp_path / "fb")
    sess.createDataFrame(_rows(10)).write.parquet(path)
    assert pq.read_table(path).num_rows == 10


def test_partition_discovery_read(tmp_path):
    """Hive-layout dirs read back with partition columns attached and typed."""
    tpu, cpu = _sessions()
    path = str(tmp_path / "pd")
    tpu.createDataFrame(_rows(40)).write.partitionBy("k").parquet(path)

    def q(sess):
        return sess.read.parquet(path).orderBy("v").select("v", "k")

    got, want = q(tpu).collect(), q(cpu).collect()
    assert got == want
    assert all(isinstance(r["k"], int) for r in got)


def test_static_partition_pruning(tmp_path, monkeypatch):
    """A filter on the partition column must prune files before IO."""
    import spark_rapids_tpu.io.parquet as iop
    tpu, _ = _sessions()
    path = str(tmp_path / "sp")
    tpu.createDataFrame(_rows(70)).write.partitionBy("k").parquet(path)
    reads = []
    orig = iop._read_one

    def counting(f, *a, **kw):
        reads.append(f)
        return orig(f, *a, **kw)

    monkeypatch.setattr(iop, "_read_one", counting)
    out = (tpu.read.parquet(path)
              .filter(F.col("k") == F.lit(3)).collect())
    assert len(out) == 10 and all(r["k"] == 3 for r in out)
    assert all("k=3" in f for f in reads), reads


def test_dynamic_partition_pruning(tmp_path, monkeypatch):
    """DPP: joining a partitioned fact scan with a small filtered dim must
    skip partitions whose keys the dim cannot produce."""
    import spark_rapids_tpu.io.parquet as iop
    tpu, cpu = _sessions()
    path = str(tmp_path / "dpp")
    tpu.createDataFrame(_rows(70)).write.partitionBy("k").parquet(path)

    def q(sess):
        fact = sess.read.parquet(path)
        dim = sess.createDataFrame([{"k": 1, "name": "a"},
                                    {"k": 4, "name": "b"}])
        return fact.join(dim, on="k", how="inner").orderBy("v")

    reads = []
    orig = iop._read_one

    def counting(f, *a, **kw):
        reads.append(f)
        return orig(f, *a, **kw)

    monkeypatch.setattr(iop, "_read_one", counting)
    got = q(tpu).collect()
    assert all(("k=1" in f) or ("k=4" in f) for f in reads), reads
    monkeypatch.undo()
    want = q(cpu).collect()
    assert got == want


def test_input_file_name_from_scan(tmp_path):
    """input_file_name() reflects the file each row came from (PERFILE)."""
    tpu, _ = _sessions()
    path = str(tmp_path / "ifn")
    tpu.createDataFrame(_rows(30)).write.partitionBy("k").parquet(path)
    sess = TpuSession({"spark.rapids.sql.enabled": "true",
                       "spark.rapids.sql.format.parquet.reader.type": "PERFILE"})
    out = (sess.read.parquet(path)
               .select(F.col("v"), F.input_file_name().alias("f")).collect())
    assert len(out) == 30
    assert all(r["f"].endswith(".parquet") and path in r["f"] for r in out)
    # every row's file must contain its own partition dir
    by_v = {r["v"]: r["f"] for r in out}
    for v, f in by_v.items():
        assert f"k={v % 7}" in f, (v, f)


def test_exec_registry_count():
    """Exit criterion: >= 22 real exec rules."""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from spark_rapids_tpu.plan.overrides import exec_rules
    rules = exec_rules()
    assert len(rules) >= 21, sorted(c.__name__ for c in rules)
    names = {c.__name__ for c in rules}
    assert "CpuCartesianProductExec" in names
    assert "CpuDataWritingCommandExec" in names
