"""Device-native strings (ISSUE 15 tentpole): BYTE_ARRAY device decode
oracles vs pyarrow, the dictionary-encoded collective exchange (round-trip
bit-identity, chaos healing with encode re-run, overflow fallback), and
the dictionary-coded group keys (string-keyed agg keeps the ONE-launch
traced sort phase).
"""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.chaos import FaultInjector
from spark_rapids_tpu.io import device_decode as dd
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.shuffle.ici import IciShuffleCatalog

N_DEV = 8


@pytest.fixture(autouse=True)
def _clean_state():
    dd.reset_for_tests()
    FaultInjector.reset_for_tests()
    yield
    FaultInjector.reset_for_tests()


def _mesh_conf(**extra):
    base = {
        "spark.rapids.shuffle.mode": "ICI",
        "spark.rapids.tpu.mesh.enabled": "true",
        "spark.sql.shuffle.partitions": str(N_DEV),
        "spark.rapids.tpu.dispatch.partitionBatch": str(N_DEV),
        "spark.sql.autoBroadcastJoinThreshold": "0",
        "spark.rapids.tpu.agg.compiledStage.enabled": "false",
        "spark.rapids.tpu.join.compiledStage.enabled": "false",
        "spark.rapids.sql.batchSizeRows": "1000000",
    }
    base.update(extra)
    return base


def _baseline_conf(**extra):
    base = _mesh_conf(**extra)
    base["spark.rapids.tpu.mesh.enabled"] = "false"
    return base


def _string_table(n=3000, null_every=5, seed=11):
    rng = np.random.default_rng(seed)

    def s(i):
        if null_every and i % null_every == 0:
            return None
        if i % 7 == 1:
            return ""  # empty strings are not nulls
        return f"val{int(rng.integers(0, 40))}" * (i % 3 + 1)

    return pa.table({
        # explicit types: an all-null column (null_every=1) must still be
        # a BYTE_ARRAY string column, not Arrow's null type
        "s": pa.array([s(i) for i in range(n)], pa.string()),
        "b": pa.array([None if null_every and i % null_every == 3
                       else f"b{i % 17}".encode() for i in range(n)],
                      pa.binary()),
        "k": pa.array([f"g{i % 9}" for i in range(n)]),
        "v": pa.array(rng.normal(size=n)),
        "q": pa.array(rng.integers(0, 50, n)),
    })


def _assert_tables_equal(got, ref):
    assert got.num_rows == ref.num_rows
    for c in ref.column_names:
        a = got.column(c).combine_chunks()
        b = ref.column(c).combine_chunks()
        if a.type != b.type:
            a = a.cast(b.type)
        assert a.equals(b), f"column {c} differs"


# ---------------------------------------------------------------------------
# device BYTE_ARRAY decode: oracles vs pyarrow, zero scan fallbacks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("null_every", [0, 2, 1])
def test_byte_array_dictionary_oracle(tmp_path, null_every):
    """RLE_DICTIONARY string/binary pages at 0%/50%/100% nulls, multi-page
    chunks — bit-identical vs pyarrow, zero per-column fallbacks."""
    t = _string_table(2500, null_every=null_every)
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, compression="snappy", row_group_size=900,
                   data_page_size=400)
    got = TpuSession({}).read.parquet(p).to_arrow()
    _assert_tables_equal(got, pq.read_table(p))
    st = dd.decode_stats()
    assert st["fallback_columns"] == 0
    assert st["dispatches"] == 3


def test_byte_array_plain_oracle(tmp_path):
    """PLAIN (non-dictionary) BYTE_ARRAY pages: the 4-byte length-prefix
    walk + device cumsum/gather path, incl. empty strings and nulls."""
    t = _string_table(2200, null_every=4)
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, use_dictionary=False, compression="snappy",
                   row_group_size=800, data_page_size=600)
    got = TpuSession({}).read.parquet(p).to_arrow()
    _assert_tables_equal(got, pq.read_table(p))
    assert dd.decode_stats()["fallback_columns"] == 0


def test_byte_array_v2_pages_oracle(tmp_path):
    t = _string_table(1800, null_every=3)
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, compression="zstd", data_page_version="2.0",
                   row_group_size=700, data_page_size=300)
    got = TpuSession(
        {"spark.rapids.tpu.parquet.deviceDecode.verify": "true"}
    ).read.parquet(p).to_arrow()
    _assert_tables_equal(got, pq.read_table(p))
    st = dd.decode_stats()
    assert st["fallback_columns"] == 0 and st["fallback_row_groups"] == 0


def test_scan_dict_encoding_attached(tmp_path):
    """Dictionary-page string columns surface the parquet dictionary as a
    device dict_encoding: codes + dictionary reproduce the column."""
    from spark_rapids_tpu.config import default_conf
    from spark_rapids_tpu.io.device_decode import DeviceFileDecoder
    from spark_rapids_tpu.types import DoubleType, StringType
    t = _string_table(1500, null_every=6)
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, row_group_size=1500)

    class A:
        def __init__(self, name, dt):
            self.name, self.dtype, self.nullable = name, dt, True

    with DeviceFileDecoder(p, [A("k", StringType()),
                               A("v", DoubleType())],
                           default_conf()) as dec:
        batch = dec.decode_row_group(0)
        col = batch.columns[0]
        de = getattr(col, "dict_encoding", None)
        assert de is not None
        codes, dcol = de
        codes_np = np.asarray(codes)[: batch.num_rows]
        dvals = dcol.to_arrow().to_pylist()
        svals = col.to_arrow().to_pylist()
        assert len(set(dvals)) == len(dvals)  # dictionary duplicate-free
        for i, v in enumerate(svals):
            if v is not None:
                assert dvals[codes_np[i]] == v


def test_chaos_scan_read_string_chunks_heal(tmp_path):
    """Chaos scan.read corrupt/truncate on a string-bearing file heals via
    host fallback, never wrong data."""
    t = _string_table(2000, null_every=5)
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, compression="snappy", row_group_size=700)
    ref = pq.read_table(p)
    inj = FaultInjector.get()
    inj.force("scan.read", "truncate", 2)
    got = TpuSession({}).read.parquet(p).to_arrow()
    _assert_tables_equal(got, ref)
    assert inj.injection_count() == 2


# ---------------------------------------------------------------------------
# dictionary-encoded collective exchange
# ---------------------------------------------------------------------------


def _string_agg_query(s, t):
    return (s.createDataFrame(t, num_partitions=N_DEV)
            .groupBy("k")
            .agg(F.sum(F.col("v")).alias("sv"),
                 F.count(F.col("q")).alias("c"),
                 F.max(F.col("s")).alias("ms")))


def _sorted_pylist(table):
    return table.sort_by([(n, "ascending")
                          for n in table.column_names]).to_pylist()


def test_dict_exchange_round_trip_bit_identical():
    """Mesh session (string payloads ride as codes + one broadcast
    dictionary) vs single-device baseline: bit-identical incl. float bit
    patterns, collective launches recorded, zero per-map exchanges."""
    from spark_rapids_tpu.obs import mesh_profile
    from spark_rapids_tpu.parallel.mesh import collective_stats
    t = _string_table(4000, null_every=7, seed=29)
    before = collective_stats()
    seq0 = mesh_profile.current_seq()
    s1 = TpuSession(_mesh_conf())
    r1 = _string_agg_query(s1, t).to_arrow()
    after = collective_stats()
    assert after["launches"] - before["launches"] >= 1
    assert after["dict_exchanges"] - before["dict_exchanges"] >= 1
    assert after["dict_encode_ns"] - before["dict_encode_ns"] > 0
    assert not mesh_profile.fallbacks_since(seq0)  # zero per-map
    s2 = TpuSession(_baseline_conf())
    r2 = _string_agg_query(s2, t).to_arrow()
    a = r1.sort_by([("k", "ascending")])
    b = r2.sort_by([("k", "ascending")])
    assert a.column("k").to_pylist() == b.column("k").to_pylist()
    assert a.column("ms").to_pylist() == b.column("ms").to_pylist()
    assert a.column("c").to_pylist() == b.column("c").to_pylist()
    av = np.array(a.column("sv").to_pylist(), np.float64)
    bv = np.array(b.column("sv").to_pylist(), np.float64)
    assert np.array_equal(av.view(np.int64), bv.view(np.int64))


def test_dict_exchange_chaos_lost_shard_rebuilds_encode():
    """Chaos mesh.shard (lost peer) on a dictionary-encoded exchange:
    lineage recovery re-runs the whole collective INCLUDING the encode
    pass — results stay bit-identical and the encode counter shows the
    re-run."""
    from spark_rapids_tpu.parallel.mesh import collective_stats
    t = _string_table(2500, null_every=6, seed=31)
    clean = _sorted_pylist(_string_agg_query(TpuSession(_mesh_conf()),
                                             t).to_arrow())
    IciShuffleCatalog.reset_for_tests()
    before = collective_stats()
    inj = FaultInjector.get()
    inj.force("mesh.shard", "io_error", 1)
    try:
        got = _sorted_pylist(_string_agg_query(TpuSession(_mesh_conf()),
                                               t).to_arrow())
    finally:
        inj.clear_forced()
    assert got == clean
    assert any(r["site"] == "mesh.shard" for r in inj.trace())
    # the heal re-ran the encode: at least exchange + recovery encodes
    assert collective_stats()["dict_exchanges"] \
        - before["dict_exchanges"] >= 2


def test_dict_exchange_chaos_shuffle_read_soak():
    """Seeded chaos at shuffle.read/mesh.shard with a string payload in
    play: bit-identical to the clean run."""
    t = _string_table(2000, null_every=5, seed=33)
    clean = _sorted_pylist(_string_agg_query(TpuSession(_mesh_conf()),
                                             t).to_arrow())
    IciShuffleCatalog.reset_for_tests()
    chaos = _mesh_conf(**{
        "spark.rapids.tpu.test.chaos.enabled": "true",
        "spark.rapids.tpu.test.chaos.seed": "77",
        "spark.rapids.tpu.test.chaos.sites": "shuffle.read,mesh.shard",
        "spark.rapids.tpu.test.chaos.probability": "0.25",
        "spark.rapids.tpu.deviceRetry.backoffBaseMs": "1",
        "spark.rapids.tpu.deviceRetry.backoffMaxMs": "4",
    })
    got = _sorted_pylist(_string_agg_query(TpuSession(chaos),
                                           t).to_arrow())
    assert got == clean


def test_dict_exchange_overflow_falls_back_per_map():
    """Past the cardinality guard the exchange declines with the NEW
    reason `dictionary_overflow` (burndown honesty: bundle counter +
    explain("metrics")) and the per-map path still answers correctly."""
    from spark_rapids_tpu.obs import mesh_profile
    t = _string_table(1500, null_every=0, seed=37)
    seq0 = mesh_profile.current_seq()
    s = TpuSession(_mesh_conf(**{
        "spark.rapids.tpu.exchange.dictionaryEncode.maxCardinality": "2"}))
    got = _string_agg_query(s, t).to_arrow()
    ref = _string_agg_query(TpuSession(_baseline_conf()), t).to_arrow()
    assert _sorted_pylist(got) == _sorted_pylist(ref)
    reasons = [f["reason"] for f in mesh_profile.fallbacks_since(seq0)]
    assert "dictionary_overflow" in reasons
    rendered = s.explain("metrics")
    assert "per_map=dictionary_overflow" in rendered


def test_dict_exchange_conf_off_keeps_per_map_reason():
    from spark_rapids_tpu.obs import mesh_profile
    t = _string_table(1200, seed=41)
    seq0 = mesh_profile.current_seq()
    s = TpuSession(_mesh_conf(**{
        "spark.rapids.tpu.exchange.dictionaryEncode.enabled": "false"}))
    _string_agg_query(s, t).to_arrow()
    reasons = [f["reason"] for f in mesh_profile.fallbacks_since(seq0)]
    assert "string_or_nested_payload" in reasons


# ---------------------------------------------------------------------------
# dictionary-coded group keys: string-keyed agg stays device-resident
# ---------------------------------------------------------------------------


def test_string_keyed_agg_dispatch_count(tmp_path):
    """A string-keyed aggregation over a device-decoded scan runs its
    sort phase as ONE traced launch (opjit kind "aggsort") — the codes
    from the parquet dictionary feed the key-encode program directly
    instead of splitting to the eager per-op chain at the string key."""
    from spark_rapids_tpu.execs import opjit
    t = _string_table(3000, null_every=8, seed=43)
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, row_group_size=3000)
    s = TpuSession({"spark.rapids.tpu.agg.compiledStage.enabled": "false"})
    q = (s.read.parquet(p).groupBy("k")
         .agg(F.sum(F.col("v")).alias("sv"),
              F.count(F.col("q")).alias("c")))
    before = dict(opjit.cache_stats()["calls_by_kind"])
    got = q.to_arrow().sort_by("k")
    after = opjit.cache_stats()["calls_by_kind"]
    assert after.get("aggsort", 0) - before.get("aggsort", 0) >= 1
    ref = (t.group_by(["k"]).aggregate([("v", "sum"), ("q", "count")])
           .rename_columns(["k", "sv", "c"]).sort_by("k"))
    assert got.column("k").to_pylist() == ref.column("k").to_pylist()
    assert got.column("c").to_pylist() == ref.column("c").to_pylist()
    a = np.array(got.column("sv").to_pylist(), np.float64)
    b = np.array(ref.column("sv").to_pylist(), np.float64)
    assert np.allclose(a, b)


def test_encode_group_keys_consumes_dict_encoding():
    """encode_group_keys uses attached codes directly (no host
    dictionary pass) and groups identically to the host encode."""
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.vector import TpuColumnVector
    from spark_rapids_tpu.execs.aggregates import encode_group_keys
    from spark_rapids_tpu.types import StringType
    vals = ["a", "b", "a", None, "c", "b"]
    col = TpuColumnVector.from_arrow(pa.array(vals))
    host_enc = encode_group_keys([col], len(vals), col.capacity)
    # attach a device encoding and re-encode: codes must induce the SAME
    # grouping (equal rows ↔ equal codes under equal validity)
    dcol = TpuColumnVector.from_arrow(pa.array(["a", "b", "c"]))
    codes = np.zeros(col.capacity, np.int32)
    codes[:6] = [0, 1, 0, 0, 2, 1]
    col.dict_encoding = (jnp.asarray(codes), dcol)
    dev_enc = encode_group_keys([col], len(vals), col.capacity)
    hv = np.asarray(host_enc[0][0])[:6]
    dv = np.asarray(dev_enc[0][0])[:6]
    valid = np.array([v is not None for v in vals])

    def same(v, i, j):  # grouping equality = (validity, value-if-valid)
        if valid[i] != valid[j]:
            return False
        return not valid[i] or v[i] == v[j]

    for i in range(6):
        for j in range(6):
            assert same(hv, i, j) == same(dv, i, j), (i, j)
