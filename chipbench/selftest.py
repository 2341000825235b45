"""Self-tests of the yardstick, on the CPU, in about a minute:

    python -m chipbench.selftest [-k substring]

The trace reduction on a small trace recorded on the chip, the roofline's byte
functions on hand-worked shapes, percentile and open-loop arithmetic, each plain
reference against a hand-checked table, the manifest against the allowed names,
a dummy configuration / mix / metric / reader / template and the three-table star
rehearsal (testdata/tpch-star-resident.json, Q3) added in a temporary copy without
editing a file, the generated data and the schedules of the accepted cells against
hashes recorded from the tree that made them first, the tables of both one-chip
star configurations against hashes recorded before the generator learnt the
`text` kind, that kind's
formatting, streaming, Arrow and Parquet forms and refusals, a two-table
configuration with formatted names (testdata/tpch-names.json, a join grouped by
`c_name`) added in a temporary copy and run whole, the 80 Q6 parameter sets
(traffic/q6-params.json, a cell added in a temporary copy as well) against the reference,
the lower-precision control failing the limit, and whole runs (the look for a chip
skipped) with the timed path broken underneath, each of which must come out not
correct.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import textwrap

os.environ["JAX_PLATFORMS"] = "cpu"          # before anything imports jax

import numpy as np  # noqa: E402

from . import check, datagen, loadgen, manifest, roofline, stats  # noqa: E402
from . import trace as tm  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


# --- trace reduction -------------------------------------------------------

def test_union_and_clip():
    s, e = tm.union(np.array([0., 5., 20., 22.]), np.array([10., 15., 30., 25.]))
    assert s.tolist() == [0., 20.] and e.tolist() == [15., 30.]
    s, e = tm.clip(s, e, 12., 24.)
    assert s.tolist() == [12., 20.] and e.tolist() == [15., 24.]


def test_innermost_at():
    ln = tm.Line("/host:CPU", "python", ["outer", "a", "b"],
                 np.array([0., 1., 5.]), np.array([10., 3., 9.]))
    assert tm.innermost_at(ln, np.array([0.5, 2., 4., 6., 11.])) == [0, 1, 0, 2, -1]


def test_recorded_trace():
    """chipbench/testdata/small.xplane.pb (recorded on a TPU v5e by
    record_small_trace.py): three launches of one program, a 50 ms sleep under
    a `host.sleep` span, two launches of another; the device clock runs about
    0.8 ms ahead of the host's, so two launches fall before the window span."""
    r = tm.reduce(tm.load(os.path.join(HERE, "testdata", "small.xplane.pb")))
    assert r["devices"] == 1 and r["ops_line"] == ["XLA Ops"]
    assert abs(r["window_s"] - 0.053901619) < 1e-12
    assert r["launches"] == 3
    # programs in the window: 2985 + 1342 + 664 ns; their operations' union a little less
    assert abs(r["device_ops"][0][1] - 4.991e-6) < 1e-12
    assert abs(r["busy_s"] - 4.963e-6) < 1e-12 and r["busy_s"] <= r["device_ops"][0][1]
    assert abs(r["idle_share"] - (1 - 4.963e-6 / 0.053901619)) < 1e-12
    name, secs = r["idle_gaps"][0]
    assert name == "host.sleep" and 0.050 < secs < 0.053
    assert abs(sum(g[1] for g in r["idle_gaps"]) + r["busy_s"] - r["window_s"]) < 1e-9


# --- roofline ---------------------------------------------------------------

def test_resident_bytes():
    from .queries import q1, q6
    li = datagen.tables({"rows": 1000, "storage": "resident", "columns": ["l_tax"]})["lineitem"]
    assert roofline.resident_bytes(li, q6.COLUMNS["lineitem"], 1000) == 1000 * (4 + 8 + 4 + 8)
    assert roofline.resident_bytes(li, q1.COLUMNS["lineitem"], 10) == \
        10 * (4 + 1 + 1 + 4 + 8 + 8 + 8)
    star = datagen.tables(manifest._json(os.path.join(HERE, "testdata",
                                                       "tpch-star-resident.json")))
    # BIGINT keys 8, DATE and INT 4, CHAR(10) for the longest market segment
    assert roofline.resident_bytes(star["orders"], ("o_orderkey", "o_custkey", "o_orderdate",
                                                    "o_shippriority"), 3) == 3 * (8 + 8 + 4 + 4)
    assert roofline.resident_bytes(star["customer"], ("c_mktsegment",), 2) == 2 * 10


def test_parquet_bytes_and_peaks():
    import pyarrow.parquet as pq
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.parquet")
        datagen.write_parquet(path, 5, 3000, keep=())
        md = pq.ParquetFile(path).metadata
        want = sum(md.row_group(0).column(i).total_uncompressed_size
                   for i in range(md.num_columns)
                   if md.row_group(0).column(i).path_in_schema in ("l_tax", "l_shipdate"))
        assert md.num_row_groups == 1 and want > 3000 * 8
        assert roofline.parquet_bytes(md, ("l_tax", "l_shipdate")) == want
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert abs(roofline.least_seconds(819_000_000, "TPU v5 lite") - 1e-3) < 1e-15
    for kind in ("cpu", "_source", "TPU v9"):
        try:
            roofline.peaks(kind)
        except KeyError:
            continue
        raise AssertionError(f"peaks({kind!r}) should be an error")


# --- the generator ------------------------------------------------------------

def _sha(arrays) -> str:
    m = hashlib.sha256()
    for a in arrays:
        m.update(np.ascontiguousarray(a).view(np.uint8).tobytes())
    return m.hexdigest()


def _config(name: str) -> dict:
    return manifest._json(os.path.join(HERE, "configs", name + ".json"))


def test_accepted_data_equals_the_parents():
    """testdata/parent_hashes.json was recorded from PR 25's tree, before the
    generator learnt tables: every LINEITEM column, the Parquet file (bytes and
    column-chunk sizes) and the eight tenant slices of the resident cache, for
    two seeds at 2.5 M rows."""
    import pyarrow.parquet as pq
    want = manifest._json(os.path.join(HERE, "testdata", "parent_hashes.json"))
    rows = want["rows"]
    for seed in want["seeds"]:
        li = datagen.tables(_config("tpch-sf1-parquet"), rows)["lineitem"]
        assert li.storage == "parquet" and list(li.columns) == list(datagen.LINEITEM)
        whole = li.generate(seed, list(li.columns))
        assert {k: _sha([v]) for k, v in whole.items()} == want["parquet"][str(seed)]["columns"]
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "t.parquet")
            kept = li.write_parquet(path, seed, keep=("l_tax", "l_shipmode"))
            assert np.array_equal(kept["l_tax"], whole["l_tax"])          # streamed = whole
            assert kept["l_shipmode"][0] == datagen.SHIPMODES[whole["l_shipmode"][0]].encode()
            md = pq.ParquetFile(path).metadata
            chunks = [[rg, md.row_group(rg).column(c).path_in_schema,
                       md.row_group(rg).column(c).total_compressed_size,
                       md.row_group(rg).column(c).total_uncompressed_size]
                      for rg in range(md.num_row_groups) for c in range(md.num_columns)]
            assert hashlib.sha256(json.dumps(chunks).encode()).hexdigest() == \
                want["parquet"][str(seed)]["chunks"]
            with open(path, "rb") as f:
                assert hashlib.sha256(f.read()).hexdigest() == want["parquet"][str(seed)]["file"]
        res = datagen.tables(_config("tpch-sf10-resident"), rows)["lineitem"]
        assert res.storage == "resident" and len(res.cached) == 7
        got = [_sha([res.generate(seed, res.cached, lo, hi)[k] for k in res.cached])
               for lo, hi in datagen.tenant_slices(rows, 8)]
        assert got == want["resident"][str(seed)]


def _table_digests(config: dict, rows: int, seed: int) -> dict:
    """{table: {"columns": {column: sha}, "stored": sha}} of a configuration's
    tables at `rows`: every column drawn whole, and what the program is handed,
    the Parquet file's bytes or the Arrow stream of a resident cache."""
    import pyarrow as pa
    out = {}
    for name, table in datagen.tables(config, rows).items():
        whole = table.generate(seed, list(table.columns))
        d = {"columns": {k: _sha([v]) for k, v in whole.items()}}
        if table.storage == "parquet":
            with tempfile.TemporaryDirectory() as tmp:
                path = os.path.join(tmp, "t.parquet")
                table.write_parquet(path, seed)
                with open(path, "rb") as f:
                    d["stored"] = hashlib.sha256(f.read()).hexdigest()
        else:
            arrow = table.to_arrow(table.generate(seed, table.cached))
            sink = pa.BufferOutputStream()
            with pa.ipc.new_stream(sink, arrow.schema) as w:
                w.write_table(arrow)
            d["stored"] = hashlib.sha256(sink.getvalue().to_pybytes()).hexdigest()
        out[name] = d
    return out


def test_accepted_star_tables_equal_the_parents():
    """Every table of both one-chip star configurations as the generator drew
    them before it learnt the `text` kind, at 2.5 M lineitem rows, two seeds
    (testdata/parent_hashes.json `star`): each column whole, the Parquet files
    byte for byte, the resident caches' Arrow streams."""
    want = manifest._json(os.path.join(HERE, "testdata", "parent_hashes.json"))["star"]
    for name in want["configs"]:
        for seed in want["seeds"]:
            got = _table_digests(_config(name), want["rows"], seed)
            assert got == want["configs"][name][str(seed)], (name, seed)


def test_accepted_schedules_equal_the_parents():
    want = manifest._json(os.path.join(HERE, "testdata", "parent_hashes.json"))
    for key, digest in want["traffic"].items():
        name, seed = key.rsplit(".", 1)
        t = manifest._json(os.path.join(HERE, "traffic", name + ".json"))
        if t["loop"] == "closed":
            seq = loadgen.closed_order(t, int(seed), 0)
        else:
            seq = [(r.tenant, r.template, round(r.due, 9))
                   for r in loadgen.open_schedule(t, 40.0, int(seed))]
        assert hashlib.sha256(json.dumps(seq).encode()).hexdigest() == digest, key


# --- the text kind ------------------------------------------------------------

C_NAME = ["text", "Customer#%09d", "c_custkey", {"plus": 1}]


def _one_table(columns: dict, rows: int) -> datagen.Table:
    return datagen.tables({"table": "t", "rows": rows, "tables": {
        "t": {"rows": rows, "storage": "parquet", "columns": columns}}})["t"]


def test_text_prints_as_dbgen():
    """dbgen's numbers at both ends of nine digits, and a prefix, a field and a
    suffix against Python's own formatting on every row."""
    t = _one_table({"c_custkey": ["seq"], "c_name": C_NAME}, 3)
    assert t.generate(5, ["c_name"])["c_name"].tolist() == [
        b"Customer#000000001", b"Customer#000000002", b"Customer#000000003"]
    assert t.width("c_name") == 18 and t.generate(5, ["c_name"])["c_name"].dtype == "S18"
    t = _one_table({"c_custkey": ["int", 999_999_998, 999_999_998], "c_name": C_NAME}, 2)
    assert t.generate(5, ["c_name"])["c_name"].tolist() == [b"Customer#999999999"] * 2
    t = _one_table({"k": ["int", 0, 99_999], "s": ["text", "x%05d-y", "k"]}, 50_000)
    g = t.generate(6, ["k", "s"])
    assert g["s"].tolist() == [b"x%05d-y" % k for k in g["k"].tolist()] and t.width("s") == 8


def test_text_whole_sliced_streamed_and_read_back():
    """A unique c_name over two row groups: the same whole, in two slices that
    cut a chunk and as the streamed file; a REQUIRED Arrow string; the file read
    back through pyarrow equal to what the reference keeps. The writer is the
    harness's (snappy, REQUIRED, dictionary on), so the chunk starts as a dictionary and goes on in PLAIN once the 1 MiB
    dictionary page is full, as dbgen's c_name does under Spark's writer."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    rows = datagen.CHUNK + 100_000
    t = _one_table({"c_custkey": ["seq"], "c_name": C_NAME}, rows)
    whole = t.generate(2**31 + 3, ["c_name"])["c_name"]
    cut = rows // 3
    parts = [t.generate(2**31 + 3, ["c_name"], lo, hi)["c_name"]
             for lo, hi in ((0, cut), (cut, rows))]
    assert np.array_equal(np.concatenate(parts), whole)
    arrow = t.to_arrow({"c_name": whole[:4]})
    assert arrow.schema.field("c_name").type == pa.string()
    assert not arrow.schema.field("c_name").nullable
    assert arrow.column("c_name").to_pylist() == [f"Customer#{k:09d}" for k in range(1, 5)]
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.parquet")
        kept = t.write_parquet(path, 2**31 + 3, keep=("c_name",))["c_name"]
        assert np.array_equal(kept, whole) and kept.dtype == "S18"
        back = pq.read_table(path).column("c_name").to_pylist()
        assert back == [v.decode() for v in kept.tolist()]
        md = pq.ParquetFile(path).metadata
        assert md.num_row_groups == 2 and md.schema.column(1).max_definition_level == 0
        chunk = md.row_group(0).column(1)
        assert chunk.has_dictionary_page and \
            sorted(chunk.encodings) == ["PLAIN", "RLE", "RLE_DICTIONARY"], chunk.encodings


def test_appended_text_leaves_the_other_draws():
    """c_name appended to CUSTOMER and o_clerk (with the INT it prints) to
    ORDERS of the star configuration from files: every column that was there
    draws what the parent drew."""
    want = manifest._json(os.path.join(HERE, "testdata", "parent_hashes.json"))["star"]
    conf = _config("tpch-sf1-star-parquet")
    conf["tables"]["customer"]["columns"]["c_name"] = C_NAME
    orders = conf["tables"]["orders"]["columns"]
    orders["o_clerkkey"] = ["int", 1, 1000]
    orders["o_clerk"] = ["text", "Clerk#%09d", "o_clerkkey"]
    seed = want["seeds"][-1]
    parent = want["configs"]["tpch-sf1-star-parquet"][str(seed)]
    for name, table in datagen.tables(conf, want["rows"]).items():
        old = list(parent[name]["columns"])
        assert list(table.columns)[:len(old)] == old
        got = table.generate(seed, old)
        assert {k: _sha([got[k]]) for k in old} == parent[name]["columns"], name
    names = datagen.tables(conf, want["rows"])["orders"].generate(seed, ["o_clerk"])["o_clerk"]
    assert np.sort(names)[[0, -1]].tolist() == [b"Clerk#000000001", b"Clerk#000001000"]


def test_text_specs_that_are_refused():
    """Each refused at load, with the column named: a source not in the
    table, a source that is no integer, a second % field, a field without its
    zero, keys that print wider than the field (150,000 customers), and a
    fourth element that is not {"plus": k}."""
    cases = {
        "missing source": ["text", "Customer#%09d", "c_nokey"],
        "not an integer": ["text", "Customer#%09d", "c_acctbal"],
        "a string source": ["text", "Customer#%09d", "c_mktsegment"],
        "second field": ["text", "Customer#%09d%d", "c_custkey"],
        "two padded fields": ["text", "%05d-%05d", "c_custkey"],
        "no zero": ["text", "Customer#%9d", "c_custkey"],
        "too wide": ["text", "Customer#%05d", "c_custkey"],
        "too wide by plus": ["text", "Customer#%06d", "c_custkey", {"plus": 850_001}],
        "below zero": ["text", "Customer#%09d", "c_custkey", {"plus": -1}],
        "unknown option": ["text", "Customer#%09d", "c_custkey", {"pluss": 1}],
        "options not a map": ["text", "Customer#%09d", "c_custkey", 1],
    }
    for case, spec in cases.items():
        conf = _config("tpch-sf1-star-parquet")
        conf["tables"]["customer"]["columns"]["c_name"] = spec
        try:
            datagen.tables(conf)
        except ValueError as e:
            assert "customer.c_name" in str(e), (case, e)
            continue
        raise AssertionError(f"{case}: {spec} was taken")
    conf["tables"]["customer"]["columns"]["c_name"] = \
        ["text", "Customer#%06d", "c_custkey", {"plus": 850_000}]
    assert datagen.tables(conf)["customer"].width("c_name") == 15


def _star_config() -> dict:
    return manifest._json(os.path.join(HERE, "testdata", "tpch-star-resident.json"))


def test_tables_keys_and_slices():
    """The star rehearsal's tables at 3 M lineitem rows: sizes by ratio, a
    dense primary key, foreign keys inside their domain with a third of the
    customers never drawn, and the same table whole or in slices."""
    full = datagen.tables(_star_config())
    assert [full[n].rows for n in ("customer", "orders", "lineitem")] == \
        [1_500_000, 15_000_000, 59_986_052]
    t = datagen.tables(_star_config(), 3_000_000)
    cust, orders, li = t["customer"], t["orders"], t["lineitem"]
    assert (cust.rows, orders.rows) == (75_017, 750_174)
    o = orders.generate(11, list(orders.columns))
    assert np.array_equal(o["o_orderkey"], np.arange(orders.rows))
    assert o["o_orderkey"].dtype == np.int64
    drawn = np.unique(o["o_custkey"])
    assert 0 <= drawn[0] and drawn[-1] < cust.rows
    assert abs((cust.rows - len(drawn)) / cust.rows - 1 / 3) < 0.001
    assert set(np.unique(o["o_shippriority"])) == {0} and o["o_orderdate"].max() < 10441
    parts = [li.generate(11, ["l_orderkey", "l_discount"], lo, hi)
             for lo, hi in datagen.tenant_slices(li.rows, 3)]
    whole = li.generate(11, ["l_orderkey", "l_discount"])
    for k in whole:
        assert np.array_equal(np.concatenate([p[k] for p in parts]), whole[k])
    assert whole["l_orderkey"].min() >= 0 and whole["l_orderkey"].max() < orders.rows
    # a lineitem ships 1..121 days after its order, whole or sliced
    ship = li.generate(11, ["l_shipdate"], 1_000_000, 2_200_000)["l_shipdate"]
    gap = ship - o["o_orderdate"][whole["l_orderkey"][1_000_000:2_200_000]]
    assert (gap.min(), gap.max()) == (1, 121) and li.width("l_shipdate") == 4
    # another table's column in the same place draws other values; another seed too
    assert not np.array_equal(whole["l_discount"][:1000],
                              li.generate(12, ["l_discount"], 0, 1000)["l_discount"])


# --- arithmetic -------------------------------------------------------------

def test_percentile_spread_apportion():
    assert stats.percentile([4, 1, 3, 2], 0.5) == 2.5
    assert stats.percentile(list(range(101)), 0.95) == 95
    assert stats.percentile([7], 0.95) == 7
    assert stats.spread([1, 2, 3, 4, 5, 6]) == (5.25 - 1.75) / 3.5
    assert stats.apportion([3, 1], 7) == [5, 2] and sum(stats.apportion([1, 1, 1], 10)) == 10


TRAFFIC = {"loop": "open", "tenants": 4, "tenant_zipf": 1.0, "rate_per_s": 25.0,
           "templates": [{"query": "q6", "share": 0.75}, {"query": "q1", "share": 0.25}]}


def test_open_schedule():
    a = loadgen.open_schedule(TRAFFIC, 8.0, 1)
    b = loadgen.open_schedule(TRAFFIC, 8.0, 2**31 + 5)
    assert len(a) == len(b) == 200
    due = [r.due for r in a]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 8.0
    # the same work and the same gaps for every seed, rotated to another start
    kinds = lambda recs: sorted((r.tenant, r.template) for r in recs)  # noqa: E731
    gaps = lambda recs: sorted(np.diff([0.0] + [r.due for r in recs]).round(9))  # noqa: E731
    assert kinds(a) == kinds(b) and gaps(a) == gaps(b)
    seq = lambda recs: [(r.tenant, r.template) for r in recs]  # noqa: E731
    assert seq(a) != seq(b)
    k = next(k for k in range(200) if seq(a)[k:] + seq(a)[:k] == seq(b))
    assert np.allclose(np.roll(np.diff([0.0] + [r.due for r in a]), -k),
                       np.diff([0.0] + [r.due for r in b]))
    # tenant 0 of Zipf(1) over 4 gets 12/25 of the queries, three quarters of them q6
    assert sum(r.tenant == 0 for r in a) == 96
    assert sum(r.tenant == 0 and r.template == 0 for r in a) == 72
    # exponential gaps: the mean is 1/rate, the largest several times that
    g = np.diff([0.0] + due)
    assert abs(g.mean() - 8.0 / 201) < 1e-9 and g.max() > 4 * g.mean()


def test_gen_late_and_latency_readers():
    from .readers import gen_late_percentile, latency_percentile, rows_per_s

    class Ctx:
        pass
    ctx = Ctx()
    ctx.cell = type("C", (), {"traffic": {"templates": [{"class": "interactive"},
                                                         {"class": "batch"}]}})()
    R = loadgen.Record
    ctx.records = [R(0, 0, due=1.0, sent=1.001, done=1.5, free_at=0.0),
                   R(0, 1, due=1.1, sent=1.502, done=2.0, free_at=1.5),
                   R(0, 0, due=3.0, sent=3.0, done=3.2, free_at=2.0, failed=True)]
    ctx.rows_of = {(0, 0): 1000, (0, 1): 1000}
    ctx.completed = lambda: [r for r in ctx.records if not r.failed]
    assert abs(gen_late_percentile.read(ctx, q=1.0) - 2.0) < 1e-9       # 1.502 - 1.5
    # the failed query counts as the slowest seen (900 ms), never as its own 200 ms
    assert abs(latency_percentile.read(ctx, q=1.0) - 900.0) < 1e-9
    assert abs(latency_percentile.read(ctx, q=0.5, slo_class="interactive") - 500.0) < 1e-9
    assert abs(rows_per_s.read(ctx) - 2000 / 2.0) < 1e-9


# --- references -------------------------------------------------------------

def tiny_table() -> dict:
    return {"lineitem": {
        "l_shipdate": np.array([8766, 9130, 9131, 8800, 8800, 10471, 10472], np.int32),
        "l_discount": np.array([0.05, 0.07, 0.06, 0.04, 0.06, 0.06, 0.06]),
        "l_quantity": np.array([23, 1, 1, 1, 24, 10, 10], np.int32),
        "l_extendedprice": np.array([100.0, 200.0, 300.0, 400.0, 500.0, 600.0, 700.0]),
        "l_tax": np.array([0.0, 0.5, 0.0, 0.0, 0.0, 0.25, 0.0]),
        "l_returnflag": np.array([b"R", b"A", b"A", b"R", b"R", b"A", b"N"], "S1"),
        "l_linestatus": np.array([b"O", b"F", b"F", b"O", b"O", b"F", b"O"], "S1"),
    }}


def test_q6_reference():
    from .queries import q6
    # rows 0 and 1 pass: date in [8766, 9131), discount in [0.05, 0.07], quantity < 24
    assert q6.reference(tiny_table()) == [{"revenue": 100.0 * 0.05 + 200.0 * 0.07}]
    empty = {"lineitem": {k: v[:0] for k, v in tiny_table()["lineitem"].items()}}
    assert q6.reference(empty) == [{"revenue": None}]


def test_q6_literals():
    from .queries import q6
    assert q6.literals() == (8766, 9131, 0.05, 0.07, 24)          # PR 23's constants
    assert q6.literals(1996, 0.09, 25) == (9496, 9862, 0.08, 0.1, 25)
    t = tiny_table()
    # a year earlier nothing ships; quantity 25 lets the row of quantity 24 in
    assert q6.reference(t, year=1993, discount=0.06, quantity=24) == [{"revenue": None}]
    assert q6.reference(t, year=1994, discount=0.06, quantity=25) == \
        [{"revenue": 100.0 * 0.05 + 200.0 * 0.07 + 500.0 * 0.06}]


def test_q6_parameter_sets():
    """traffic/q6-params.json: the 80 sets of clause 2.4.6.3, each answered by
    the program on the CPU at 2^16 rows as the reference answers it, and no two
    alike."""
    from . import engine
    with tempfile.TemporaryDirectory() as d:
        _with_q6_params(d)
        assert manifest.validate(d) == [], manifest.validate(d)
        cell = manifest.Cell("parquet-q6-params", d)
    sets = _q6_sets()
    assert len(sets) == 80 == len({json.dumps(p, sort_keys=True) for p in sets})
    assert {p["year"] for p in sets} == set(range(1993, 1998)) \
        and {p["quantity"] for p in sets} == {24, 25} \
        and sorted({p["discount"] for p in sets}) == [round(0.01 * i, 2) for i in range(2, 10)]
    assert all(t["share"] == 1.0 for t in cell.traffic["templates"])
    order = loadgen.closed_order(cell.traffic, 5, 0)
    assert len({q for _, q in order[:400]}) == 80
    assert order != loadgen.closed_order(cell.traffic, 6, 0)
    with tempfile.TemporaryDirectory() as d:
        dep = engine.Deployment(cell, 2**31 + 17, 1 << 16, d, lambda _msg: None)
        try:
            records = [loadgen.Record(tenant=0, template=q, due=0.0) for q in range(80)]
            for rec in records:
                dep.send(rec)
            checker = check.Checker(cell, {0: dep.tenants[0].columns})
        finally:
            dep.stop()
    checks = checker.check(records, {})
    assert check.verdict(checks) and checks["answers_compared"]["value"] == 80, checks
    revenues = {checker.reference(0, q)[0]["revenue"] for q in range(80)}
    assert len(revenues) == 80 and None not in revenues


def test_q1_reference():
    from .queries import q1
    rows = q1.reference(tiny_table())       # the last row ships after the cut-off
    assert [(r["l_returnflag"], r["l_linestatus"]) for r in rows] == [("A", "F"), ("R", "O")]
    a, r = rows
    assert a["count_order"] == 3 and a["sum_qty"] == 12 and a["sum_base_price"] == 1100.0
    assert math.isclose(a["sum_disc_price"], 200 * .93 + 300 * .94 + 600 * .94)
    assert math.isclose(a["sum_charge"], 200 * .93 * 1.5 + 300 * .94 + 600 * .94 * 1.25)
    assert math.isclose(a["avg_disc"], 0.19 / 3) and a["avg_qty"] == 4.0
    assert r["count_order"] == 3 and r["sum_qty"] == 48 and r["avg_price"] == 1000.0 / 3


def test_compare_rows():
    want = [{"k": "A", "n": 3, "x": 1.0}]
    assert check.compare_rows([{"k": "A", "n": 3, "x": 1.0}], want) == \
        {"max_rel_err": 0.0, "inexact": 0}
    assert check.compare_rows([{"k": "A", "n": 3, "x": 1.0 + 1e-9}], want)["max_rel_err"] > 9e-10
    assert check.compare_rows([{"k": "A", "n": 4, "x": 1.0}], want)["inexact"] == 1
    assert check.compare_rows([{"k": "B", "n": 3, "x": 1.0}], want)["inexact"] == 1
    assert check.compare_rows([{"k": "A", "n": 3.0, "x": 1.0}], want)["inexact"] == 1
    assert check.compare_rows([], want)["max_rel_err"] == math.inf
    assert check.compare_rows("shed", want)["inexact"] >= 1
    assert check.compare_rows([{"k": "A", "n": 3, "x": math.nan}], want)["max_rel_err"] == math.inf


def test_control_fails_the_limit():
    """The reference in the program's place, one precision step down (DOUBLE
    columns handed over as FLOAT), has to read above the limit: three seeds, at
    a size a test can hold, Q6 under seven of its parameter sets. PERF.md has the
    readings at the cells' own sizes."""
    from .queries import q1, q3, q6
    limit = check.limits()["double_max_rel_err"]
    cols = sorted(set(q1.COLUMNS["lineitem"]) | set(q6.COLUMNS["lineitem"]))
    li = datagen.tables({"rows": 400_000, "storage": "parquet"})["lineitem"]
    sets = _q6_sets()
    star = datagen.tables(_star_config(), 400_000)
    for seed in (1, 2**31 + 7, 987654321):
        c = {"lineitem": li.generate(seed, cols)}
        c3 = {n: star[n].kept(star[n].generate(seed, q3.COLUMNS[n]), q3.COLUMNS[n])
              for n in q3.COLUMNS}
        for q, tables, params in [(q1, c, {}), (q3, c3, {})] + [(q6, c, p) for p in sets[::13]]:
            got = q.reference(check.lower_precision(tables), **params)
            gap = check.compare_rows(got, q.reference(tables, **params))
            assert gap["max_rel_err"] > limit, (seed, q.__name__, params, gap)


# --- the manifest -----------------------------------------------------------

def test_manifest_is_sound():
    assert manifest.validate() == []
    b = manifest.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    for w in b["workloads"]:
        cell = manifest.Cell(w["name"])
        assert cell.metrics("end_to_end") and cell.metrics("per_layer")
        assert any(m["name"] == "setup_s" for m in cell.metrics("end_to_end"))
        for m in cell.metrics("end_to_end") + cell.metrics("per_layer"):
            read, args = cell.reader(m["name"])
            assert callable(read) and isinstance(args, dict)
        for t in cell.traffic["templates"]:
            q = cell.query(t["query"])
            assert callable(q.build) and callable(q.reference) and q.COLUMNS
    assert not manifest.NAME.match("has space") and not manifest.NAME.match("a/b")
    assert manifest.UNIT.match("rows/s") and not manifest.UNIT.match("rows per s")


def test_add_by_files_alone():
    """A dummy configuration, mix, template, metric and reader, added to a
    temporary copy as new files and new BENCHMARK.json entries: no file that
    was there is edited, and the harness finds all of them by name."""
    with tempfile.TemporaryDirectory() as d:
        shutil.copytree(HERE, os.path.join(d, "chipbench"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        b = manifest.benchmark()
        bench = os.path.join(d, "chipbench")
        with open(os.path.join(bench, "configs", "dummy-conf.json"), "w") as f:
            json.dump({"name": "dummy-conf", "rows": 10, "storage": "resident",
                       "columns": ["l_tax"], "session_conf": {}}, f)
        with open(os.path.join(bench, "traffic", "dummy-mix.json"), "w") as f:
            json.dump({"loop": "closed", "clients": 1, "tenants": 1,
                       "templates": [{"query": "dummy_q", "share": 1.0}]}, f)
        with open(os.path.join(bench, "queries", "dummy_q.py"), "w") as f:
            f.write("COLUMNS = {'lineitem': ('l_tax',)}\n"
                    "def build(F, t):\n    return t['lineitem']\n"
                    "def reference(c):\n    return []\n")
        with open(os.path.join(bench, "readers", "dummy_reader.py"), "w") as f:
            f.write("def read(ctx, k):\n    return k\n")
        with open(os.path.join(bench, "metrics", "dummy_metric.json"), "w") as f:
            json.dump({"reader": "dummy_reader", "args": {"k": 7}}, f)
        b["configs"].append({"name": "dummy-conf", "source": "none", "reduced": [],
                             "file": "chipbench/configs/dummy-conf.json", "why": "test"})
        b["workloads"].append({"name": "dummy-cell", "config": "dummy-conf",
                               "traffic": "dummy-mix", "chips": 1, "why": "test"})
        b["per_layer"].append({"name": "dummy_metric", "unit": "count", "better": "lower",
                               "source": "program_counter", "layer": "device",
                               "moves": "setup_s", "workloads": ["dummy-cell"]})
        with open(os.path.join(d, "BENCHMARK.json"), "w") as f:
            json.dump(b, f)
        code = ("from chipbench import manifest as m; assert m.ROOT == %r, m.ROOT; "
                "assert m.validate() == [], m.validate(); c = m.Cell('dummy-cell'); "
                "read, args = c.reader('dummy_metric'); assert read(None, **args) == 7; "
                "assert c.query('dummy_q').reference({}) == []; "
                "assert [x['name'] for x in c.metrics('per_layer')][-1] == 'dummy_metric'"
                % os.path.realpath(d))
        p = subprocess.run([sys.executable, "-c", code], cwd=os.path.realpath(d),
                           capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]


def _copy_and_add(d: str, add) -> None:
    """In `d`, a copy of the benchmark to which `add(bench_dir, BENCHMARK.json)`
    adds what a later PR would: new files and new entries. No file is edited."""
    bench = os.path.join(d, "chipbench")
    shutil.copytree(HERE, bench, ignore=shutil.ignore_patterns("__pycache__"))
    b = manifest.benchmark()
    add(bench, b)
    with open(os.path.join(d, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)


def _add_cell(b: dict, name: str, config: str, traffic: str, like: str) -> None:
    """A cell that reports the metrics the cell `like` reports."""
    b["workloads"].append({"name": name, "config": config, "traffic": traffic,
                           "chips": 1, "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for m in b[group]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)


def _with_star(d: str, tenants: int = 1) -> None:
    """The star rehearsal as a cell: a configuration file, a traffic file and
    entries in BENCHMARK.json (queries/q3.py is already there)."""
    def add(bench, b):
        shutil.copy(os.path.join(HERE, "testdata", "tpch-star-resident.json"),
                    os.path.join(bench, "configs"))
        mix = manifest._json(os.path.join(HERE, "testdata", "q3-stream.json"))
        mix["tenants"] = tenants
        with open(os.path.join(bench, "traffic", "q3-stream.json"), "w") as f:
            json.dump(mix, f)
        b["configs"].append({"name": "tpch-star-resident", "source": _star_config()["source"],
                             "file": "chipbench/configs/tpch-star-resident.json",
                             "reduced": ["cached_columns"], "why": "the star join"})
        _add_cell(b, "star-q3-stream", "tpch-star-resident", "q3-stream", "resident-q1-stream")
    _copy_and_add(d, add)


def _q6_sets() -> list:
    mix = manifest._json(os.path.join(HERE, "traffic", "q6-params.json"))
    return [t["params"] for t in mix["templates"]]


def _with_q6_params(d: str) -> None:
    """`parquet-q6-params` (PERF.md section 7) as the PR that admits it will add
    it: traffic/q6-params.json and metrics/plan_cache_hit_share.json are there,
    so entries in BENCHMARK.json are all it takes."""
    def add(_bench, b):
        _add_cell(b, "parquet-q6-params", "tpch-sf1-parquet", "q6-params", "parquet-q6-stream")
        b["per_layer"].append({"name": "plan_cache_hit_share", "unit": "%", "better": "higher",
                               "source": "program_counter", "layer": "plan + plan cache",
                               "moves": "rows_per_s", "workloads": ["parquet-q6-params"]})
    _copy_and_add(d, add)


def test_star_join_by_files_alone():
    """Three resident tables, Q3 and its reference, added as files and entries
    alone, through a whole run at 2^16 lineitem rows on the CPU."""
    from . import run
    from .queries import q3
    with tempfile.TemporaryDirectory() as d:
        _with_star(d)
        assert manifest.validate(d) == [], manifest.validate(d)
        r = run.run_cell("star-q3-stream", 2**31 + 29, 0.3, trace=False,
                         rehearsal_rows=1 << 16, root=d)
        assert r["correct"] is True and r["attempted"] >= 1, r["checks"]
        rows = (1 << 16) + (1 << 16) * 1_500_000 // 59_986_052 \
            + (1 << 16) * 15_000_000 // 59_986_052
        assert r["metrics"]["rows_per_s"]["value"] > 0
        cell = manifest.Cell("star-q3-stream", d)
        ref = q3.reference(check.reference_columns(cell, 2**31 + 29, 1 << 16)[0])
        assert len(ref) == 10 and list(ref[0]) == ["l_orderkey", "revenue", "o_orderdate",
                                                   "o_shippriority"]
        assert [x["revenue"] for x in ref] == sorted((x["revenue"] for x in ref), reverse=True)
        assert sum(datagen.tables(cell.config, 1 << 16)[t].rows for t in q3.COLUMNS) == rows


def _with_text(d: str) -> None:
    """The names rehearsal as two cells: CUSTOMER with `c_name` resident and
    ORDERS with `o_clerk` from Parquet (testdata/tpch-names.json; cell
    `names-stream`), and the same with CUSTOMER from Parquet too
    (`names-files-stream`); a template that joins them and groups by `c_name`
    (testdata/names_q.py), a traffic file and entries in BENCHMARK.json."""
    def add(bench, b):
        conf = manifest._json(os.path.join(HERE, "testdata", "tpch-names.json"))
        shutil.copy(os.path.join(HERE, "testdata", "names_q.py"),
                    os.path.join(bench, "queries"))
        with open(os.path.join(bench, "traffic", "names-stream.json"), "w") as f:
            json.dump({"loop": "closed", "clients": 1, "tenants": 1, "trace_seconds": 10,
                       "templates": [{"query": "names_q", "share": 1.0, "class": "batch"}]}, f)
        for name, cell, storage in (("tpch-names", "names-stream", "resident"),
                                    ("tpch-names-files", "names-files-stream", "parquet")):
            conf["name"] = name
            conf["tables"]["customer"]["storage"] = storage
            with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
                json.dump(conf, f)
            b["configs"].append({"name": name, "source": conf["source"], "reduced": [],
                                 "file": f"chipbench/configs/{name}.json",
                                 "why": "a formatted name column through a join and a group-by"})
            _add_cell(b, cell, name, "names-stream", "parquet-q3-stream")
    _copy_and_add(d, add)


def test_text_column_by_files_alone():
    """A text column through a configuration, a join, a group-by and the
    reference, added as files and entries alone: whole runs at 2^16 orders on
    the CPU from the copy's root (where the new template is found by name), with
    CUSTOMER resident and from Parquet, read `correct`; one byte of one name
    altered where the answer is produced does not."""
    code = textwrap.dedent("""\
        import json
        from chipbench import manifest, run, selftest
        assert manifest.ROOT == %r, manifest.ROOT
        assert manifest.validate() == [], manifest.validate()
        out = {}
        def alter(rows):
            row = rows[len(rows) // 2]
            row["c_name"] = row["c_name"][:-1] + chr(ord(row["c_name"][-1]) ^ 1)
            return rows
        for case, cell in (("sound", "names-stream"), ("files", "names-files-stream"),
                           ("altered", "names-stream")):
            cls, real, broken = selftest._broken_collect(alter)
            cls.collect = broken if case == "altered" else real
            out[case] = run.run_cell(cell, 2**31 + 37, 0.3, trace=False,
                                     rehearsal_rows=1 << 16)
        print("RESULT " + json.dumps(out, default=str))
    """)
    with tempfile.TemporaryDirectory() as d:
        d = os.path.realpath(d)
        _with_text(d)
        # the copy's chipbench comes first (the working directory), the program from this tree
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(
            filter(None, [manifest.ROOT, os.environ.get("PYTHONPATH")])))
        p = subprocess.run([sys.executable, "-c", code % d], cwd=d, env=env,
                           capture_output=True, text=True, timeout=900)
        assert p.returncode == 0, p.stderr[-3000:]
        out = json.loads(p.stdout.rsplit("RESULT ", 1)[1])
        for sound in (out["sound"], out["files"]):
            assert sound["correct"] is True and sound["failed"] == 0, sound["checks"]
            assert sound["checks"]["answers_compared"]["value"] >= 1
        altered = out["altered"]
        assert altered["correct"] is False, altered["checks"]
        assert altered["checks"]["inexact_values"]["value"] >= 1
        c = altered["checks"]["double_max_rel_err"]
        assert c["value"] <= c["limit"], c


def test_tenants_over_several_tables_are_refused():
    with tempfile.TemporaryDirectory() as d:
        _with_star(d, tenants=2)
        faults = manifest.validate(d)
        assert len(faults) == 1 and "more than one tenant" in faults[0], faults


def test_q3_reference():
    """Hand-worked: customers 0 and 2 are BUILDING; order 10 (customer 0, early)
    has two late lineitems, order 11 (customer 1) is another segment's, order 12
    (customer 2) is too recent, order 13 (customer 2, early) has one late and one
    early lineitem; lineitem of key 99 has no order."""
    from .queries import q3
    t = {"customer": {"c_custkey": np.array([0, 1, 2]),
                      "c_mktsegment": np.array([b"BUILDING", b"MACHINERY", b"BUILDING"])},
         "orders": {"o_orderkey": np.array([10, 11, 12, 13]), "o_custkey": np.array([0, 1, 2, 2]),
                    "o_orderdate": np.array([9000, 9000, 9204, 9203], np.int32),
                    "o_shippriority": np.array([0, 0, 0, 0], np.int32)},
         "lineitem": {"l_orderkey": np.array([10, 10, 11, 12, 13, 13, 99]),
                      "l_extendedprice": np.array([100., 200., 300., 400., 500., 600., 700.]),
                      "l_discount": np.array([0.5, 0.0, 0.0, 0.0, 0.1, 0.0, 0.0]),
                      "l_shipdate": np.array([9205, 9300, 9300, 9300, 9205, 9204, 9300],
                                             np.int32)}}
    import datetime
    day = lambda n: datetime.date(1970, 1, 1) + datetime.timedelta(n)  # noqa: E731
    assert q3.reference(t) == [
        {"l_orderkey": 13, "revenue": 450.0, "o_orderdate": day(9203), "o_shippriority": 0},
        {"l_orderkey": 10, "revenue": 250.0, "o_orderdate": day(9000), "o_shippriority": 0}]


def test_refuses_a_bare_directory():
    """Only BENCHMARK.json and chipbench/: the program is not there, so the
    command exits non-zero and prints no result."""
    with tempfile.TemporaryDirectory() as d:
        shutil.copytree(HERE, os.path.join(d, "chipbench"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), d)
        p = subprocess.run([sys.executable, "-m", "chipbench.run", "--workload",
                            "parquet-q6-stream", "--seed", "1", "--seconds", "1",
                            "--trace", "0"], cwd=d, capture_output=True, text=True,
                           timeout=300, env={k: v for k, v in os.environ.items()
                                             if k != "PYTHONPATH"})
        assert p.returncode != 0 and '"correct"' not in p.stdout, (p.returncode, p.stdout)


def test_refuses_a_cpu_backend():
    p = subprocess.run([sys.executable, "-m", "chipbench.run", "--workload",
                        "parquet-q6-stream", "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=manifest.ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2 and '"correct"' not in p.stdout, (p.returncode, p.stdout[-500:])
    assert "TPU" in p.stderr


# --- whole runs with the timed path broken underneath -----------------------

ROWS = 150_000


def _run(workload="resident-q1-stream", seed=2**31 + 11, root=manifest.ROOT):
    from . import run
    return run.run_cell(workload, seed, 0.3, trace=False, rehearsal_rows=ROWS, root=root)


def test_a_sound_run_is_correct():
    for cell in ("resident-q1-stream", "parquet-q6-stream", "parquet-q1-stream"):
        r = _run(cell)
        assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1, r["checks"]
        assert list(r)[-1] == "checks" and "setup_s" in r["metrics"]


def _broken_collect(alter):
    """DataFrame.collect of the program, with the answer altered where it is produced."""
    from spark_rapids_tpu.session import DataFrame
    real = DataFrame.collect

    def collect(self, *a, **kw):
        return alter(real(self, *a, **kw))
    return DataFrame, real, collect


def _expect_incorrect(alter, failing_check):
    cls, real, broken = _broken_collect(alter)
    cls.collect = broken
    try:
        r = _run()
    finally:
        cls.collect = real
    assert r["correct"] is False, r["checks"]
    c = r["checks"][failing_check]
    assert not c["value"] <= c["limit"], (failing_check, c)


def test_an_altered_double_is_not_correct():
    def alter(rows):
        rows[0]["sum_charge"] *= 1 + 1e-9
        return rows
    _expect_incorrect(alter, "double_max_rel_err")


def test_an_altered_count_is_not_correct():
    def alter(rows):
        rows[-1]["count_order"] += 1
        return rows
    _expect_incorrect(alter, "inexact_values")


def test_a_dropped_group_is_not_correct():
    _expect_incorrect(lambda rows: rows[:-1], "inexact_values")


def test_a_failing_query_is_not_correct():
    state = {"n": 0}

    def alter(rows):
        state["n"] += 1
        if state["n"] > 3:          # the warm-up passes, the window's queries fail
            raise RuntimeError("injected")
        return rows
    _expect_incorrect(alter, "unanswered")


def test_another_sets_literals_are_not_correct():
    """Every parameter set answered with the literals of the set after it."""
    from .queries import q6
    sets = _q6_sets()
    real = q6.build
    q6.build = lambda F, tables, **p: real(F, tables, **sets[(sets.index(p) + 1) % len(sets)])
    try:
        with tempfile.TemporaryDirectory() as d:
            _with_q6_params(d)
            r = _run("parquet-q6-params", root=d)
    finally:
        q6.build = real
    assert r["correct"] is False and r["attempted"] >= 1
    assert r["checks"]["double_max_rel_err"]["value"] > 1e-3, r["checks"]


def test_half_the_rows_left_out_is_not_correct():
    """The program is handed the first half of the table; the reference keeps all of it."""
    real = datagen.Table.to_arrow
    datagen.Table.to_arrow = lambda self, cols: real(
        self, {k: v[:len(v) // 2] for k, v in cols.items()})
    try:
        r = _run()
    finally:
        datagen.Table.to_arrow = real
    assert r["correct"] is False
    assert r["checks"]["inexact_values"]["value"] > 0


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    pick = args[args.index("-k") + 1] if "-k" in args else ""
    tests = [(n, f) for n, f in sorted(globals().items())
             if n.startswith("test_") and callable(f) and pick in n]
    failed = 0
    for name, fn in tests:
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report every test, then fail
            failed += 1
            import traceback
            traceback.print_exc()
            print(f"FAIL {name}: {type(e).__name__}: {e}", flush=True)
        else:
            print(f"ok   {name}", flush=True)
    print(f"{len(tests) - failed} passed, {failed} failed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
