"""TPC-H Q3 from files (ISSUE 33): CUSTOMER, ORDERS and LINEITEM as Parquet
tables written by the benchmark's generator, scanned, decoded on the device
and joined with nothing cached, held to the benchmark's plain reference
(`chipbench/queries/q3.py`); the decoder's sides that only this query's files
reach (BIGINT chunks that change encoding, the ragged dictionary under a
filter); the scan node's `scan.*` counters of a query's summary
(docs/observability.md); and the cell `parquet-q3-stream` as BENCHMARK.json
declares it.
"""

import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu.functions as F
from chipbench import check, datagen, engine, manifest
from chipbench.queries import q3
from spark_rapids_tpu.io import device_decode as dd
from spark_rapids_tpu.session import TpuSession

CELL = "parquet-q3-stream"
SIBLING = "star-sf1-q3-stream"
#: the CPU backend computes DOUBLE as float64: a revenue differs from the
#: reference's extended-precision sum by a few ulp of 2.2e-16
DOUBLE_REL = 1e-13
ROWS = 1 << 14                 # LINEITEM rows; ORDERS 4,095, CUSTOMER 409
GROUP = 1 << 12                # rows a row group: LINEITEM has four
SCAN_COUNTERS = ("scan.files", "scan.row_groups", "scan.rows",
                 "scan.columns_decoded", "scan.columns_general")


def _config():
    return manifest.Cell(CELL).config


def _schema(rows=ROWS):
    return datagen.tables(_config(), rows)


def _generated(seed, rows=ROWS):
    """{table: {column: numpy}}: every column of the three tables."""
    return {name: t.generate(seed, list(t.columns), 0, t.rows)
            for name, t in _schema(rows).items()}


def _reference(cols, rows=ROWS):
    schema = _schema(rows)
    return q3.reference({name: schema[name].kept(c, q3.COLUMNS[name])
                         for name, c in cols.items()})


def _write(tmp_path, cols, rows=ROWS, files=1, group=GROUP):
    """Each table as `files` snappy Parquet files of contiguous row ranges,
    written as `Table.write_parquet` writes (REQUIRED, dictionary on) but
    with `group` rows a row group. Returns {table: [paths]}."""
    schema, out = _schema(rows), {}
    for name, c in cols.items():
        whole = schema[name].to_arrow(c)
        edges = [whole.num_rows * i // files for i in range(files + 1)]
        out[name] = []
        for i, (lo, hi) in enumerate(zip(edges[:-1], edges[1:])):
            path = os.path.join(str(tmp_path), f"{name}.{i}.parquet")
            pq.write_table(whole.slice(lo, hi - lo), path, compression="snappy",
                           use_dictionary=True, row_group_size=group)
            out[name].append(path)
    return out


def _session(extra=None):
    conf = dict(_config()["session_conf"])
    conf.update(extra or {})
    return TpuSession(conf)


def _q3_from_files(session, paths):
    return q3.build(F, {name: session.read.parquet(*p) for name, p in paths.items()})


def _q3_cached(session, cols):
    """Q3 over the same columns as `device_cache()`d tables: the sibling's way."""
    schema = _schema()
    return q3.build(F, {name: session.createDataFrame(schema[name].to_arrow(
        {k: c[k] for k in q3.COLUMNS[name]})).device_cache()
        for name, c in cols.items()})


def _same(got, want):
    c = check.compare_rows(got, want)
    assert c["inexact"] == 0 and c["max_rel_err"] <= DOUBLE_REL, (c, got, want)


def data_page_encodings(path, column=0, row_group=0):
    """The encoding of each data page of a column chunk, in file order, read
    from the page headers (8 = RLE_DICTIONARY, 0 = PLAIN): the footer's
    `encodings` lists PLAIN for the dictionary page itself, so it cannot
    tell a chunk that changed encoding from one that did not."""
    cc = pq.read_metadata(path).row_group(row_group).column(column)
    start, length = dd._chunk_range(cc)
    with open(path, "rb") as f:
        f.seek(start)
        chunk = f.read(length)
    out, pos = [], 0
    while pos < len(chunk):
        hdr, body = dd._read_struct(chunk, pos)
        if hdr[1] == 0:
            out.append(hdr[5][2])
        elif hdr[1] == 3:
            out.append(hdr[8][4])
        pos = body + hdr[3]
    return out


def _stats_delta(before):
    after = dd.decode_stats()
    return {k: after[k] - before.get(k, 0) for k in after}


# ---------------------------------------------------------------------------
# (a) the answer from files, through the served path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [7, 2**31 + 29, 1234567])
def test_q3_from_files_matches_the_reference(tmp_path, seed):
    cols = _generated(seed)
    paths = _write(tmp_path, cols)
    assert pq.read_metadata(paths["lineitem"][0]).num_row_groups == 4
    want = _reference(cols)
    assert len(want) == 10
    _same(_q3_from_files(_session(), paths).collect(), want)


def test_q3_from_the_harness_writer(tmp_path):
    """The files as a run of the cell writes them (`Table.write_parquet`: one
    row group a 2^20-row chunk, all 25 columns) and the columns it keeps for
    the reference."""
    seed, rows = 11, 1 << 13
    kept, paths = {}, {}
    for name, t in _schema(rows).items():
        paths[name] = [os.path.join(str(tmp_path), f"{name}.parquet")]
        kept[name] = t.write_parquet(paths[name][0], seed, keep=q3.COLUMNS[name])
        md = pq.read_metadata(paths[name][0])
        assert md.num_columns == len(t.columns) and md.num_rows == t.rows
    assert sum(pq.read_metadata(p[0]).num_columns for p in paths.values()) == 25
    _same(_q3_from_files(_session(), paths).collect(), q3.reference(kept))


# ---------------------------------------------------------------------------
# (b) one file a table, four files a table, cached tables: the same ten rows
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["one_file", "four_files", "cached"])
def test_q3_layouts_give_the_same_ten_rows(tmp_path, layout):
    cols = _generated(7)
    want = _reference(cols)
    # tables this small would be broadcast; at SF1 they are not
    s = _session({"spark.sql.autoBroadcastJoinThreshold": "-1"})
    if layout == "cached":
        df = _q3_cached(s, cols)
    else:
        df = _q3_from_files(s, _write(tmp_path, cols, files=1 if layout == "one_file" else 4))
    plan = engine.plan_text(df)
    assert engine.host_operators(plan) == [], plan
    # a scan is one partition a file: four files a table are four partitions,
    # and the joins then stand over hash exchanges (a cached table of one
    # batch is one partition too)
    assert ("TpuShuffleExchange" in plan) == (layout == "four_files"), plan
    if layout == "four_files":
        assert plan.count("TpuFileScanExec[parquet, 4 files") == 3, plan
    _same(df.collect(), want)


def test_same_seed_same_values_as_the_cached_sibling():
    """Q3's ten columns come first in each table, with the specs and in the
    order of `tpch-sf1-star-resident`, so a seed gives both configurations
    the same values: the two cells answer the same Q3."""
    sibling = datagen.tables(manifest.Cell(SIBLING).config, ROWS)
    mine = _schema()
    for name, columns in q3.COLUMNS.items():
        assert list(mine[name].columns)[:len(columns)] == list(sibling[name].columns)
        a = mine[name].generate(2**31 + 11, columns)
        b = sibling[name].generate(2**31 + 11, columns)
        for c in columns:
            assert a[c].dtype == b[c].dtype and np.array_equal(a[c], b[c]), (name, c)


# ---------------------------------------------------------------------------
# (c) the plan as executed, and what the decoder counted
# ---------------------------------------------------------------------------


def test_q3_one_file_a_table_plan_and_decode_stats(tmp_path):
    cols = _generated(7)
    s = _session()
    df = _q3_from_files(s, _write(tmp_path, cols))
    plan = engine.plan_text(df)
    assert engine.host_operators(plan) == [], plan
    assert "Exchange" not in plan, plan
    assert plan.count("TpuFileScanExec[parquet, 1 files") == 3, plan
    assert plan.count("ShuffledSymmetricHashJoin") == 2 and "TpuTopN" in plan, plan
    df.collect()
    for _ in range(2):
        before = dd.decode_stats()
        _same(df.collect(), _reference(cols))
        d = _stats_delta(before)
        assert d["fallback_columns"] == d["fallback_row_groups"] == d["fallback_files"] == 0, d
        # c_mktsegment's one row group: the only ragged chunk a Q3 reads
        assert d["general_variable_length_dictionary"] == 1, d
        assert sum(v for k, v in d.items() if k.startswith("general_")) == 1, d
        executed = s.last_query_phases()
        assert "exchange.map" not in executed["phases"], executed["phases"]
        assert not [k for k in executed["counters"] if k.startswith("exchange.")]


# ---------------------------------------------------------------------------
# (d) a BIGINT chunk whose dictionary overflows inside the row group
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("version", ["1.0", "2.0"])
@pytest.mark.parametrize("keys", ["dense_sequence", "uniform_draw", "few_then_many"])
def test_bigint_dictionary_overflow_decodes_on_the_device(tmp_path, keys, version):
    """`o_orderkey` (a dense sequence) and `l_orderkey` (a uniform draw) at
    SF1 outgrow the writer's dictionary page inside a 2^20-row group: the
    chunk is dictionary pages, then PLAIN pages. The same layout at a test's
    size, by a small dictionary page limit, beside an INT column that stays
    a dictionary."""
    n = 40_000
    rng = np.random.default_rng(len(keys))
    if keys == "dense_sequence":
        v = np.arange(n, dtype=np.int64) + (1 << 33)
    elif keys == "uniform_draw":
        v = rng.integers(0, n // 4, n, dtype=np.int64) * 3 - 7
    else:
        v = np.concatenate([rng.integers(0, 16, n // 2, dtype=np.int64),
                            rng.integers(-2**62, 2**62, n - n // 2, dtype=np.int64)])
    t = pa.Table.from_arrays(
        [pa.array(v), pa.array((v % 5).astype(np.int32))],
        schema=pa.schema([pa.field("k", pa.int64(), nullable=False),
                          pa.field("small", pa.int32(), nullable=False)]))
    path = os.path.join(str(tmp_path), "keys.parquet")
    pq.write_table(t, path, compression="snappy", use_dictionary=True,
                   dictionary_pagesize_limit=16 << 10, data_page_size=32 << 10,
                   data_page_version=version)
    pages = data_page_encodings(path)
    switch = pages.index(0)
    assert switch > 0 and set(pages[:switch]) == {8} and set(pages[switch:]) == {0}, pages
    assert set(data_page_encodings(path, column=1)) == {8}
    before = dd.decode_stats()
    got = _session().read.parquet(path).to_arrow()
    d = _stats_delta(before)
    assert d["row_groups"] == 1 and d["device_columns"] == 2, d
    assert d["fallback_columns"] == d["fallback_row_groups"] == d["fallback_files"] == 0, d
    want = pq.read_table(path)
    assert got.column("k").to_pylist() == want.column("k").to_pylist()
    assert got.column("small").to_pylist() == want.column("small").to_pylist()


# ---------------------------------------------------------------------------
# (e) the filter over the ragged dictionary column
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("segment", ["BUILDING", "AUTOMOBILE", "HOUSEHOLD", "CARS", "NO SUCH"])
def test_segment_filter_keeps_pyarrows_rows(tmp_path, segment):
    rows = 1 << 16                                   # CUSTOMER 1,638 rows
    cols = {"customer": _generated(2**31 + 29, rows)["customer"]}
    path = _write(tmp_path, cols, rows)["customer"][0]
    before = dd.decode_stats()
    got = (_session().read.parquet(path).filter(F.col("c_mktsegment") == segment)
           .select("c_custkey", "c_mktsegment").to_arrow())
    d = _stats_delta(before)
    # a literal past the chunk's maximum prunes the row group by its footer
    # statistics; one inside the range and absent ("CARS") decodes it
    assert d["general_variable_length_dictionary"] == (segment != "NO SUCH"), d
    assert d["fallback_columns"] == d["fallback_row_groups"] == 0, d
    whole = pq.read_table(path, columns=["c_custkey", "c_mktsegment"])
    want = whole.filter(pc.equal(whole.column("c_mktsegment"), segment))
    assert got.column("c_custkey").to_pylist() == want.column("c_custkey").to_pylist()
    assert got.column("c_mktsegment").to_pylist() == want.column("c_mktsegment").to_pylist()
    assert (want.num_rows == 0) == (segment in ("CARS", "NO SUCH"))


# ---------------------------------------------------------------------------
# (f) the scan's counters in a query's summary
# ---------------------------------------------------------------------------


def test_scan_counters_of_one_q3(tmp_path):
    cols = _generated(7)
    paths = _write(tmp_path, cols)
    footers = {name: pq.read_metadata(p[0]) for name, p in paths.items()}
    s = _session()
    df = _q3_from_files(s, paths)
    seen = []
    for _ in range(3):
        _same(df.collect(), _reference(cols))
        counters = s.last_query_phases()["counters"]
        seen.append({k: counters[k] for k in SCAN_COUNTERS})
    assert seen[0] == seen[1] == seen[2], seen
    groups = {name: md.num_row_groups for name, md in footers.items()}
    assert groups == {"customer": 1, "orders": 1, "lineitem": 4}
    assert seen[0] == {
        "scan.files": 3,
        "scan.row_groups": sum(groups.values()),
        "scan.rows": sum(md.num_rows for md in footers.values()),
        # only the columns Q3 reads are staged: 2 + 4 + 4 of the files' 25
        "scan.columns_decoded": sum(len(q3.COLUMNS[n]) * g for n, g in groups.items()),
        "scan.columns_general": 1}, seen[0]
    assert sum(md.num_columns for md in footers.values()) == 25


def test_scan_counters_leave_a_host_decoded_scan_out(tmp_path):
    """With the device decoder off the host reads the files: the counters
    of decoding read 0 (`scan.files` counts the three tables the host reader
    handed over), and the answer is the same."""
    cols = _generated(7)
    s = _session({"spark.rapids.tpu.parquet.deviceDecode.enabled": "false"})
    _same(_q3_from_files(s, _write(tmp_path, cols)).collect(), _reference(cols))
    counters = s.last_query_phases()["counters"]
    assert {k: counters[k] for k in SCAN_COUNTERS} == \
        {**dict.fromkeys(SCAN_COUNTERS, 0), "scan.files": 3}


def test_cached_q3_has_no_scan_counters():
    s = _session()
    _q3_cached(s, _generated(7)).collect()
    assert not [k for k in s.last_query_phases()["counters"] if k.startswith("scan.")]


# ---------------------------------------------------------------------------
# the cell as declared
# ---------------------------------------------------------------------------


def test_cell_is_declared_by_entries_and_data_files():
    assert manifest.validate() == []
    cell = manifest.Cell(CELL)
    assert (cell.entry["config"], cell.entry["traffic"], cell.chips) == \
        ("tpch-sf1-star-parquet", "q3-spec-stream", 1)
    assert len(cell.entry["why"]) <= 200
    conf = cell.config
    assert conf["rows"] == 6001215 and conf["scale_factor"] == 1
    assert all(t["storage"] == "parquet" for t in conf["tables"].values())
    entry = [c for c in cell.bench["configs"] if c["name"] == conf["name"]][0]
    assert entry["reduced"] == list(conf["reduced"]) == ["scale_factor", "file_columns"]
    assert conf["session_conf"] == manifest.Cell(SIBLING).config["session_conf"]
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert e2e == {"rows_per_s", "setup_s"}, e2e
    layer = {m["name"]: m for m in cell.metrics("per_layer")}
    for name, counter in (("scan_general_columns_per_query", "scan.columns_general"),
                          ("scan_row_groups_per_query", "scan.row_groups")):
        assert layer[name]["workloads"] == [CELL] and layer[name]["layer"] == "scan"
        read, args = cell.reader(name)
        assert read.__module__ == "chipbench.readers.query_counter"
        assert args == {"counter": counter}
    assert not {"exchange_map_ms_per_query", "exchange_fetch_ms_per_query",
                "stage_launch_ms_per_batch", "stage_fetch_ms_per_query"} & set(layer)
    sizes = {n: t.rows for n, t in datagen.tables(conf).items()}
    assert sizes == {"customer": 150000, "orders": 1500000, "lineitem": 6001215}
