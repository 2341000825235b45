"""Device-side parquet decode: per-encoding oracles vs pyarrow, per-column
fallback parity, O(row-groups) dispatch accounting, chaos scan.read healing,
and encrypted-file detection (reference GpuParquetScan device decode +
GpuParquetScan.scala:590 encryption semantics)."""

import os
import struct

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from asserts import assert_tpu_and_cpu_are_equal_collect

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.chaos import FaultInjector
from spark_rapids_tpu.io import device_decode as dd
from spark_rapids_tpu.session import TpuSession


@pytest.fixture(autouse=True)
def _clean_decode_state():
    dd.reset_for_tests()
    FaultInjector.reset_for_tests()
    yield
    FaultInjector.reset_for_tests()


def _mixed_table(n=4000, null_every=5, seed=7):
    rng = np.random.default_rng(seed)

    def nulled(vals, k):
        return [None if k and i % k == 0 else v for i, v in enumerate(vals)]

    return pa.table({
        "i32": pa.array(nulled([int(x) for x in
                                rng.integers(-2**31, 2**31, n)], null_every),
                        pa.int32()),
        "i64": pa.array(nulled([int(x) for x in
                                rng.integers(-2**63, 2**63, n)], null_every),
                        pa.int64()),
        "f32": pa.array(rng.normal(size=n).astype(np.float32), pa.float32()),
        "f64": pa.array(nulled([float(x) for x in rng.normal(size=n)],
                               null_every), pa.float64()),
        "bool": pa.array(nulled([bool(i % 3 == 0) for i in range(n)],
                                null_every)),
        "date": pa.array(nulled([i % 20000 for i in range(n)], null_every),
                         pa.date32()),
        "ts": pa.array(nulled([1_600_000_000_000_000 + i for i in range(n)],
                              null_every), pa.timestamp("us")),
        "i8": pa.array(nulled([i % 120 - 60 for i in range(n)], null_every),
                       pa.int8()),
        "lowcard": pa.array((np.arange(n) % 5).astype(np.int64)),
    })


def _device_read(path, conf=None):
    s = TpuSession(dict(conf or {}))
    return s.read.parquet(path).to_arrow()


def _assert_tables_equal(got, ref):
    assert got.num_rows == ref.num_rows
    for c in ref.column_names:
        a = got.column(c).combine_chunks()
        b = ref.column(c).combine_chunks()
        if a.type != b.type:
            a = a.cast(b.type)
        assert a.equals(b), f"column {c} differs"


def _write(tmp_path, table, name="t.parquet", **kw):
    p = str(tmp_path / name)
    pq.write_table(table, p, **kw)
    return p


# ---------------------------------------------------------------------------
# per-encoding oracles: bit-identical vs the pyarrow decode
# ---------------------------------------------------------------------------


def test_plain_encoding_oracle(tmp_path):
    p = _write(tmp_path, _mixed_table(), use_dictionary=False,
               compression="snappy", row_group_size=1500)
    got = _device_read(p)
    _assert_tables_equal(got, pq.read_table(p))
    st = dd.decode_stats()
    assert st["dispatches"] == 3  # one per row group
    assert st["fallback_columns"] == 0


def test_rle_dictionary_oracle(tmp_path):
    p = _write(tmp_path, _mixed_table(), use_dictionary=True,
               compression="snappy", row_group_size=1500, data_page_size=800)
    got = _device_read(p)
    _assert_tables_equal(got, pq.read_table(p))
    assert dd.decode_stats()["fallback_columns"] == 0


def test_bitpacked_boolean_oracle(tmp_path):
    n = 3000
    t = pa.table({
        "b_dense": pa.array([bool(i % 7 == 0) for i in range(n)]),
        "b_null": pa.array([None if i % 4 == 0 else bool(i % 2)
                            for i in range(n)]),
        "b_allnull": pa.array([None] * n, pa.bool_()),
    })
    p = _write(tmp_path, t, compression="snappy", row_group_size=1000,
               data_page_size=200)
    _assert_tables_equal(_device_read(p), pq.read_table(p))
    assert dd.decode_stats()["fallback_columns"] == 0


@pytest.mark.parametrize("null_every", [0, 2, 1])
def test_def_level_null_densities(tmp_path, null_every):
    """Mixed null densities including no-null (null_every=0) and all-null
    (null_every=1) pages."""
    n = 2500
    vals = [None if null_every and i % null_every == 0 else i
            for i in range(n)]
    t = pa.table({"v": pa.array(vals, pa.int64()),
                  "w": pa.array(vals, pa.int32())})
    p = _write(tmp_path, t, compression="snappy", row_group_size=800,
               data_page_size=300)
    _assert_tables_equal(_device_read(p), pq.read_table(p))
    assert dd.decode_stats()["fallback_columns"] == 0


def test_data_page_v2_oracle(tmp_path):
    p = _write(tmp_path, _mixed_table(), compression="snappy",
               data_page_version="2.0", row_group_size=1500,
               data_page_size=700)
    _assert_tables_equal(_device_read(p), pq.read_table(p))
    assert dd.decode_stats()["fallback_columns"] == 0


@pytest.mark.parametrize("codec", ["snappy", "zstd", "gzip", "NONE"])
def test_codecs(tmp_path, codec):
    p = _write(tmp_path, _mixed_table(1500), compression=codec,
               row_group_size=600)
    _assert_tables_equal(_device_read(p), pq.read_table(p))
    assert dd.decode_stats()["dispatches"] == 3


# ---------------------------------------------------------------------------
# dispatch accounting: O(row-groups) launches per scan
# ---------------------------------------------------------------------------


def test_dispatch_counter_o_row_groups(tmp_path):
    """Many pages per row group must still cost ONE decode dispatch per
    row group — not O(pages), not O(columns)."""
    from spark_rapids_tpu.execs import opjit
    n = 6000
    t = _mixed_table(n)
    p = _write(tmp_path, t, compression="snappy", row_group_size=1000,
               data_page_size=200)  # ~dozens of pages per group
    md = pq.ParquetFile(p).metadata
    assert md.num_row_groups == 6
    before = opjit.cache_stats()["calls_by_kind"].get("parquet_decode", 0)
    _assert_tables_equal(_device_read(p), pq.read_table(p))
    st = dd.decode_stats()
    assert st["dispatches"] == md.num_row_groups
    assert st["row_groups"] == md.num_row_groups
    # the launches land in the process-wide dispatch accounting too
    after = opjit.cache_stats()["calls_by_kind"].get("parquet_decode", 0)
    assert after - before == md.num_row_groups


def test_row_group_pruning_still_prunes(tmp_path):
    """Footer-statistics pruning applies before any decode dispatch: a
    pushed filter that excludes whole row groups skips their launches."""
    n = 4000
    t = pa.table({"k": pa.array(np.arange(n, dtype=np.int64)),
                  "v": pa.array(np.arange(n, dtype=np.float64))})
    p = _write(tmp_path, t, row_group_size=1000)
    s = TpuSession({})
    got = (s.read.parquet(p).filter(F.col("k") >= 3500).to_arrow()
           .sort_by("k"))
    assert got.column("k").to_pylist() == list(range(3500, 4000))
    assert dd.decode_stats()["dispatches"] == 1  # 3 of 4 groups pruned


# ---------------------------------------------------------------------------
# per-column fallback parity: device + pinned-host columns in ONE batch
# ---------------------------------------------------------------------------


def test_per_column_fallback_parity(tmp_path):
    n = 2000
    t = pa.table({
        "dev_i": pa.array([None if i % 6 == 0 else i for i in range(n)],
                          pa.int64()),
        # decimal128 → FIXED_LEN_BYTE_ARRAY: genuinely host-only (strings
        # decode on device since the BYTE_ARRAY kernels landed)
        "host_d": pa.array([None if i % 9 == 0 else __import__(
            "decimal").Decimal(i) / 4 for i in range(n)],
            pa.decimal128(25, 2)),
        "dev_f": pa.array(np.arange(n) * 0.25, pa.float64()),
        "dev_s": pa.array([None if i % 9 == 0 else f"s{i % 23}"
                           for i in range(n)]),  # BYTE_ARRAY: device decode
    })
    p = _write(tmp_path, t, compression="snappy", row_group_size=700)
    got = _device_read(p)
    _assert_tables_equal(got, pq.read_table(p))
    st = dd.decode_stats()
    assert st["fallback_columns"] >= 3  # host_l once per row group
    assert st["device_columns"] >= 9    # incl. the string column
    assert st["dispatches"] == 3


def test_device_decode_off_matches(tmp_path):
    p = _write(tmp_path, _mixed_table(1200), row_group_size=500)
    on = _device_read(p)
    st = dd.decode_stats()
    assert st["dispatches"] == 3
    dd.reset_for_tests()
    off = _device_read(
        p, {"spark.rapids.tpu.parquet.deviceDecode.enabled": "false"})
    assert dd.decode_stats()["dispatches"] == 0
    _assert_tables_equal(on, off)


def test_query_parity_device_vs_cpu(tmp_path):
    p = _write(tmp_path, _mixed_table(3000), compression="snappy",
               row_group_size=1000)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.read.parquet(p)
        .filter(F.col("i64").isNotNull() & (F.col("lowcard") >= 2))
        .groupBy("lowcard").agg(F.count(F.col("i32")).alias("c"),
                                F.sum(F.col("f64")).alias("sf")),
        # per-row-group device batches sum floats in a different
        # association order than the CPU whole-file read
        ignore_order=True, approx_float=True)


def test_partitioned_directory_device_decode(tmp_path):
    root = tmp_path / "part"
    for k in (1, 2):
        d = root / f"k={k}"
        d.mkdir(parents=True)
        n = 600
        t = pa.table({"v": pa.array(np.arange(n, dtype=np.int64) * k),
                      "f": pa.array(np.arange(n) * 0.5, pa.float64())})
        pq.write_table(t, str(d / "f0.parquet"), row_group_size=250)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.read.parquet(str(root)).filter(F.col("k") == 2),
        ignore_order=True)
    assert dd.decode_stats()["dispatches"] > 0


def test_verify_conf_passes_on_clean_files(tmp_path):
    p = _write(tmp_path, _mixed_table(1000), row_group_size=400)
    got = _device_read(
        p, {"spark.rapids.tpu.parquet.deviceDecode.verify": "true"})
    _assert_tables_equal(got, pq.read_table(p))
    assert dd.decode_stats()["dispatches"] == 3


# ---------------------------------------------------------------------------
# chaos scan.read: corrupt/truncated page bytes → clean fallback, never
# wrong data
# ---------------------------------------------------------------------------


def test_chaos_truncated_page_heals_via_host(tmp_path):
    p = _write(tmp_path, _mixed_table(2000), compression="snappy",
               row_group_size=700)
    ref = pq.read_table(p)
    inj = FaultInjector.get()
    inj.force("scan.read", "truncate", 2)
    got = _device_read(p)
    _assert_tables_equal(got, ref)
    assert inj.injection_count() == 2
    st = dd.decode_stats()
    assert (st["fallback_columns"] + st["fallback_row_groups"]
            + st["fallback_files"]) > 0


def test_chaos_corrupt_page_with_verify_never_wrong(tmp_path):
    """A flipped byte that still decompresses/parses could silently decode
    wrong values; with the verify cross-check armed the mismatch (or the
    structural failure) demotes to host — results stay bit-identical."""
    p = _write(tmp_path, _mixed_table(2000), compression="snappy",
               row_group_size=700)
    ref = pq.read_table(p)
    inj = FaultInjector.get()
    inj.force("scan.read", "corrupt", 3)
    got = _device_read(
        p, {"spark.rapids.tpu.parquet.deviceDecode.verify": "true"})
    _assert_tables_equal(got, ref)
    assert inj.injection_count() == 3


def test_chaos_io_error_heals(tmp_path):
    p = _write(tmp_path, _mixed_table(1000), row_group_size=500)
    ref = pq.read_table(p)
    inj = FaultInjector.get()
    inj.force("scan.read", "io_error", 1)
    _assert_tables_equal(_device_read(p), ref)


# ---------------------------------------------------------------------------
# encrypted-parquet detection (reference GpuParquetScan.scala:590)
# ---------------------------------------------------------------------------


def _fake_encrypted_footer_file(tmp_path, name="enc.parquet"):
    """A parquet file whose tail carries the encrypted-footer PARE magic."""
    p = _write(tmp_path, pa.table({"a": pa.array([1, 2, 3], pa.int64())}),
               name=name)
    raw = bytearray(open(p, "rb").read())
    raw[-4:] = b"PARE"
    enc = str(tmp_path / ("pare_" + name))
    open(enc, "wb").write(bytes(raw))
    return enc


def test_encrypted_footer_message_names_file_and_reason(tmp_path):
    enc = _fake_encrypted_footer_file(tmp_path)
    s = TpuSession({})
    with pytest.raises(dd.ParquetEncryptedException) as ei:
        s.read.parquet(enc).to_arrow()
    msg = str(ei.value)
    assert enc in msg                       # names the file
    assert "encrypted" in msg               # names the reason
    assert "PARE" in msg
    assert "CPU" in msg                     # names the fallback route


def test_encrypted_footer_message_on_cpu_path(tmp_path):
    """The host/CPU scan path raises the same clean message instead of
    pyarrow's cryptic magic-bytes error."""
    enc = _fake_encrypted_footer_file(tmp_path)
    s = TpuSession({"spark.rapids.sql.enabled": "false"})
    with pytest.raises(dd.ParquetEncryptedException) as ei:
        s.read.parquet(enc).to_arrow()
    assert enc in str(ei.value) and "encrypted" in str(ei.value)


def test_plaintext_footer_crypto_metadata_detected(tmp_path):
    """Plaintext-footer mode: the footer parses but FileMetaData carries
    encryption_algorithm (field 8) — detection flags it without PARE."""
    p = _write(tmp_path, pa.table({"a": pa.array([1, 2, 3], pa.int64())}))
    raw = bytearray(open(p, "rb").read())
    flen = struct.unpack("<I", raw[-8:-4])[0]
    footer = bytes(raw[-8 - flen:-8])
    fields, endpos = dd._read_struct(footer, 0)
    last = max(fields)
    assert endpos == len(footer) and 0 < 8 - last <= 15
    # splice an empty struct at field id 8 (encryption_algorithm) before
    # the stop byte, then rewrite the footer length
    new_footer = footer[:endpos - 1] \
        + bytes([((8 - last) << 4) | 12, 0x00, 0x00])
    out = bytes(raw[:-8 - flen]) + new_footer \
        + struct.pack("<I", len(new_footer)) + b"PAR1"
    enc = str(tmp_path / "ptfooter.parquet")
    open(enc, "wb").write(out)
    reason = dd.detect_encryption(enc)
    assert reason is not None and "plaintext footer" in reason
    s = TpuSession({})
    with pytest.raises(dd.ParquetEncryptedException) as ei:
        s.read.parquet(enc).to_arrow()
    assert enc in str(ei.value)


def test_detect_encryption_negative(tmp_path):
    p = _write(tmp_path, pa.table({"a": pa.array([1], pa.int64())}))
    assert dd.detect_encryption(p) is None
    short = str(tmp_path / "short.bin")
    open(short, "wb").write(b"tiny")
    assert dd.detect_encryption(short) is None


# ---------------------------------------------------------------------------
# ORC predicate pushdown oracle: pruning never changes results
# ---------------------------------------------------------------------------


def _orc_file(tmp_path, n=5000):
    import pyarrow.orc as paorc
    t = pa.table({
        "k": pa.array(np.arange(n, dtype=np.int64)),
        "v": pa.array([None if i % 5 == 0 else i * 0.5 for i in range(n)],
                      pa.float64()),
        "s": pa.array([f"g{i % 7}" for i in range(n)]),
    })
    p = str(tmp_path / "t.orc")
    paorc.write_table(t, p, stripe_size=64 << 10)
    return p


def test_orc_pushdown_oracle(tmp_path):
    """The same ORC query with scan filters pushed (default) and with the
    exact same predicate applied only above the scan must agree — pruning
    never changes results (and the CPU session agrees too)."""
    p = _orc_file(tmp_path)

    def q(s):
        return (s.read.orc(p)
                .filter((F.col("k") >= 1234) & (F.col("k") < 2500))
                .groupBy("s").agg(F.count(F.col("k")).alias("c"),
                                  F.sum(F.col("v")).alias("sv")))

    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=True)


def test_orc_pushdown_filters_reach_scan(tmp_path):
    """The scan-level pushdown itself prunes rows before the Filter exec:
    read through the TPU session and check the pushed filter produced
    exactly the filtered row set."""
    p = _orc_file(tmp_path, n=2000)
    s = TpuSession({})
    got = (s.read.orc(p).filter(F.col("k") == 77).to_arrow())
    assert got.num_rows == 1
    assert got.column("k").to_pylist() == [77]


# ---------------------------------------------------------------------------
# gather-free decode: dense index streams, dictionary -> PLAIN fallback by
# position, PLAIN from uint32 words — chosen from the page layout alone, and
# bit-identical to pyarrow whichever path a chunk takes
# ---------------------------------------------------------------------------


def _bucket(n):
    from spark_rapids_tpu.columnar.vector import bucket_capacity
    return bucket_capacity(n)


def _program_specs():
    return [spec for specs in dd._PROGRAMS for spec in specs]


def _varint_bytes(v):
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _literal_page(values, bw, groups_per_run=63):
    """One page's hybrid-encoded region made of bit-packed literal runs only
    (what parquet-cpp writes for non-repeating values: runs of <= 504)."""
    out = bytearray()
    step = groups_per_run * 8
    for a in range(0, len(values), step):
        part = np.asarray(values[a:a + step], np.uint64)
        groups = -(-len(part) // 8)
        slots = np.zeros(groups * 8, np.uint64)
        slots[:len(part)] = part
        bits = ((slots[:, None] >> np.arange(bw, dtype=np.uint64)) & 1) \
            .astype(np.uint8).reshape(-1)
        out += _varint_bytes((groups << 1) | 1)
        out += np.packbits(bits, bitorder="little").tobytes()
    return bytes(out)


def _walk_pages(pages, bw):
    """Run rows + staged parts of consecutive pages, as _stage_column
    accumulates them."""
    runs, parts, seen, bits = [], [], 0, 0
    for vals, region in pages:
        runs += dd._walk_runs(region, 0, len(region), bw, len(vals), seen,
                              bits)
        parts.append(region)
        seen += len(vals)
        bits += len(region) * 8
    return runs, parts, seen


@pytest.mark.parametrize("width", range(1, 33))
def test_dense_unpack_equals_run_expansion(width):
    """Every bit width 1..32 (a file cannot carry a 2^31-entry dictionary,
    so the kernel is driven from hand-encoded pages): pages of 504-value
    runs with a short last run, page value counts that are multiples of 8
    except the last, a total that is no multiple of 8 or 512."""
    import jax.numpy as jnp

    from spark_rapids_tpu.kernels import parquet_decode as K
    rng = np.random.default_rng(width)
    counts = [1016, 8, 2048, 900 + width]        # the last page ends mid-group
    vals = [rng.integers(0, 1 << width, n, dtype=np.uint64) for n in counts]
    runs, parts, n = _walk_pages(
        [(v, _literal_page(v, width)) for v in vals], width)
    want = np.concatenate(vals)
    cap = 4096
    lit = dd._literal_segments(runs, parts, n, cap)
    assert lit is not None
    (groups, slots), starts, counts, words = lit
    assert (groups, slots) == (((width, 1),), cap)
    assert (list(starts), list(counts)) == ([0], [n])
    got = K.unpack_dense_segments(jnp.asarray(words), groups, slots,
                                  jnp.asarray(starts), jnp.asarray(counts),
                                  cap)
    assert got.dtype == jnp.uint32
    assert np.array_equal(np.asarray(got)[:n], want)
    general = K.expand_runs(jnp.asarray(dd._pad_runs(runs)),
                            jnp.asarray(dd._pad_bytes(parts)), cap)
    assert np.array_equal(np.asarray(general)[:n], want.astype(np.int64))


@pytest.mark.parametrize("case", ["width_change", "page_ends_mid_group",
                                  "widths_out_of_order", "many_small_pages",
                                  "tiny", "rle_run", "truncated_run",
                                  "uneven_segments"])
def test_literal_segments_layouts(case):
    """What the walk may call dense: width changes and mid-group page ends
    split the stream into segments, staged by width and placed at their
    dense starts. The key holds the widths, a bucket of the segment count
    of each and one slot bucket, never a page's own count. An RLE run, a
    run the page end cuts short or segments too uneven to pad alike stay
    general."""
    import jax.numpy as jnp

    from spark_rapids_tpu.kernels import parquet_decode as K
    rng = np.random.default_rng(11)

    def page(n, bw):
        v = rng.integers(0, 1 << bw, n, dtype=np.uint64)
        return v, _literal_page(v, bw), bw

    cap = 8192
    if case == "width_change":
        pages = [page(1024, 9), page(520, 9), page(2048, 10), page(77, 11)]
        want_key = (((9, 1), (10, 1), (11, 1)), 2048)
    elif case == "page_ends_mid_group":
        pages = [page(1001, 7), page(1024, 7), page(3, 7)]
        want_key = (((7, 2),), 2048)             # the 3-value page continues
    elif case == "widths_out_of_order":          # staged by width, not order
        pages = [page(100, 9), page(1001, 7), page(50, 9), page(8, 7),
                 page(300, 12), page(77, 7)]
        want_key, cap = (((7, 4), (9, 2), (12, 1)), 1024), 2048
    elif case == "many_small_pages":
        pages = [page(9, 5) for _ in range(10)] + [page(100, 5)]
        want_key, cap = (((5, 16),), 128), 512
    elif case == "tiny":                         # an output bucket under 32
        pages = [page(3, 2), page(6, 3)]
        want_key, cap = (((2, 1), (3, 1)), 32), 16
    elif case == "uneven_segments":
        pages = [page(4099, 5)] + [page(9, 5) for _ in range(9)]
        want_key = None
    else:
        pages = [page(1024, 6), page(512, 6)]
        want_key = None
    runs, parts, seen, bits, want = [], [], 0, 0, []
    for vals, region, bw in pages:
        if case == "rle_run" and seen:
            region = _varint_bytes(40 << 1) + b"\x05" + region
            vals = np.concatenate([np.full(40, 5, np.uint64), vals])
        if case == "truncated_run" and seen:
            region = region[:-3]
        runs += dd._walk_runs(region, 0, len(region), bw, len(vals), seen,
                              bits)
        parts.append(region)
        want.append(vals)
        seen += len(vals)
        bits += len(region) * 8
    lit = dd._literal_segments(runs, parts, seen, cap)
    if want_key is None:
        assert lit is None
        return
    key, starts, counts, words = lit
    assert key == want_key
    assert int(counts.sum()) == seen
    assert len(starts) == len(counts) == sum(k for _, k in key[0])
    got = K.unpack_dense_segments(jnp.asarray(words), *key,
                                  jnp.asarray(starts), jnp.asarray(counts),
                                  cap)
    assert got.shape == (cap,)
    assert np.array_equal(np.asarray(got)[:seen], np.concatenate(want))
    general = K.expand_runs(jnp.asarray(dd._pad_runs(runs)),
                            jnp.asarray(dd._pad_bytes(parts)), cap)
    assert np.array_equal(np.asarray(general)[:seen],
                          np.asarray(got)[:seen].astype(np.int64))


@pytest.mark.parametrize("counts", [(1001, 1003, 999, 520), (900, 1010, 1015),
                                    (515, 77, 1020, 9)])
def test_dense_key_holds_no_page_counts(counts):
    """Page structures that differ only in where their pages end — all
    inside a group of 8 — share one program key: the widths, a bucket of
    the segment count, one slot bucket. Where the pages end is data."""
    rng = np.random.default_rng(sum(counts))
    vals = [rng.integers(0, 1 << 7, n, dtype=np.uint64) for n in counts]
    runs, parts, n = _walk_pages([(v, _literal_page(v, 7)) for v in vals], 7)
    key, starts, counts_, _ = dd._literal_segments(runs, parts, n, 4096)
    assert key == (((7, 4),), 1024)
    assert sorted(counts_[counts_ > 0].tolist()) == sorted(counts)
    assert sorted(starts[counts_ > 0].tolist()) == \
        np.cumsum((0,) + counts[:-1]).tolist()


def _index_table(n, card, dtype=pa.int32()):
    """Values whose dictionary has `card` entries and never repeats back to
    back, so parquet-cpp writes bit-packed literal runs only."""
    v = pa.array((np.arange(n, dtype=np.int64) * 7919) % card + 1000).cast(dtype)
    return pa.Table.from_arrays([v], schema=pa.schema(
        [pa.field("v", dtype, nullable=False)]))


@pytest.mark.parametrize("width,n,pages,version", [
    (1, 1003, "one", "1.0"), (2, 4099, "many", "2.0"),
    (3, 515, "one", "2.0"), (4, 5000, "many", "1.0"),
    (5, 2051, "many", "2.0"), (6, 70003, "many", "1.0"),
    (7, 1021, "one", "1.0"), (8, 9001, "many", "2.0"),
    (9, 20011, "many", "1.0"), (10, 3001, "many", "2.0"),
    (11, 2500, "one", "1.0"), (12, 70001, "one", "2.0"),
    (13, 9999, "one", "1.0"), (14, 20003, "one", "2.0"),
    (15, 40003, "one", "1.0"), (16, 70003, "one", "2.0"),
    (17, 131111, "one", "1.0"),
])
def test_dense_dictionary_index_oracle(tmp_path, width, n, pages, version):
    """All-literal, one-width dictionary index streams from a real writer,
    bit for bit against pyarrow: one page and many, data page v1 and v2,
    row counts that are no multiple of 8 or of a run's 504 values."""
    card = (1 << (width - 1)) + 1
    kw = {"data_page_size": 1 << 26} if pages == "one" \
        else {"data_page_size": 512}      # the dictionary fills in page one
    p = _write(tmp_path, _index_table(n, card), compression="snappy",
               data_page_version=version, dictionary_pagesize_limit=1 << 22,
               **kw)
    _assert_tables_equal(_device_read(p), pq.read_table(p))
    (spec,) = _program_specs()
    assert spec[0] == "dict" and spec[8] is None
    assert spec[6][0] == "dense"
    # (a big dictionary is cut into several pages whatever the page size:
    # the widths then grow along the chunk, one segment each)
    widths = [w for w, _ in spec[6][1]]
    assert widths[-1] == width and widths == sorted(set(widths))
    assert widths == [width] or width >= 14
    st = dd.decode_stats()
    assert (st["values"], st["dense_values"]) == (n, n)
    assert st["fallback_columns"] == 0


@pytest.mark.parametrize("version", ["1.0", "2.0"])
@pytest.mark.parametrize("n", [1, 2, 7, 9, 16, 17, 31, 33])
def test_dense_tiny_row_groups(tmp_path, n, version):
    """Row groups whose output bucket is smaller than a group of 32 slots
    (a short trailing row group of a REQUIRED dictionary column): the dense
    unpack works on at least 32 slots and the bucket is cut from it."""
    t = pa.Table.from_arrays(
        [pa.array(np.arange(n, dtype=np.int32) * 3 + 5),
         pa.array(np.arange(n, dtype=np.float64) / 4 - 1)],
        schema=pa.schema([pa.field("v", pa.int32(), nullable=False),
                          pa.field("x", pa.float64(), nullable=False)]))
    p = _write(tmp_path, t, compression="snappy", data_page_version=version)
    _assert_tables_equal(_device_read(p), pq.read_table(p))
    specs = _program_specs()
    assert [sp[0] for sp in specs] == ["dict", "dict"]
    st = dd.decode_stats()
    assert st["values"] == 2 * n and st["fallback_columns"] == 0
    if n > 1:                     # (one value: a zero-width RLE run, general)
        assert all(sp[6][0] == "dense" and sp[6][3] == max(16, _bucket(n))
                   for sp in specs)
        assert st["dense_values"] == 2 * n


@pytest.mark.parametrize("case", ["rle_run_among_literals", "nullable",
                                  "nullable_no_nulls", "boolean", "string"])
def test_layouts_that_stay_general(tmp_path, case):
    """Layouts the dense path must not take: equal to pyarrow all the same,
    counted under `values` only and under the reason they stayed general.
    (An RLE run among the literals was one of them before the mixed
    expansion: it is dense now, by the boundary table.)"""
    n = 5003
    v = (np.arange(n, dtype=np.int64) * 7919) % 50
    if case == "rle_run_among_literals":
        v[2000:2100] = 7
        t = pa.table({"v": pa.array(v.astype(np.int32))})
    elif case == "nullable":
        t = pa.table({"v": pa.array([None if i % 5 == 0 else int(x)
                                     for i, x in enumerate(v)], pa.int32())})
    elif case == "nullable_no_nulls":
        t = pa.table({"v": pa.array([int(x) for x in v], pa.int32())})
    elif case == "boolean":
        t = pa.table({"v": pa.array(v % 2 == 0)})
    else:
        t = pa.table({"v": pa.array([f"s{x}" for x in v])})
    schema = None
    if case in ("rle_run_among_literals", "boolean", "string"):
        schema = pa.schema([pa.field("v", t.schema.field("v").type,
                                     nullable=False)])
        t = t.cast(schema)
    p = _write(tmp_path, t, compression="snappy")
    _assert_tables_equal(_device_read(p), pq.read_table(p))
    specs = _program_specs()
    assert not any(sp[0] == "dict" and sp[6][0] == "dense" for sp in specs)
    st = dd.decode_stats()
    reason = {"rle_run_among_literals": None, "nullable": "nullable",
              "nullable_no_nulls": "nullable", "boolean": "boolean",
              "string": "variable_length_dictionary"}[case]
    assert (st["values"], st["dense_values"]) == (n, 0 if reason else n)
    assert st["fallback_columns"] == 0
    assert {k[8:]: c for k, c in st.items()
            if k.startswith("general_") and c} == \
        ({reason: 1} if reason else {})
    if reason is None:
        assert specs[0][6][0] == "mixed"


# ---------------------------------------------------------------------------
# mixed index streams (RLE runs among the literal runs) and fixed-length
# dictionary strings: no search an element, chosen from the walked layout
# ---------------------------------------------------------------------------


def _hybrid_page(pieces, bw):
    """One page's hybrid region from ("lit", values) / ("rle", value, count)
    pieces, and the values it decodes to. A literal piece whose length is no
    multiple of 8 pads its last group (legal only as a page's last run)."""
    out, vals = bytearray(), []
    for piece in pieces:
        if piece[0] == "lit":
            v = np.asarray(piece[1], np.uint64)
            out += _literal_page(v, bw, groups_per_run=1 << 20) if bw else \
                _varint_bytes((-(-len(v) // 8) << 1) | 1)
            vals.append(v)
        else:
            _, value, count = piece
            out += _varint_bytes(count << 1) \
                + int(value).to_bytes((bw + 7) // 8, "little")
            vals.append(np.full(count, value, np.uint64))
    return np.concatenate(vals), bytes(out)


@pytest.mark.parametrize("layout", ["rle_8_to_17", "one_long_run",
                                    "page_ends_mid_group", "widths_change",
                                    "rle_only", "runs_within_a_bucket"])
@pytest.mark.parametrize("width", [0, 1, 2, 3, 12])
def test_mixed_segments_equal_run_expansion(width, layout):
    """Hand-encoded index streams with RLE runs among the literal runs: the
    boundary table and the header-free literal stream decode to what the
    run table decodes to. RLE counts are no multiples of 8, pages end
    inside a group of 8, widths change between pages, a one-entry
    dictionary has width 0. The key holds the widths, their slot buckets
    and a bucket of the boundary count."""
    import jax.numpy as jnp

    from spark_rapids_tpu.kernels import parquet_decode as K
    rng = np.random.default_rng(width * 7 + len(layout))

    def lit(n, bw=width):
        return ("lit", rng.integers(0, 1 << bw, n, dtype=np.uint64))

    def rle(count, bw=width):
        return ("rle", int(rng.integers(0, 1 << bw)), count)

    if layout == "rle_8_to_17":
        pages = [([lit(64)] + [x for c in range(8, 18)
                               for x in (rle(c), lit(8 * (c % 3 + 1)))],
                  width)]
    elif layout == "one_long_run":
        pages = [([lit(504), rle(3001), lit(16)], width),
                 ([rle(9), lit(24)], width)]
    elif layout == "page_ends_mid_group":
        pages = [([rle(11), lit(1003)], width), ([lit(21)], width),
                 ([lit(8), rle(13), lit(5)], width)]
    elif layout == "widths_change":
        pages = [([lit(40, width), rle(9, width), lit(19, width)], width),
                 ([lit(512, width + 1), rle(17, width + 1)], width + 1),
                 ([rle(10, width), lit(7, width)], width),
                 ([lit(100, width + 5)], width + 5)]
    elif layout == "rle_only":
        pages = [([rle(700), rle(9)], width), ([rle(15)], width)]
    else:
        pages = [([x for _ in range(20 + width) for x in (lit(16), rle(9))],
                  width)]
    runs, parts, want, seen, bits = [], [], [], 0, 0
    for pieces, bw in pages:
        vals, region = _hybrid_page(pieces, bw)
        runs += dd._walk_runs(region, 0, len(region), bw, len(vals), seen,
                              bits)
        parts.append(region)
        want.append(vals)
        seen += len(vals)
        bits += len(region) * 8
    want = np.concatenate(want)
    cap = _bucket(seen)
    assert dd._literal_segments(runs, parts, seen, cap) is None
    spec, arrays, general = dd._stage_indices(runs, parts, seen, cap)
    assert general is None and spec[0] == "mixed" and spec[3] == cap
    widths = sorted({bw for pieces, bw in pages if bw
                     and any(p[0] == "lit" for p in pieces)})
    assert [w for w, _ in spec[1]] == widths
    assert all(s % 32 == 0 and s == max(32, _bucket(s)) for _, s in spec[1])
    bounds = arrays[0]
    assert bounds.dtype == np.int32 and bounds.shape == (4, spec[2])
    assert spec[2] == _bucket(int((bounds[0] < cap).sum()))
    if layout == "runs_within_a_bucket":     # 40..64 boundaries: one bucket
        assert spec[2] == 64
    got = K.expand_mixed(jnp.asarray(arrays[1]) if widths else None, spec[1],
                         jnp.asarray(bounds), cap)
    assert got.dtype == jnp.uint32 and got.shape == (cap,)
    assert np.array_equal(np.asarray(got)[:seen], want)
    general_side = K.expand_runs(jnp.asarray(dd._pad_runs(runs)),
                                 jnp.asarray(dd._pad_bytes(parts)), cap)
    assert np.array_equal(np.asarray(general_side)[:seen],
                          want.astype(np.int64))


def test_mixed_segments_turn_down_a_truncated_run():
    """A literal run the page end cuts short (its header promises more
    bytes than the page holds) keeps the run table, under its reason."""
    rng = np.random.default_rng(3)
    vals, region = _hybrid_page(
        [("rle", 2, 9), ("lit", rng.integers(0, 8, 61, dtype=np.uint64))], 3)
    region = region[:-2]
    runs = dd._walk_runs(region, 0, len(region), 3, len(vals), 0, 0)
    spec, arrays, general = dd._stage_indices(runs, [region], len(vals), 128)
    assert (spec[0], general) == ("runs", "truncated_run")


def _runny_values(n, card, rng):
    """Uniform draws over `card` values with RLE-able stretches planted:
    runs of 8..17 equal values, and one long one."""
    v = rng.integers(0, card, n)
    at = 100
    for c in list(range(8, 18)) * 3:
        v[at:at + c] = v[at]
        at += c + int(rng.integers(30, 90))
    v[n // 2:n // 2 + 1500] = v[n // 2]
    return v


@pytest.mark.parametrize("version", ["1.0", "2.0"])
@pytest.mark.parametrize("width", [0, 1, 2, 3, 12, "grows"])
@pytest.mark.parametrize("kind", ["string", "int64"])
def test_mixed_dictionary_index_oracle(tmp_path, kind, width, version):
    """Writer-made index streams with RLE runs among the literals, bit for
    bit against pyarrow, for a fixed-length string column and a fixed-width
    dictionary column: many pages (they end inside groups of 8), data page
    v1 and v2, a dictionary that grows between pages (widths change). Every
    value is counted dense: no search for the indices, none for the chars."""
    n = 20011
    rng = np.random.default_rng(41)
    if width == "grows":
        v = np.concatenate([_runny_values(6000, 3, rng),
                            _runny_values(n - 6000, 300, rng)])
    else:
        card = 1 if width == 0 else (1 << (width - 1)) + 1
        v = _runny_values(n, card, rng)
    if kind == "string":
        arr = pa.array([f"{x:05d}" for x in v])
    else:
        arr = pa.array(v.astype(np.int64) * 1_000_003)
    t = pa.Table.from_arrays([arr], schema=pa.schema(
        [pa.field("v", arr.type, nullable=False)]))
    p = _write(tmp_path, t, compression="snappy", data_page_version=version,
               data_page_size=600, write_batch_size=250,
               dictionary_pagesize_limit=1 << 22)
    _assert_tables_equal(_device_read(
        p, {"spark.rapids.tpu.parquet.deviceDecode.verify": "true"}),
        pq.read_table(p))
    (spec,) = _program_specs()
    idx_spec = spec[4] if kind == "string" else spec[6]
    assert spec[0] == ("str_fixed" if kind == "string" else "dict")
    assert idx_spec[0] == "mixed"
    widths = [w for w, _ in idx_spec[1]]
    if width == "grows":
        assert len(widths) > 1 and widths == sorted(set(widths))
    elif width:                  # (a one-entry dictionary: zero-width runs)
        assert widths[-1] == width
    st = dd.decode_stats()
    assert (st["values"], st["dense_values"]) == (n, n)
    assert st["fallback_columns"] == 0
    assert not any(c for k, c in st.items() if k.startswith("general_"))


def _decode_columns(path, names):
    """Row group 0 of `path` through the device decoder: {name: column}."""
    from types import SimpleNamespace

    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.types import StringType
    attrs = [SimpleNamespace(name=c, dtype=StringType()) for c in names]
    with dd.DeviceFileDecoder(path, attrs, RapidsConf({})) as dec:
        batch = dec.decode_row_group(0)
    return dict(zip(names, batch.columns))


@pytest.mark.parametrize("version", ["1.0", "2.0"])
@pytest.mark.parametrize("length", [0, 1, 3])
def test_fixed_length_dictionary_strings(tmp_path, length, version):
    """A REQUIRED string column whose dictionary entries all have one byte
    length takes no ragged path — offsets an iota, chars a take from the
    dictionary's matrix, codes the indices — beside a nullable, a
    variable-length and a PLAIN string column of the same file, which keep
    the general side and are not counted dense. Buffers are what the
    general side gives: offsets, chars padded with zeros to the char
    bucket, the parquet dictionary and its indices as the `dict_encoding`."""
    n = 3001
    rng = np.random.default_rng(length)
    v = _runny_values(n, 1 if length == 0 else 3, rng)
    words = ["", "", ""] if length == 0 else \
        [w[:length] for w in ("RAN", "AFO", "NXY")]
    fixed = [words[x] for x in v]
    t = pa.Table.from_arrays(
        [pa.array(fixed),
         pa.array([None if i % 7 == 0 else s for i, s in enumerate(fixed)]),
         pa.array([("ab", "c", "defg")[i * 7 % 3] for i in range(n)]),
         pa.array([f"p{x}" for x in v])],
        schema=pa.schema([pa.field("fixed", pa.string(), nullable=False),
                          pa.field("nullable", pa.string()),
                          pa.field("ragged", pa.string(), nullable=False),
                          pa.field("plain", pa.string(), nullable=False)]))
    p = _write(tmp_path, t, compression="snappy", data_page_version=version,
               use_dictionary=["fixed", "nullable", "ragged"],
               data_page_size=500, write_batch_size=200)
    _assert_tables_equal(_device_read(p), pq.read_table(p))
    kinds = {sp[0]: sp for sp in _program_specs()}
    assert set(kinds) == {"str_fixed", "str_dict", "str_plain"}
    assert kinds["str_fixed"][6] == length
    st = dd.decode_stats()
    assert (st["values"], st["dense_values"]) == (4 * n, n)
    assert (st["general_nullable"], st["general_variable_length_dictionary"],
            st["general_plain_strings"]) == (1, 1, 1)
    # the buffers themselves, and the dictionary encoding
    dd.reset_for_tests()
    cols = _decode_columns(p, ["fixed", "ragged"])
    cap = _bucket(n)
    ref = pq.read_table(p, read_dictionary=["fixed", "ragged"])
    for name, col in cols.items():
        want = ref.column(name).combine_chunks()
        plain = want.cast(pa.string())
        offs = np.asarray(col.offsets)
        chars = np.asarray(col.data)
        want_offs = np.frombuffer(plain.buffers()[1], np.int32, n + 1)
        total = int(want_offs[-1])
        assert offs.dtype == np.int32 and offs.shape == (cap + 1,)
        assert np.array_equal(offs[:n + 1], want_offs)
        assert (offs[n:] == total).all()
        assert chars.dtype == np.uint8 and chars.shape == (_bucket(total),)
        assert bytes(chars[:total]) == \
            (plain.buffers()[2].to_pybytes()[:total] if total else b"")
        assert not chars[total:].any()
        assert col.validity is None
        codes, dictionary = col.dict_encoding
        codes = np.asarray(codes)
        assert codes.dtype == np.int32 and codes.shape == (cap,)
        assert np.array_equal(codes[:n], want.indices.to_numpy())
        assert not codes[n:].any()
        assert dictionary.to_arrow().to_pylist() == \
            want.dictionary.to_pylist()


def _chunk_pages(path):
    """(page type, page bytes) of the first column chunk of row group 0."""
    cc = pq.ParquetFile(path).metadata.row_group(0).column(0)
    start, length = dd._chunk_range(cc)
    with open(path, "rb") as f:
        f.seek(start)
        chunk = f.read(length)
    pages, pos = [], 0
    while pos < len(chunk):
        hdr, dpos = dd._read_struct(chunk, pos)
        pages.append((hdr[1], chunk[pos:dpos + hdr[3]]))
        pos = dpos + hdr[3]
    return pages


def _decode_spliced(path, chunk, name, dtype):
    """Row group 0 of `path` decoded with its column chunk's bytes replaced:
    pages of two files of the same values spliced into layouts no writer
    option produces on demand."""
    from types import SimpleNamespace

    from spark_rapids_tpu.config import RapidsConf
    attr = SimpleNamespace(name=name, dtype=dtype)
    with dd.DeviceFileDecoder(path, [attr], RapidsConf({})) as dec:
        real = dec.reader
        dec.reader = SimpleNamespace(read=lambda start, length: chunk,
                                     close=real.close)
        return dec.decode_row_group(0).to_arrow().column(name)


@pytest.mark.parametrize("nullable", [False, True], ids=["required", "nullable"])
@pytest.mark.parametrize("version", ["1.0", "2.0"])
@pytest.mark.parametrize("case", [
    "switch_at_page_boundary", "switch_after_first_page",
    "empty_dictionary_prefix", "empty_plain_tail", "interleaved",
    "growing_dictionary_then_plain", "prefix_of_5", "prefix_of_16",
    "prefix_of_32"])
def test_dictionary_fallback_chunk_layouts(tmp_path, case, version, nullable):
    """The dictionary -> PLAIN fallback chunk in every order of its pages:
    the same values written dictionary-encoded and PLAIN with equal page
    boundaries, then spliced. One switch point is a concatenation (prefix
    in its own bucket, PLAIN placed by position); interleaved pages keep
    the segment-table merge. A prefix of a few values has an output bucket
    under the 32 slots a dense group takes."""
    from spark_rapids_tpu.types import DoubleType
    n, per_page = 3000, 256
    if case.startswith("prefix_of_"):
        per_page = int(case.rsplit("_", 1)[1])
        n = 20 * per_page + 3
    rng = np.random.default_rng(5)
    if case == "growing_dictionary_then_plain":
        vals = rng.random(n) * 1e5               # every page adds entries
    else:
        vals = ((np.arange(n) * 7919) % 50) / 8.0    # dictionary fills at once
    arr = pa.array([None if nullable and i % 7 == 3 else float(x)
                    for i, x in enumerate(vals)], pa.float64())
    t = pa.Table.from_arrays([arr], schema=pa.schema(
        [pa.field("x", pa.float64(), nullable=nullable)]))
    kw = dict(compression="snappy", write_batch_size=per_page,
              data_page_size=1, data_page_version=version)
    dp = _write(tmp_path, t, "dict.parquet", use_dictionary=True, **kw)
    pp = _write(tmp_path, t, "plain.parquet", use_dictionary=False, **kw)
    dict_pages, plain_pages = _chunk_pages(dp), _chunk_pages(pp)
    assert dict_pages[0][0] == dd._PAGE_DICT
    head, dpages = dict_pages[0][1], [b for _, b in dict_pages[1:]]
    ppages = [b for _, b in plain_pages]
    k = len(dpages)
    assert k == len(ppages) == -(-n // per_page)
    switch = {"switch_at_page_boundary": k // 2, "switch_after_first_page": 1,
              "empty_dictionary_prefix": 0, "empty_plain_tail": k,
              "growing_dictionary_then_plain": 5}.get(
                  case, 1 if case.startswith("prefix_of_") else None)
    if case == "interleaved":
        body = [ppages[i] if i % 3 == 1 else dpages[i] for i in range(k)]
    else:
        body = dpages[:switch] + ppages[switch:]
    got = _decode_spliced(dp, head + b"".join(body), "x", DoubleType())
    assert got.combine_chunks().equals(arr)
    (spec,) = _program_specs()
    st = dd.decode_stats()
    assert st["values"] == n and st["fallback_columns"] == 0
    if case == "empty_dictionary_prefix":
        assert spec[0] == "plain"
        dense = n
    elif case == "interleaved":
        assert spec[8][0] == "segments" and spec[6][0] == "runs"
        assert spec[6][3] == spec[-1]            # expands over the capacity
        dense = 0
    elif case == "empty_plain_tail":
        assert spec[8] is None
        dense = n
    else:
        assert spec[8] == ("tail",)
        assert spec[6][3] < spec[-1]             # the prefix's own bucket
        assert spec[6][3] == max(16, _bucket(switch * per_page))
        dense = n
    if nullable:
        # definition levels are a per-element run lookup of their own
        assert spec[0] == "plain" or spec[6][0] == "runs"
        dense = 0
    elif case == "growing_dictionary_then_plain":
        assert spec[6][0] == "dense" and len(spec[6][1]) > 1   # widths grow
    assert st["dense_values"] == dense


def test_writer_made_fallback_chunk_oracle(tmp_path):
    """parquet-cpp's own dictionary overflow (dictionary_pagesize_limit):
    dictionary pages, then PLAIN pages, in one chunk — beside an ordinary
    dictionary column and a PLAIN one in the same program."""
    n = 9001
    rng = np.random.default_rng(9)
    t = pa.table({"x": pa.array(rng.random(n) * 1e5),
                  "q": pa.array(((np.arange(n) * 7919) % 50).astype(np.int32)),
                  "k": pa.array(rng.integers(-2**62, 2**62, n))})
    t = t.cast(pa.schema([pa.field(c, t.schema.field(c).type, nullable=False)
                          for c in t.column_names]))
    p = _write(tmp_path, t, compression="snappy", use_dictionary=["x", "q"],
               dictionary_pagesize_limit=20000, data_page_size=1000,
               write_batch_size=100)
    _assert_tables_equal(_device_read(
        p, {"spark.rapids.tpu.parquet.deviceDecode.verify": "true"}),
        pq.read_table(p))
    x, q, k = _program_specs()
    assert x[8] == ("tail",) and k[0] == "plain"
    # q's pages end inside a group of 8, so each opens a segment: in the
    # key they are one width, a bucket of their number and one slot bucket
    assert q[6][:3] == ("dense", ((6, 16),), 1024)
    assert x[6][0] == "dense"
    st = dd.decode_stats()
    assert st["values"] == 3 * n and st["fallback_columns"] == 0
    assert st["dense_values"] == 3 * n


def _gathers(jaxpr, out):
    """(output elements, operand shape) of every gather of a jaxpr, nested
    calls and loop bodies included (a loop body counts once)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out.append((int(np.prod(eqn.outvars[0].aval.shape)),
                        tuple(eqn.invars[0].aval.shape)))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _gathers(inner, out)
    return out


def test_cell_file_program_is_gather_free(tmp_path, monkeypatch):
    """Structural guard on the benchmark cell's own layout (one 2^20-row
    group of chipbench's file, the four columns Q6 reads): the decode
    program holds one 2^20-element gather per dictionary column — the
    dictionary lookup — and none over a run table, so a later refactor
    cannot bring the 112 gathers an element back unnoticed (PERF.md PR 25).
    A count made on the CPU, not a timing."""
    import jax
    from chipbench import datagen
    rows = 1 << 20
    path = str(tmp_path / "cell.parquet")
    datagen.write_parquet(path, 25, rows, keep=())
    captured = []
    build = dd._build_program

    def recording(specs):
        fn = build(specs)

        def call(*args):
            captured.append((specs, fn, args))
            return fn(*args)
        return call
    monkeypatch.setattr(dd, "_build_program", recording)
    cols = ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]
    got = TpuSession({}).read.parquet(path).select(*cols).to_arrow()
    _assert_tables_equal(got, pq.read_table(path, columns=cols))
    (specs, fn, args), = captured
    by_col = dict(zip(cols, specs))
    for c in ("l_quantity", "l_shipdate"):       # all-literal, one width
        assert by_col[c][6][0] == "dense" and len(by_col[c][6][1]) == 1
        assert by_col[c][8] is None
    for c in ("l_extendedprice", "l_discount"):  # dictionary, then PLAIN
        assert by_col[c][8] == ("tail",) and by_col[c][6][0] == "dense"
        assert by_col[c][6][3] == 1 << 18        # 131,088 values' bucket
    gathers = _gathers(jax.make_jaxpr(fn)(*args).jaxpr, [])
    full = [g for g in gathers if g[0] >= rows]
    assert len(full) == 2, gathers               # quantity, shipdate
    assert sum(n for n, _ in gathers) <= 2.5 * rows + 1024, gathers
    assert not any(len(shape) == 2 for _, shape in gathers), gathers
    st = dd.decode_stats()
    assert (st["values"], st["dense_values"]) == (4 * rows, 4 * rows)


def _recorded_programs(monkeypatch):
    """(specs, program, arguments) of every decode program launched from
    here on."""
    captured = []
    build = dd._build_program

    def recording(specs):
        fn = build(specs)

        def call(*args):
            captured.append((specs, fn, args))
            return fn(*args)
        return call
    monkeypatch.setattr(dd, "_build_program", recording)
    return captured


def _primitives(jaxpr, out):
    """Names of every primitive of a jaxpr and of the calls nested in it
    (a `pjit` adds the name of the function it calls)."""
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        if "name" in eqn.params:
            out.add(str(eqn.params["name"]))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, out)
    return out


def test_cell_file_q1_program_has_no_search(tmp_path, monkeypatch):
    """Structural guard on `parquet-q1-stream`'s own layout (one 2^20-row
    group of chipbench's file, the seven columns Q1 reads): the two CHAR(1)
    columns' index streams hold RLE runs among their literal runs, and the
    program still holds no `searchsorted` and no sort, and at most two
    2^20-element gathers a string column (the literal stream by position,
    the dictionary's matrix by index). A count made on the CPU, not a
    timing (PERF.md PR 28)."""
    import jax
    from chipbench import datagen
    rows = 1 << 20
    path = str(tmp_path / "cell.parquet")
    datagen.write_parquet(path, 25, rows, keep=())
    captured = _recorded_programs(monkeypatch)
    cols = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
            "l_returnflag", "l_linestatus", "l_shipdate"]    # the file's order
    got = TpuSession({}).read.parquet(path).select(*cols).to_arrow()
    _assert_tables_equal(got, pq.read_table(path, columns=cols))
    (specs, fn, args), = captured
    by_col = dict(zip(cols, specs))
    for c in ("l_returnflag", "l_linestatus"):
        assert by_col[c][0] == "str_fixed" and by_col[c][6] == 1
        assert by_col[c][4][0] == "mixed" and len(by_col[c][4][1]) == 1
        assert by_col[c][9]                          # codes ride along
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    names = _primitives(jaxpr, set())
    assert not names & {"sort", "searchsorted", "while", "scan"}, names
    gathers = _gathers(jaxpr, [])
    full = [g for g in gathers if g[0] >= rows]
    # quantity, shipdate, and two a string column
    assert len(full) == 2 + 2 * 2, gathers
    assert sorted(shape for _, shape in full if len(shape) == 2) == \
        [(16, 1), (16, 1)], gathers                  # the dictionaries' rows
    st = dd.decode_stats()
    assert (st["values"], st["dense_values"]) == (7 * rows, 7 * rows)
    assert not any(c for k, c in st.items() if k.startswith("general_"))


def test_mixed_key_holds_no_run_counts(tmp_path, monkeypatch):
    """Row groups of the cell's CHAR(1) columns whose RLE-run and boundary
    counts differ (another seed's draws) inside one bucket share one
    program: the counts enter the key as buckets only."""
    from chipbench import datagen
    rows = 1 << 16
    seen = []
    mixed = dd._mixed_segments

    def recording(runs, parts, out_cap):
        out = mixed(runs, parts, out_cap)
        seen.append((len(runs), int((out[1][0] < out_cap).sum())))
        return out
    monkeypatch.setattr(dd, "_mixed_segments", recording)
    s = TpuSession({})
    for seed in (25, 26, 27):
        path = str(tmp_path / f"s{seed}.parquet")
        datagen.write_parquet(path, seed, rows, keep=())
        got = s.read.parquet(path).select("l_returnflag").to_arrow()
        _assert_tables_equal(got, pq.read_table(path,
                                                columns=["l_returnflag"]))
    assert len(set(seen)) == 3, seen     # other runs, other boundaries
    assert len({_bucket(b) for _, b in seen}) == 1, seen
    st = dd.decode_stats()
    assert (st["programs"], st["dispatches"]) == (1, 3)


@pytest.mark.parametrize("case", ["parent_without_counters",
                                  "nothing_decoded", "partly_dense",
                                  "all_dense", "decoded_from_a_real_scan",
                                  "parquet-q1"])
def test_benchmark_dense_share_reader(tmp_path, case):
    """chipbench's reader of `scan_dense_decode_share` on recorded
    counters: a program without them, or a window that decoded no value,
    reads as nothing (the metric is left out), never as 0 %."""
    import json
    from types import SimpleNamespace

    from chipbench import manifest
    from chipbench.readers import decode_value_share
    spec = json.load(open(os.path.join(
        manifest.HERE, "metrics", "scan_dense_decode_share.json")))
    assert spec["reader"] == "decode_value_share"
    old = {"dispatches": 3, "rows": 30, "fallback_columns": 0}
    before = dict(old, values=120, dense_values=100)
    if case == "parent_without_counters":
        ctx = SimpleNamespace(before={"decode": old},
                              after={"decode": dict(old, dispatches=9)})
        want = None
    elif case == "nothing_decoded":
        ctx = SimpleNamespace(before={"decode": before},
                              after={"decode": dict(before)})
        want = None
    elif case == "partly_dense":
        ctx = SimpleNamespace(before={"decode": before}, after={"decode": dict(
            before, values=120 + 4000, dense_values=100 + 3750)})
        want = 93.75
    elif case == "all_dense":
        ctx = SimpleNamespace(before={"decode": before}, after={"decode": dict(
            before, values=120 + 4000, dense_values=100 + 4000)})
        want = 100.0
    elif case == "parquet-q1":
        # a string column counts as dense only when both halves applied:
        # CHAR(1) flags (indices and chars without a search) do, a
        # variable-length dictionary (dense indices, ragged chars) does not
        first = dd.decode_stats()
        n = 4099
        v = _runny_values(n, 3, np.random.default_rng(1))
        t = pa.Table.from_arrays(
            [pa.array([("R", "A", "N")[x] for x in v]),
             pa.array([("O", "F")[x % 2] for x in v]),
             pa.array([("AIR", "REG AIR", "MAIL")[x] for x in v]),
             pa.array(v.astype(np.int64))],
            schema=pa.schema(
                [pa.field(c, ty, nullable=False) for c, ty in (
                    ("flag", pa.string()), ("status", pa.string()),
                    ("mode", pa.string()), ("qty", pa.int64()))]))
        p = _write(tmp_path, t)
        _assert_tables_equal(_device_read(p), pq.read_table(p))
        ctx = SimpleNamespace(before={"decode": first},
                              after={"decode": dd.decode_stats()})
        assert ctx.after["decode"]["general_variable_length_dictionary"] == 1
        want = 75.0
    else:
        first = dd.decode_stats()
        n = 4099
        p = _write(tmp_path, _index_table(n, 50).append_column(
            "nullable", pa.array([None if i % 3 == 0 else i
                                  for i in range(n)], pa.int64())))
        _assert_tables_equal(_device_read(p), pq.read_table(p))
        ctx = SimpleNamespace(before={"decode": first},
                              after={"decode": dd.decode_stats()})
        want = 50.0
    got = decode_value_share.read(ctx, **spec["args"])
    assert got is None if want is None else got == pytest.approx(want)


# ---------------------------------------------------------------------------
# tracelint: the new kernels classify device-clean
# ---------------------------------------------------------------------------


def test_parquet_decode_kernels_classify_device():
    from spark_rapids_tpu.analysis.registry_check import scan_kernels
    verdicts = scan_kernels()["kernels/parquet_decode.py"]
    assert verdicts, "kernel scan found no public parquet decode kernels"
    assert all(v == "device" for v in verdicts.values()), verdicts
