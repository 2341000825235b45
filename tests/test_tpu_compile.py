"""Ask the TPU's compiler, without a TPU: AOT compiles for a described
`v5e:2x2` device of the programs the served path launches on the chip.

The sandbox has libtpu but no chip; `jax.experimental.topologies` describes
one and `jit(...).lower(shapes).compile()` raises what the chip's compiler
would raise (Mosaic layout refusals, the X64 rewriter's UNIMPLEMENTED, HBM
overflow). Nothing runs, so these say nothing about results or times.

Product programs are not rebuilt by hand: the engine runs the real path on
the CPU backend with `jax.jit` recorded, and the very functions it built are
lowered again for the described device at the shapes it called them with.
Code that asks `utils/hw` about the backend is steered here, in the test.

Rules of this file (see /opt/skills/guides/on-chip-measurement §2): the
topology is described inside the module-scoped fixture, never at import, in
a skipif or in parametrize; everything compiles in the test's own process;
the persistent compile cache is off around the compiles; all such tests live
in this one file so one xdist worker loads libtpu.
"""

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.session import TpuSession

V5E_HBM_BYTES = 16 * 1024 ** 3
#: rows of the scanned row group. The smoke's row groups hold 2^20 rows, but
#: on this compiler a decode program then takes ~24 s to compile (the
#: def-level cumsum alone ~15 s; PR 22 compiled all of these at 2^20 by hand
#: and they passed) and tier-1 has no such room. What the compiler refuses
#: (a dtype, a bit view, a layout) it refuses at any size.
ROW_GROUP = 1 << 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an AOT TPU executable can be written to the persistent cache but not
    # read back without a chip: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile_for(jitted, args, kwargs, sharding):
    """Lower `jitted` for the described device at the shapes of one recorded
    call; returns the compiled executable."""
    def sds(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                        sharding=sharding)
        return x
    compiled = jitted.lower(*jax.tree.map(sds, args),
                            **jax.tree.map(sds, kwargs)).compile()
    _assert_fits_hbm(compiled)
    return compiled


def _assert_fits_hbm(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, f"program needs {total} B of 16 GiB HBM"


class _Recorded:
    """What `jax.jit(fn)` returned, remembering each call's arguments."""

    def __init__(self, fn, jitted, log):
        self.fn, self.jitted, self._log = fn, jitted, log

    def __call__(self, *args, **kwargs):
        self._log.append((self, args, kwargs))
        return self.jitted(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.jitted, name)


@contextlib.contextmanager
def _recording_jit(monkeypatch):
    """Every program the engine builds through `jax.jit` while this is
    active is recorded with its call arguments. The engine's process-wide
    program caches are emptied first (so the programs ARE built now) and
    afterwards (so no recorder outlives the test)."""
    from spark_rapids_tpu.execs import compiled, compiled_join, opjit
    from spark_rapids_tpu.io import device_decode

    def clear():
        opjit.clear_cache()
        compiled._STAGE_FN_CACHE.clear()
        compiled_join._JOIN_STAGE_FN_CACHE.clear()
        with device_decode._LOCK:
            device_decode._PROGRAMS.clear()

    log = []
    real = jax.jit

    def jit(fn, **kw):
        return _Recorded(fn, real(fn, **kw), log)

    clear()
    with monkeypatch.context() as m:
        m.setattr(jax, "jit", jit)
        try:
            yield log
        finally:
            clear()


def _calls(log, module_suffix):
    return [(r.jitted, a, k) for r, a, k in log
            if r.fn.__module__.endswith(module_suffix)]


# ---------------------------------------------------------------------------
# scan + compiled aggregation stage over one row group
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def row_group_file(tmp_path_factory):
    """One ROW_GROUP-row row group with every encoding the device decoder
    stages: PLAIN fixed-width, dictionary fixed-width, dictionary
    BYTE_ARRAY, PLAIN BYTE_ARRAY — and q1's column shapes."""
    rng = np.random.default_rng(22)
    n = ROW_GROUP
    t = pa.table({
        "l_quantity": pa.array(rng.integers(1, 51, n), pa.int64()),
        "l_extendedprice": pa.array(rng.uniform(900, 105000, n)),
        "l_discount": pa.array(rng.integers(0, 11, n) / 100.0),
        "l_tax": pa.array(rng.integers(0, 9, n) / 100.0),
        "l_returnflag": pa.array(np.array(list("RAN"))[rng.integers(0, 3, n)]),
        "l_linestatus": pa.array(np.array(list("OF"))[rng.integers(0, 2, n)]),
        "l_shipdate": pa.array(rng.integers(8035, 10590, n).astype(np.int32),
                               pa.int32()),
        "name": pa.array(np.char.add("Customer#",
                                     np.arange(n).astype(str))),
    })
    # the same flags REQUIRED, as chipbench's file declares them: the mixed
    # index stream and the fixed-length dictionary (no search an element)
    flag = t.column("l_returnflag").combine_chunks()
    t = t.append_column(pa.field("flag_required", flag.type, nullable=False),
                        flag)
    path = str(tmp_path_factory.mktemp("tpu_compile") / "rg.parquet")
    pq.write_table(t, path, row_group_size=n, compression="snappy",
                   use_dictionary=["l_discount", "l_tax", "l_returnflag",
                                   "l_linestatus", "flag_required"])
    return path


def _decode_programs(monkeypatch, path, columns, rows=ROW_GROUP):
    s = TpuSession({})
    with _recording_jit(monkeypatch) as log:
        out = s.read.parquet(path).select(*columns).agg(
            *[F.count(F.col(c)).alias(c) for c in columns]).collect()
        assert list(out[0].values()) == [rows] * len(columns)
        calls = _calls(log, "io.device_decode")
    assert len(calls) == 1, "one decode program per row group"
    return calls[0]


@pytest.mark.parametrize("columns", [
    ("l_extendedprice",),      # PLAIN fixed-width (8-byte, u64 -> f64 view)
    ("l_returnflag",),         # dictionary-encoded BYTE_ARRAY (RLE indices)
    ("name",),                 # PLAIN BYTE_ARRAY
    ("flag_required",),        # CHAR(1) REQUIRED: boundary table, iota offsets
], ids=["fixed_width", "dictionary", "byte_array", "fixed_length_dictionary"])
def test_parquet_decode_program_compiles_for_v5e(monkeypatch, one_chip,
                                                 row_group_file, columns):
    jitted, args, kwargs = _decode_programs(monkeypatch, row_group_file,
                                            columns)
    _compile_for(jitted, args, kwargs, one_chip)


@pytest.fixture(scope="module")
def star_files(tmp_path_factory):
    """ORDERS' and CUSTOMER's columns as `tpch-sf1-star-parquet` writes
    them, ROW_GROUP rows a group, with the writer's dictionary page cut so
    that the BIGINT keys outgrow it inside the row group as they do at 2^20
    rows: dictionary pages, then PLAIN pages."""
    from chipbench import datagen, manifest
    conf = manifest.Cell("parquet-q3-stream").config
    schema = datagen.tables(conf, ROW_GROUP * 4)        # ORDERS: 2^16 rows
    out = {}
    for name in ("orders", "customer"):
        t = schema[name]
        n = min(t.rows, ROW_GROUP)
        path = str(tmp_path_factory.mktemp("tpu_compile") / f"{name}.parquet")
        pq.write_table(t.to_arrow(t.generate(7, list(t.columns), 0, n)), path,
                       row_group_size=ROW_GROUP, compression="snappy",
                       use_dictionary=True, dictionary_pagesize_limit=64 << 10)
        out[name] = (path, n)
    return out


@pytest.mark.parametrize("table,columns", [
    ("orders", ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority")),
    ("customer", ("c_custkey", "c_mktsegment")),
], ids=["bigint_keys_that_change_encoding", "ragged_dictionary_strings"])
def test_star_scan_decode_programs_compile_for_v5e(monkeypatch, one_chip,
                                                   star_files, table, columns):
    """The decoder's sides that only Q3's files reach (ISSUE 33): a BIGINT
    chunk of dictionary pages then PLAIN pages beside an int64 dictionary of
    few entries, and a REQUIRED dictionary of strings of different lengths."""
    from test_parquet_q3 import data_page_encodings
    path, n = star_files[table]
    if table == "orders":
        pages = data_page_encodings(path)               # o_orderkey
        assert pages[0] == 8 and pages[-1] == 0, pages
        assert set(data_page_encodings(path, column=1)) == {8}      # o_custkey
    _compile_for(*_decode_programs(monkeypatch, path, columns, rows=n), one_chip)


def test_compiled_agg_stage_q1_shape_compiles_for_v5e(monkeypatch, one_chip,
                                                      row_group_file):
    import benchmarks.tpch as tpch
    s = tpch.make_session(tpu=True)
    with _recording_jit(monkeypatch) as log:
        df = tpch.q1(s, {"lineitem": s.read.parquet(row_group_file)})
        assert "TpuCompiledAggStage" in df.explain()
        assert len(df.collect()) == 6
        stages = _calls(log, "execs.compiled")
    assert stages, "q1 did not run the compiled aggregation stage"
    jitted, args, kwargs = stages[0]
    assert ROW_GROUP in {np.shape(a)[0] for a in jax.tree.leaves(args)
                         if np.ndim(a)}
    _compile_for(jitted, args, kwargs, one_chip)


# ---------------------------------------------------------------------------
# DOUBLE join keys where f64 has no bit view (what the v5e compiler says)
# ---------------------------------------------------------------------------

def test_join_key_encode_with_double_key_compiles_for_v5e(monkeypatch,
                                                          one_chip):
    from spark_rapids_tpu.utils import hw
    monkeypatch.setattr(hw, "f64_bit_views", lambda: False)
    rng = np.random.default_rng(3)
    k = np.round(rng.uniform(800, 600000, 4096), 2)
    s = TpuSession({"spark.sql.autoBroadcastJoinThreshold": "0"})
    left = s.createDataFrame(pa.table({"k": k, "lv": np.arange(4096)}))
    right = s.createDataFrame(pa.table({"k": k[::4], "rv": np.arange(1024)}))
    with _recording_jit(monkeypatch) as log:
        assert left.join(right, on="k").count() >= 1024
        programs = _calls(log, "execs.opjit")
    encodes = [c for c in programs
               if any(getattr(a, "dtype", None) == jnp.float64
                      for a in jax.tree.leaves(c[1]))]
    assert encodes, "no opjit program took the DOUBLE key column"
    for jitted, args, kwargs in encodes:
        _compile_for(jitted, args, kwargs, one_chip)
    # and the premise: the bit view itself is what this compiler refuses
    with pytest.raises(jax.errors.JaxRuntimeError, match="X64"):
        jax.jit(lambda x: jax.lax.bitcast_convert_type(x, jnp.int64)).lower(
            jax.ShapeDtypeStruct((8,), jnp.float64,
                                 sharding=one_chip)).compile()


# ---------------------------------------------------------------------------
# the fused join's three programs: the build prepared once (hash, sort, the
# bucket directory's scatter-add and prefix sum), the probe's one gather of a
# directory row a lane, the emit
# ---------------------------------------------------------------------------

def test_fused_join_programs_compile_for_v5e(monkeypatch, one_chip):
    from spark_rapids_tpu.execs import joins
    rng = np.random.default_rng(5)
    s = TpuSession({})
    fact = s.createDataFrame(pa.table({"fk": rng.integers(0, 4000, 1 << 14),
                                       "v": np.arange(1 << 14)}))
    dim = s.createDataFrame(pa.table({"pk": np.arange(0, 4000, 2),
                                      "w": np.arange(2000) * 0.5}))
    df = fact.join(dim, on=fact["fk"] == dim["pk"]) \
        .select((F.col("v") + 1).alias("v1"), "w")
    assert "HashJoin+Project]" in df.explain()      # the join in a segment
    with _recording_jit(monkeypatch) as log:
        assert df.count() > 0
        programs = _calls(log, "execs.opjit")
    # the build's program is the one that hands a directory back, the
    # probe's the one that takes it
    dir_shape = (1 << joins.dir_bits(2048), 2)
    takes = [c for c in programs if any(
        np.shape(a) == dir_shape for a in jax.tree.leaves(c[1]))]
    assert len(takes) == 1, "no program took the build's directory"
    assert len(programs) >= 3           # joinbuild, joinprobe, joinemit
    for jitted, args, kwargs in programs:
        compiled = _compile_for(jitted, args, kwargs, one_chip)
        if (jitted, args, kwargs) == takes[0]:
            # a lane's range costs gathers, no loop: the binary search is gone
            assert "while" not in compiled.as_text()


# ---------------------------------------------------------------------------
# the four-chip collective exchange
# ---------------------------------------------------------------------------

def test_mesh_exchange_program_compiles_for_four_v5e_chips(monkeypatch, topo):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_tpu.parallel import mesh as pm
    # compile the variant the chip runs: the destination ids donated
    monkeypatch.setattr(pm, "_donate", lambda positions: tuple(positions))
    n_dev, cap, slot_cap = 4, 1 << 12, 1 << 10
    mesh = Mesh(np.array(topo.devices), (pm._AXIS,))
    sig = (("int64", True), ("float64", False), ("int32", True))
    fn = pm._build_exchange(mesh, n_dev, slot_cap, sig)
    try:
        sharded = NamedSharding(mesh, P(pm._AXIS))

        def arg(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=sharded)
        flat = [arg((n_dev * cap,), jnp.dtype(dt)) for dt, _ in sig] \
            + [arg((n_dev * cap,), jnp.bool_) for _ in sig]
        compiled = fn.lower(arg((n_dev * cap,), jnp.int32),
                            arg((n_dev * n_dev,), jnp.int32),
                            *flat).compile()
    finally:
        with pm._CACHE_LOCK:
            pm._EXCHANGE_CACHE.clear()
    assert "all-to-all" in compiled.as_text()
    # every lane leaves sharded over the mesh (chip r's shard is reduce
    # block r): nothing is gathered to all chips
    assert "all-gather" not in compiled.as_text()
    assert all(o == sharded for o in compiled.output_shardings)
    _assert_fits_hbm(compiled)
