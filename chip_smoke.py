"""chip_smoke.py — the served TPC-H path on the attached TPU, checked against
the CPU oracle. The quickest proof that the system still starts on the chip.

    python chip_smoke.py [--seed N] [--out DIR] [--rows N]      # one chip
    python chip_smoke.py --chips 4 [--rows N]                    # mesh phase only

One chip (the default, what the driver runs): generates the repo's TPC-H
tables from --seed with spark_rapids_tpu/datagen.py at --rows lineitem rows
(default 2^20, see DEFAULT_ROWS), writes them as snappy Parquet (2^20-row row
groups), reads
them back with `s.read.parquet` under default confs (+ what
benchmarks/tpch.make_session sets) and runs q6, q1, q3, q18 through the
scheduler — cold, then a repeat that must be a plan-cache hit with zero new
compiles. The same queries then run on the CPU oracle session over the same
files and every row is compared. Any exception ends the run non-zero.

--chips 4 runs ONLY the mesh phase: q3 and q18 on a four-device mesh session
(collective exchanges) against a one-device session in the same process,
compared bit for bit, at 2^16 lineitem rows. That is 2^22 halved six times,
and the reason is compilation, not memory: the mesh session runs the general
path, ~260 programs, whose TPU sort/segment programs compile in time that
falls only below ~2^17 elements per shard (jit compile for v5e, measured in
the sandbox: >= 800 s for q3 alone at 2^22, 528 s for both queries at 2^18,
207 s at 2^16; the chip's host took about twice the sandbox's time on the
one-chip run), and four chips are charged four times over.

One process holds the chip for the whole run; nothing is spawned. Fails at
once unless jax.devices()[0].platform == "tpu". --cpu-rehearsal exists for
debugging the script's own control flow in a sandbox without a chip; it is
never the default, it never prints the result line, and it exits non-zero.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
#: lineitem rows when --rows is not given. The issue asked for 2^24 and the
#: run must end inside 1200 s, compilation included. At 2^24 on the chip the
#: run was correct and took 1416.8 s, 1130 s of it compiling ~300 programs
#: (PERF.md section 6, PR 22) — and that part hardly shrinks with the data: TPU
#: sort and scan programs compile in nearly the same time from 2^17 elements
#: up. Jit compile for v5e summed over the four queries, sandbox, five runs in
#: parallel: 888 s at 2^22, 742 s at 2^21, 600 s at 2^20. So the default is
#: 2^24 halved four times: the first size whose estimate (~720 s on the
#: chip's host) leaves the limit a margin for a slower host. HBM is not what
#: binds: peak_bytes_in_use at 2^24 was 3.08 GB of 16.9. `--rows 16777216`
#: runs the full size wherever the limit allows.
DEFAULT_ROWS = {1: 1 << 20, 4: 1 << 16}
QUERY_ORDER = ("q6", "q1", "q3", "q18")
#: ORDER BY ... LIMIT queries: (sort column, descending) of the leading key
TOPN = {"q3": "revenue", "q18": "o_totalprice"}
#: relative tolerance for DOUBLE values that went through device arithmetic
REL_TOL = 1e-9
TOL_REASON = (
    "DOUBLE sums are compared at rel_tol=%g: the device reduces in a "
    "different order than the host (tree vs sequential over up to 2^24 "
    "addends), and the chip carries f64 as an f32 pair (~2^-48 per "
    "operation), so the last digits differ; keys, counts, integer and "
    "string columns are compared exactly" % REL_TOL)


def say(platform: str, msg: str) -> None:
    print(f"[smoke {platform}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# counters: everything the repeat run must leave flat
# ---------------------------------------------------------------------------

class JaxCompileCounter:
    """Counts XLA backend compiles and persistent-cache traffic from JAX's
    own monitoring events (covers every jit in the process, not only the
    engine's program caches)."""

    def __init__(self) -> None:
        import jax
        self.compiles = 0
        self.cache_requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _evt(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def engine_counters(jaxc: JaxCompileCounter) -> dict:
    from spark_rapids_tpu.execs import compiled, compiled_join, opjit
    from spark_rapids_tpu.io import device_decode
    from spark_rapids_tpu.parallel import mesh
    from spark_rapids_tpu.profiling import SyncLedger
    from spark_rapids_tpu.serving.scheduler import QueryScheduler
    st = opjit.cache_stats()
    return {
        "dispatches": sum(st["calls_by_kind"].values()),
        "syncs": SyncLedger.get().total(),
        "opjit_hits": st["hits"], "opjit_misses": st["misses"],
        "opjit_traces": st["traces"],
        "plan_hits": QueryScheduler.get().plan_cache.stats()["hits"],
        "jax_compiles": jaxc.compiles,
        "pcache_requests": jaxc.cache_requests,
        "pcache_hits": jaxc.cache_hits,
        # the process-wide program caches tests/test_recompile_stability.py
        # lists, plus the Parquet decode programs
        "programs": {
            "opjit": opjit.cache_len(),
            "compiled_stage": len(compiled._STAGE_FN_CACHE),
            "compiled_join_stage": len(compiled_join._JOIN_STAGE_FN_CACHE),
            "mesh_exchange": len(mesh._EXCHANGE_CACHE),
            "parquet_decode": device_decode.decode_stats()["programs"],
        },
    }


def delta(after: dict, before: dict) -> dict:
    return {k: (after[k] - before[k]) for k in after if k != "programs"}


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

#: written PLAIN, not dictionary-first. c_name is unique per row: a default
#: writer starts it dictionary-encoded and switches to PLAIN pages once the
#: dictionary outgrows 1 MiB (from ~58k customers), and the device decoder
#: demotes such a mixed dictionary+PLAIN BYTE_ARRAY chunk to the host
#: (io/device_decode.py "mixed dictionary+PLAIN string chunk"; ROADMAP S5).
#: The smoke proves the DEVICE scan, so it writes the column the way the
#: writer ends up encoding nearly all of it anyway.
PLAIN_COLUMNS = {"customer": ("c_name",)}
#: written with REQUIRED (NOT NULL) columns, as the TPC-H schema declares
#: every column. lineitem carries most of the scanned columns, and on a
#: 2^20-row row group every OPTIONAL column adds a definition-level cumsum
#: that alone takes ~15 s to compile for v5e (PERF.md section 5); orders and
#: customer stay OPTIONAL (pyarrow's default) so the null-aware decode path
#: runs on the chip as well.
REQUIRED_TABLES = ("lineitem",)


def write_tables(out_dir: str, rows: int, seed: int) -> dict:
    """lineitem/orders/customer at lineitem scale `rows` (the ratios of
    benchmarks/tpch.load_tables), one snappy Parquet file each, streamed in
    2^20-row partitions = row groups."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_tpu import datagen as dg
    specs = {
        "lineitem": (dg.tpch_lineitem(rows), rows),
        "orders": (dg.tpch_orders(max(rows // 4, 1)), max(rows // 4, 1)),
        "customer": (dg.tpch_customer(max(rows // 40, 1)),
                     max(rows // 40, 1)),
    }
    os.makedirs(out_dir, exist_ok=True)
    paths = {}
    for name, (spec, n) in specs.items():
        path = os.path.join(out_dir, f"{name}.parquet")
        writer, offset, part = None, 0, 0
        while offset < n:
            k = min(1 << 20, n - offset)
            t = spec.generate_partition(seed, part, k, offset=offset)
            if name in REQUIRED_TABLES:
                t = t.cast(pa.schema([f.with_nullable(False)
                                      for f in t.schema]))
            if writer is None:
                writer = pq.ParquetWriter(
                    path, t.schema, compression="snappy",
                    use_dictionary=[c for c in t.schema.names
                                    if c not in PLAIN_COLUMNS.get(name, ())])
            writer.write_table(t, row_group_size=1 << 20)
            offset += k
            part += 1
        writer.close()
        paths[name] = path
    return paths


# ---------------------------------------------------------------------------
# comparison with the oracle
# ---------------------------------------------------------------------------

def _close(a, b) -> bool:
    if a is None or b is None:
        return a is b
    if isinstance(a, float) and isinstance(b, float):
        if math.isnan(a) or math.isnan(b):
            return math.isnan(a) and math.isnan(b)
        return a == b or abs(a - b) <= REL_TOL * max(abs(a), abs(b))
    return a == b


def _rows_match(a: dict, b: dict) -> bool:
    return all(_close(a[k], b[k]) for k in a)


def compare(name: str, got, want) -> float:
    """Row-for-row comparison of two Arrow tables in the ORACLE's order.
    Non-float columns exactly; float columns within REL_TOL. For the
    ORDER BY ... LIMIT queries rows whose leading sort keys lie within
    REL_TOL of each other may swap (and, in the run that touches the LIMIT,
    be a different member of that tie). Returns the largest relative
    difference seen on a float column. Raises on any mismatch."""
    if got.column_names != want.column_names:
        raise AssertionError(f"{name}: columns {got.column_names} != "
                             f"{want.column_names}")
    if got.schema.types != want.schema.types:
        raise AssertionError(f"{name}: types {got.schema.types} != "
                             f"{want.schema.types}")
    if got.num_rows != want.num_rows:
        raise AssertionError(f"{name}: {got.num_rows} rows, oracle "
                             f"{want.num_rows}")
    g, w = got.to_pylist(), want.to_pylist()
    sort_col = TOPN.get(name)
    n = len(w)
    i = 0
    while i < n:
        if _rows_match(g[i], w[i]):
            i += 1
            continue
        if sort_col is None:
            raise AssertionError(f"{name}: row {i} differs: {g[i]} vs "
                                 f"oracle {w[i]}")
        # the run of oracle rows tied (within tolerance) with row i
        j = i + 1
        while j < n and _close(w[j][sort_col], w[i][sort_col]):
            j += 1
        pool = list(w[i:j])
        for r in g[i:j]:
            hit = next((p for p in pool if _rows_match(r, p)), None)
            if hit is not None:
                pool.remove(hit)
            elif not (j == n and _close(r[sort_col], w[i][sort_col])):
                raise AssertionError(
                    f"{name}: row at {i}..{j} not in the oracle's order: "
                    f"{r}; oracle has {w[i:j]}")
        i = j
    worst = 0.0
    for r, o in zip(g, w):
        for k, v in r.items():
            if isinstance(v, float) and isinstance(o[k], float) \
                    and math.isfinite(v) and v != o[k]:
                worst = max(worst, abs(v - o[k]) / max(abs(v), abs(o[k])))
    return worst


def quiet_explain(df) -> str:
    """explain() prints as well as returns; keep the plan off stdout until
    we decide to show it."""
    with contextlib.redirect_stdout(io.StringIO()):
        return df.explain()


def physical_plan(explained: str) -> str:
    return explained.split("== Physical Plan ==", 1)[-1]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_report(platform: str) -> None:
    import jax
    import jax.extend.backend
    import jaxlib
    from spark_rapids_tpu import native_bridge
    from spark_rapids_tpu.utils import hw
    d = jax.devices()[0]
    stats = d.memory_stats() or {}
    say(platform, f"platform: {d.platform}")
    say(platform, f"device_kind: {d.device_kind}")
    say(platform, f"device count: {len(jax.devices())}")
    say(platform, f"memory_stats bytes_limit: {stats.get('bytes_limit')}")
    say(platform, f"jax {jax.__version__}, jaxlib {jaxlib.__version__}")
    say(platform, "runtime: " + " ".join(
        str(jax.extend.backend.get_backend().platform_version).split()))
    say(platform, f"utils/hw: f64 bit views = {hw.f64_bit_views()} "
                  "(False: DOUBLE keys order/group/join through the exact "
                  "f32-pair encoding; BIGINT is exact either way)")
    built = os.path.exists(native_bridge._SO_PATH)
    ok = native_bridge.available()
    say(platform, f"native library: available={ok} "
                  f"({'found' if built else 'built now from native/src by make'}"
                  f" at {os.path.relpath(native_bridge._SO_PATH, HERE)})")


def run_one_chip(args, platform: str, jaxc: JaxCompileCounter) -> None:
    import jax

    import benchmarks.tpch as tpch
    from spark_rapids_tpu.io import device_decode

    rows = args.rows
    t0 = time.perf_counter()
    paths = write_tables(os.path.join(args.out, f"tpch_{rows}_{args.seed}"),
                         rows, args.seed)
    sizes = {k: os.path.getsize(p) for k, p in paths.items()}
    say(platform, f"data: lineitem {rows} rows, orders {max(rows // 4, 1)}, "
                  f"customer {max(rows // 40, 1)} (seed {args.seed}) written "
                  f"in {time.perf_counter() - t0:.1f}s; parquet bytes "
                  f"{sizes}")

    s = tpch.make_session(tpu=True)
    tables = {k: s.read.parquet(p) for k, p in paths.items()}
    results, dev = {}, jax.devices()[0]
    for pass_no in (1, 2):
        for q in QUERY_ORDER:
            df = tpch.QUERIES[q](s, tables)
            if pass_no == 1:
                plan = physical_plan(quiet_explain(df))
                bad = [ln.strip() for ln in plan.splitlines()
                       if "Cpu" in ln and "Exec" in ln]
                for ln in plan.strip().splitlines():
                    say(platform, f"{q} plan: {ln.rstrip()}")
                if bad:
                    print(df.explain_fallback())
                    raise AssertionError(f"{q}: host operators in the TPU "
                                         f"plan: {bad}")
            before = engine_counters(jaxc)
            t = time.perf_counter()
            out = df.to_arrow()
            secs = time.perf_counter() - t
            after = engine_counters(jaxc)
            d = delta(after, before)
            peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
            kind = "cold" if pass_no == 1 else "warm"
            say(platform,
                f"{q} {kind}: {secs:.3f}s rows_in={rows} "
                f"rows_out={out.num_rows} dispatches={d['dispatches']} "
                f"blocking_syncs={d['syncs']} jax_compiles="
                f"{d['jax_compiles']} opjit_misses={d['opjit_misses']} "
                f"plan_cache_hits={d['plan_hits']} "
                f"persistent_cache_hits={d['pcache_hits']}/"
                f"{d['pcache_requests']} peak_bytes_in_use={peak}")
            if pass_no == 1:
                results[q] = out
                continue
            # the repeat: served from the plan cache, nothing recompiled
            if d["plan_hits"] < 1:
                raise AssertionError(f"{q}: repeat was not a plan-cache hit")
            if d["jax_compiles"] or d["opjit_misses"] or d["opjit_traces"] \
                    or after["programs"] != before["programs"]:
                raise AssertionError(
                    f"{q}: repeat compiled: {d}; program caches "
                    f"{before['programs']} -> {after['programs']}")
            if not out.equals(results[q]):
                raise AssertionError(f"{q}: repeat returned other rows")
    say(platform, f"persistent compile cache over both passes: "
                  f"{jaxc.cache_hits} hits, "
                  f"{jaxc.cache_requests - jaxc.cache_hits} misses "
                  f"of {jaxc.cache_requests} cacheable compiles")

    ds = device_decode.decode_stats()
    say(platform, f"parquet device decode: {ds}")
    fell = {k: v for k, v in ds.items() if k.startswith("fallback") and v}
    if fell or not ds["dispatches"]:
        raise AssertionError(f"scan fell back to the host decoder: {ds}")

    # the oracle: same files, host plan, outside every timing above
    say(platform, TOL_REASON)
    cpu = tpch.make_session(tpu=False)
    cpu_tables = {k: cpu.read.parquet(p) for k, p in paths.items()}
    for q in QUERY_ORDER:
        t = time.perf_counter()
        want = tpch.QUERIES[q](cpu, cpu_tables).to_arrow()
        worst = compare(q, results[q], want)
        say(platform, f"{q} oracle: equal ({want.num_rows} rows, largest "
                      f"relative difference on a DOUBLE {worst:.3g}; oracle "
                      f"on the host CPU took {time.perf_counter() - t:.1f}s)")


def run_four_chips(args, platform: str) -> None:
    import jax

    import benchmarks.tpch as tpch
    from spark_rapids_tpu.obs import mesh_profile
    from spark_rapids_tpu.parallel.sharded import run_mesh_query

    n = 4
    if len(jax.devices()) < n:
        raise SystemExit(f"--chips 4 needs 4 devices, found "
                         f"{len(jax.devices())}")
    rows = args.rows
    extra = {"spark.rapids.sql.batchSizeRows": str(max(rows, 1 << 16))}
    for q in args.queries.split(","):
        name = f"tpch_{q}"

        def build(s, query=tpch.QUERIES[q]):
            return query(s, tpch.load_tables(s, rows, parts=n))

        seq0 = mesh_profile.current_seq()
        t = time.perf_counter()
        rec = run_mesh_query(name, build, n_devices=n, extra_conf=extra)
        secs = time.perf_counter() - t
        spans = sorted({p["input_devices"]
                        for p in mesh_profile.profiles_since(seq0)})
        say(platform,
            f"{name}: rows_in={rows} rows_out={rec['rows_out']} "
            f"bit_identical={rec['bit_identical']} max_abs_err="
            f"{rec['max_abs_err']} exchanges={rec['exchanges']} "
            f"collective_launches={rec['collective_launches']} "
            f"per_map_reasons={rec['per_map_reasons']} "
            f"input shards span {spans} devices "
            f"wall_ms_mesh={rec['wall_ms_mesh']} wall_ms_single="
            f"{rec['wall_ms_single']} (phase took {secs:.1f}s)")
        if not rec["bit_identical"]:
            raise AssertionError(f"{name}: mesh result differs from the "
                                 f"one-device result")
        if not rec["collective_launches_O_exchanges"] \
                or not rec["collective_launches"]:
            raise AssertionError(f"{name}: collective launches "
                                 f"{rec['collective_launches']} for "
                                 f"{rec['exchanges']} exchanges")
        if rec["per_map_reasons"]:
            raise AssertionError(f"{name}: per-map exchanges: "
                                 f"{rec['per_map_reasons']}")
        if spans != [n]:
            raise AssertionError(f"{name}: exchange inputs were not "
                                 f"sharded over {n} distinct devices: "
                                 f"{spans}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"))
    ap.add_argument("--rows", type=int, default=None,
                    help="lineitem rows (default 2^20; 2^16 with --chips 4)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--queries", default="q3,q18",
                    help="--chips 4 only: the mesh phase's queries (each "
                         "compiles its programs for all four chips)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="debug the script on a backend that is not a TPU; "
                         "prints no result line and exits 3")
    args = ap.parse_args()
    default_rows = DEFAULT_ROWS[args.chips]
    if args.rows is None:
        args.rows = default_rows

    import jax
    dev = jax.devices()[0]
    platform = dev.platform
    if platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: no accelerator: jax.devices()[0].platform is "
              f"{platform!r}, not 'tpu'", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(jax.devices())}", file=sys.stderr)
        return 2

    from spark_rapids_tpu.utils import hw
    cache_dir = hw.configure_compile_cache()
    jaxc = JaxCompileCounter()
    t0 = time.perf_counter()
    if args.rows != default_rows:
        say(platform, f"NOT the default size: --rows {args.rows}")
    device_report(platform)
    say(platform, f"compile cache: {cache_dir}")
    if args.chips == 4:
        run_four_chips(args, platform)
    else:
        run_one_chip(args, platform, jaxc)
    say(platform, f"done in {time.perf_counter() - t0:.1f}s")
    if platform != "tpu":
        say(platform, "rehearsal only: this was NOT a chip run, no result")
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
