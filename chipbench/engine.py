"""The only module of the benchmark that touches the program under test: how
a configuration becomes sessions and tables, how a query is sent, and where
the program's own counters are read. What it takes from the program is the
system under test, its spans and its counters; the yardstick (data, traffic,
reference, trace reduction, metric arithmetic) lives beside it."""

from __future__ import annotations

import contextlib
import io
import os
from typing import Dict, List

from . import datagen, roofline


def configure_jax(root: str) -> str:
    """Compile-cache placement and thresholds, before the engine is imported.
    `JAX_COMPILATION_CACHE_DIR`, when the machine sets it, is read by JAX
    itself; otherwise the cache sits at a fixed path inside the checkout (the
    path is part of the key). The thresholds are lowered so that every program
    is kept: JAX by default keeps only what took a second to compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """XLA backend compiles and persistent-cache traffic, from JAX's own
    monitoring events (copied from chip_smoke.py::JaxCompileCounter, with the
    seconds kept). A persistent-cache hit also fires the duration event, with
    the time it took to load."""

    def __init__(self) -> None:
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _evt(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_requests": self.cache_requests, "cache_hits": self.cache_hits}


def counters() -> dict:
    """The program's process-wide counters the per-layer readers take deltas of."""
    from spark_rapids_tpu.execs import opjit
    from spark_rapids_tpu.io import device_decode
    from spark_rapids_tpu.obs.metrics import MetricsRegistry
    from spark_rapids_tpu.profiling import SyncLedger
    from spark_rapids_tpu.serving.scheduler import QueryScheduler
    sched = QueryScheduler.peek()
    plan = MetricsRegistry.get().snapshot()["histograms"].get("plan.build_ms", {})
    return {
        "syncs": SyncLedger.get().total(),
        "opjit_calls": sum(opjit.cache_stats()["calls_by_kind"].values()),
        "plan_ms": sum(h["sum"] for h in plan.values()),
        "plan_count": sum(h["count"] for h in plan.values()),
        "decode": device_decode.decode_stats(),
        "plan_cache": sched.plan_cache.stats() if sched is not None else {},
    }


def plan_text(df) -> str:
    """The physical plan of `df.explain()`, which prints as well as returns."""
    with contextlib.redirect_stdout(io.StringIO()):
        return df.explain().split("== Physical Plan ==", 1)[-1].strip()


def host_operators(plan: str) -> List[str]:
    """Lines of a physical plan that name a host operator."""
    return [ln.strip() for ln in plan.splitlines() if "Cpu" in ln and "Exec" in ln]


class Tenant:
    """One tenant: its own session over its own slice of every table."""

    def __init__(self, index: int, session):
        self.index = index
        self.session = session
        self.tables: Dict[str, object] = {}         # table -> DataFrame
        self.rows: Dict[str, int] = {}              # table -> rows of this tenant's slice
        self.columns: Dict[str, dict] = {}          # table -> numpy columns, for the reference
        self.files: Dict[str, str] = {}             # table -> its Parquet file
        self.metadata: Dict[str, object] = {}       # table -> that file's FileMetaData
        self.frames: Dict[int, object] = {}


class Deployment:
    """A configuration brought up for a cell: tenants, their tables, and the
    `send` the load generator drives."""

    def __init__(self, cell, seed: int, rows: int, data_dir: str, say):
        import pyarrow.parquet as pq
        from spark_rapids_tpu.session import TpuSession
        import spark_rapids_tpu.functions as F
        self.F = F
        self.cell = cell
        self.tenants: List[Tenant] = []
        self._stopped = False
        conf = cell.config
        traffic = cell.traffic
        n_t = int(traffic.get("tenants", 1))
        self.schema = datagen.tables(conf, rows)
        self.templates = [cell.query(t["query"]) for t in traffic["templates"]]
        self.params = [t.get("params", {}) for t in traffic["templates"]]
        self.classes = [t.get("class") for t in traffic["templates"]]
        needed = cell.columns_read()
        for t in range(n_t):
            ten = Tenant(t, TpuSession(dict(conf["session_conf"])))
            self.tenants.append(ten)
            for name, table in self.schema.items():
                lo, hi = datagen.tenant_slices(table.rows, n_t)[t]
                ten.rows[name] = hi - lo
                if table.storage == "parquet":
                    path = os.path.join(data_dir, f"{cell.name}.{seed}.{t}.{name}.parquet")
                    ten.files[name] = path
                    ten.columns[name] = table.write_parquet(path, seed, lo, hi,
                                                            keep=needed.get(name, ()))
                    ten.metadata[name] = pq.read_metadata(path)
                    ten.tables[name] = ten.session.read.parquet(path)
                elif table.storage == "resident":
                    cols = table.generate(seed, table.cached, lo, hi)
                    ten.tables[name] = ten.session.createDataFrame(
                        table.to_arrow(cols)).device_cache()
                    ten.columns[name] = table.kept(cols, needed.get(name, ()))
                else:
                    raise ValueError(f"table {name}: unknown storage {table.storage!r}")
                say(f"tenant {t}: {name} rows [{lo}, {hi}) {table.storage}")
        for ten in self.tenants:
            for q, mod in enumerate(self.templates):
                ten.frames[q] = mod.build(F, ten.tables, **self.params[q])

    def query_rows(self, tenant: int, template: int) -> int:
        """Input rows of one query: the tenant's rows of every table it reads."""
        return sum(self.tenants[tenant].rows[name]
                   for name in self.templates[template].COLUMNS)

    def query_bytes(self, tenant: int, template: int) -> int:
        """Bytes the question needs (roofline.py), over the tables it reads."""
        ten, total = self.tenants[tenant], 0
        for name, columns in self.templates[template].COLUMNS.items():
            if name in ten.metadata:
                total += roofline.parquet_bytes(ten.metadata[name], columns)
            else:
                total += roofline.resident_bytes(self.schema[name], columns, ten.rows[name])
        return total

    def plans(self) -> List[str]:
        """The physical plan of every template entry, explained once each."""
        return [plan_text(self.tenants[0].frames[q]) for q in range(len(self.templates))]

    def send(self, rec, detail: bool = False) -> None:
        """One query through DataFrame.collect(): session -> scheduler ->
        execs -> host rows. A shed or an exception is a failed query."""
        from spark_rapids_tpu.serving.query_context import QueryShed
        ten = self.tenants[rec.tenant]
        try:
            out = ten.frames[rec.template].collect(priority=self.classes[rec.template])
        except Exception as e:  # noqa: BLE001 — the run goes on and counts it
            rec.result, rec.failed = repr(e), True
            return
        if isinstance(out, QueryShed):
            rec.result, rec.failed = f"shed: {out.reason}", True
            return
        rec.result = out
        rec.extra["admit_wait_ms"] = ten.session.last_admit_wait_ms()
        if detail:
            rec.extra["scan"] = scan_times(ten.session)

    def stop(self) -> None:
        if self._stopped:
            return
        self._stopped = True
        for ten in self.tenants:
            ten.frames.clear()
            ten.tables.clear()
            ten.session.stop()
            for path in ten.files.values():
                with contextlib.suppress(OSError):
                    os.remove(path)


def scan_times(session) -> dict:
    """ns of the last query's scan node: decodeTime, hostDecodeTime, uploadTime."""
    out = {}
    for node, vals in session.last_query_metrics("MODERATE").items():
        for k in ("decodeTime", "hostDecodeTime", "uploadTime"):
            if k in vals:
                out[k] = out.get(k, 0) + vals[k]
    return out


def trace_annotations(on: bool) -> None:
    """Make the program emit its per-operator `TraceAnnotation`s
    (`profiling.trace_scope`) and `srt.<phase>` spans while the benchmark's
    own profiler session runs."""
    from spark_rapids_tpu import profiling
    profiling.set_trace_annotations(on)
