"""Typed configuration registry for the TPU accelerator.

TPU-native re-design of the reference's `RapidsConf` system
(/root/reference/sql-plugin/src/main/scala/com/nvidia/spark/rapids/RapidsConf.scala:126-235
entry-builder DSL; 236 `spark.rapids.*` entries). We keep the same design: typed entries
declared once with docs/defaults, a session-level immutable snapshot re-read per query,
`internal`/`startup_only`/`commonly_used` attributes, and markdown doc generation
(reference `RapidsConf.help`, RapidsConf.scala:2318).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional


def _parse_bool(v: Any) -> bool:
    if isinstance(v, bool):
        return v
    s = str(v).strip().lower()
    if s in ("true", "1", "yes", "on"):
        return True
    if s in ("false", "0", "no", "off"):
        return False
    raise ValueError(f"invalid boolean config value: {v!r}")


_SIZE_SUFFIXES = {
    "b": 1,
    "k": 1 << 10, "kb": 1 << 10, "kib": 1 << 10,
    "m": 1 << 20, "mb": 1 << 20, "mib": 1 << 20,
    "g": 1 << 30, "gb": 1 << 30, "gib": 1 << 30,
    "t": 1 << 40, "tb": 1 << 40, "tib": 1 << 40,
}


def parse_bytes(v: Any) -> int:
    """Parse '512m', '1g', '1024' into a byte count (reference: byteStringAsBytes)."""
    if isinstance(v, (int, float)):
        return int(v)
    s = str(v).strip().lower()
    num, suffix = s, ""
    for i, ch in enumerate(s):
        if not (ch.isdigit() or ch == "." or (ch == "-" and i == 0)):
            num, suffix = s[:i], s[i:].strip()
            break
    if suffix and suffix not in _SIZE_SUFFIXES:
        raise ValueError(f"invalid byte-size suffix in config value: {v!r}")
    return int(float(num) * _SIZE_SUFFIXES.get(suffix, 1))


@dataclass
class ConfEntry:
    key: str
    doc: str
    default: Any
    converter: Callable[[Any], Any]
    internal: bool = False
    startup_only: bool = False
    commonly_used: bool = False
    checker: Optional[Callable[[Any], None]] = None

    def get(self, settings: Dict[str, str]) -> Any:
        raw = settings.get(self.key)
        if raw is None:
            return self.default
        val = self.converter(raw)
        if self.checker is not None:
            self.checker(val)
        return val


class _ConfBuilder:
    """Mirrors the reference's `conf("key").doc(...).booleanConf.createWithDefault(...)`."""

    def __init__(self, registry: "ConfRegistry", key: str):
        self._registry = registry
        self._key = key
        self._doc = ""
        self._internal = False
        self._startup_only = False
        self._commonly_used = False
        self._checker: Optional[Callable[[Any], None]] = None

    def doc(self, text: str) -> "_ConfBuilder":
        self._doc = text
        return self

    def internal(self) -> "_ConfBuilder":
        self._internal = True
        return self

    def startup_only(self) -> "_ConfBuilder":
        self._startup_only = True
        return self

    def commonly_used(self) -> "_ConfBuilder":
        self._commonly_used = True
        return self

    def check(self, fn: Callable[[Any], None]) -> "_ConfBuilder":
        self._checker = fn
        return self

    def _create(self, default: Any, converter: Callable[[Any], Any]) -> ConfEntry:
        entry = ConfEntry(
            key=self._key, doc=self._doc, default=default, converter=converter,
            internal=self._internal, startup_only=self._startup_only,
            commonly_used=self._commonly_used, checker=self._checker)
        self._registry.register(entry)
        return entry

    def boolean(self, default: bool) -> ConfEntry:
        return self._create(default, _parse_bool)

    def integer(self, default: int) -> ConfEntry:
        return self._create(default, lambda v: int(str(v), 0))

    def double(self, default: float) -> ConfEntry:
        return self._create(default, float)

    def string(self, default: Optional[str]) -> ConfEntry:
        return self._create(default, str)

    def bytes(self, default: int) -> ConfEntry:
        return self._create(default, parse_bytes)

    def string_list(self, default: List[str]) -> ConfEntry:
        return self._create(
            default,
            lambda v: [s.strip() for s in str(v).split(",") if s.strip()] if not isinstance(v, list) else v)


class ConfRegistry:
    def __init__(self) -> None:
        self.entries: Dict[str, ConfEntry] = {}

    def conf(self, key: str) -> _ConfBuilder:
        return _ConfBuilder(self, key)

    def register(self, entry: ConfEntry) -> None:
        if entry.key in self.entries:
            raise ValueError(f"duplicate config key {entry.key}")
        self.entries[entry.key] = entry

    def help_markdown(self, include_internal: bool = False) -> str:
        """Generate docs/configs.md content (reference RapidsConf.scala:2318)."""
        lines = [
            "# TPU Accelerator Configuration",
            "",
            "| Name | Description | Default | Applicable at |",
            "|---|---|---|---|",
        ]
        for key in sorted(self.entries):
            e = self.entries[key]
            if e.internal and not include_internal:
                continue
            when = "Startup" if e.startup_only else "Runtime"
            doc = str(e.doc).replace("|", "\\|")  # keep table cells aligned
            lines.append(f"| {e.key} | {doc} | {e.default} | {when} |")
        lines += ["", _PATHS_DOC]
        return "\n".join(lines) + "\n"


#: prose section appended to the generated config docs (kept here so
#: docs/configs.md regenerates from one source of truth)
_PATHS_DOC = """## General vs compiled execution paths

Every query runs on one of two device execution strategies:

* **Compiled whole-stage paths** (`spark.rapids.tpu.agg.compiledStage.enabled`,
  `spark.rapids.tpu.join.compiledStage.enabled`) fuse an entire eligible
  pipeline (scan → filter → project → group-by, or a star-join probe chain)
  into ONE jitted XLA program per batch shape. They are the fastest option but
  only engage inside a narrow eligibility window (device-pure fixed-width
  expressions, small key domains / unique build keys, no ANSI); anything else
  falls back transparently.
* **The general path** executes operator by operator (project, filter,
  shuffled join, sort-based aggregate, exchange). With
  `spark.rapids.tpu.opjit.enabled` (default on) each operator's per-batch
  device transform is itself jit-compiled and cached process-wide, keyed by a
  structural fingerprint of its expression forest plus the bucketed batch
  shape (`spark.rapids.tpu.opjit.cacheSize` bounds the LRU). Unlike the
  compiled stages this imposes no eligibility window: host-assisted
  expressions split the trace at the host boundary (the device-pure subtrees
  run compiled, the host patch stays eager), and anything that cannot trace
  at all simply stays on the eager path with identical results.

The compiled stages engage first when eligible; the opjit cache accelerates
everything they leave behind, so dispatch-bound workloads no longer pay one
host→device round trip per expression node.

With `spark.rapids.tpu.opjit.fuseStages` (default on) the general path goes
one step further: maximal chains of adjacent project/filter operators are
collapsed at plan time into ONE fused segment whose whole expression
pipeline (every projection forest plus the AND of every filter predicate)
traces into a single cached executable — a batch then flows through the
entire chain in one dispatch instead of one per operator. Host-assisted or
otherwise untraceable operators split the segment at the operator boundary
(the device-pure prefix/suffix stay fused, the offending operator runs on
its per-operator program), and a segment whose first trace fails degrades
to the per-operator programs with bit-identical results.

## Dispatch accounting

Every program launch pays a fixed dispatch+sync cost (PERF.md records what
it is on the attached chip), so for small batches the number of *launches
per batch* — not kernel time — decides general-path wall time. The opjit
cache tracks it:

* `opJitCacheHits` / `opJitCacheMisses` (per-operator metrics and the
  process-wide `opjit.cache_stats()`): one hit or miss is recorded per
  *program dispatch* through the cache. Eager-pinned fingerprints record
  nothing — their work runs as raw per-op launches.
* `cache_stats()["calls_by_kind"]` breaks dispatches down by program kind:
  `segment` (a fused stage segment: the whole project/filter chain in one
  launch), `project` / `filter` (single-operator programs), `joinenc`
  (both join sides' key encode in one launch), `exchsplit` (the exchange
  map side's hash-partition encode+split pair in one launch), `pids`
  (hash partitioner alone, e.g. under the mesh collective), `aggsort` /
  `aggreduce` (the sort-based aggregate's two phases), plus the
  whole-stage and partition-grouped kinds: `joinbuild` (a fused
  segment's join build prepared once: sort + bucket directory),
  `joinprobe` / `joinemit` (its streamed-side join probe and
  pair-emit+downstream halves a batch), `aggstage` (the grouped aggregate's whole update as one
  launch), `segmentg` (one fused segment over a GROUP of partitions'
  batches) and `exchsplitg` (the hash encode+split of a whole partition
  group with one bounds readback).
* With fusion on, a fully-fused N-operator chain contributes ONE `segment`
  dispatch per batch; with fusion off the same chain contributes N
  `project`/`filter` dispatches.
* `opJitTraceTime` isolates first-sight compile cost from steady-state
  dispatch cost; steady state should be all hits.

## Per-plan and per-partition dispatch

The compiled stages reach O(exchanges) launches by construction; the
general path reaches it by composing four mechanisms, each with its own
toggle, all default-on:

* **Segments across joins** (`spark.rapids.tpu.opjit.fuseJoins`): a fused
  stage segment absorbs a streamed-side inner equi-join at its bottom. The
  build side materializes ONCE per partition — segment build children get
  the `RequireSingleBatch` coalesce goal (or arrive host-concatenated from
  an exchange read) and is prepared ONCE a build (`joinbuild`: key encode +
  hash + sort + the bucket directory over the sorted hashes) — and each
  probe batch runs exactly TWO launches (`joinprobe`: upstream chain + key
  encode + directory look-up of each lane's candidate range; `joinemit`:
  pair expansion + verification + both-side gather + the flattened
  downstream chain + one compaction), split only at the inherent
  candidate-count sync. String keys, non-inner join types, oversized
  builds (which need sub-partitioning) and host-assisted expressions
  delegate that partition to the original join operator unchanged.
* **Segments across partial aggregation**
  (`spark.rapids.tpu.opjit.fuseAggs`): a grouped hash-aggregate at the top
  of a segment — or standing alone — runs its whole update (key eval,
  encode, stable sort, segment boundaries, every measure update and
  finalization, group-key gather) as ONE `aggstage` launch with a
  capacity-bucketed group table, so the group count stays a DEVICE scalar
  instead of syncing between the old sort and reduce phases. Unsupported
  aggregates degrade to the two-phase path with identical results.
* **Batched multi-partition dispatch**
  (`spark.rapids.tpu.dispatch.partitionBatch`, default 8): the per-
  *partition* launch axis folds the same way the per-operator axis did.
  The exchange map side schedules partition GROUPS: member partitions'
  same-layout batches run one grouped segment program (`segmentg`) through
  the child's `execute_partitions` entry point, their hash encode+split
  plans run one grouped launch (`exchsplitg`), and ALL member split bounds
  ride one device→host readback. One TPU-semaphore permit gates the whole
  group (member task contexts are adopted onto it), and block identity is
  unchanged — each member still commits under its own map id, so reduce
  reads and lineage recovery never observe the grouping. Set to 1 for the
  per-partition behavior.
* **Pipelined group scheduling**: the shuffle pipeline pool
  (`spark.rapids.tpu.shuffle.pipeline.*`) submits partition groups, not
  single partitions, as its schedulable units, so retry, chaos injection
  and cancellation wrap a whole group exactly like they wrapped one map.

tests/test_whole_stage_dispatch.py locks the result in: a q3-shaped
general-path plan must show only whole-stage dispatch kinds, a total
launch count bounded by a small constant per exchange, and bit-identical
results against every degraded configuration.

## Batch coalescing

Small batches multiply every per-batch cost above. With
`spark.rapids.tpu.coalesce.enabled` (default on) the plan pass inserts
`TpuCoalesceBatchesExec` ahead of batch-hungry operators — joins,
aggregates, sorts, and fused segments — concatenating device batches up to
`spark.rapids.sql.batchSizeBytes` / `batchSizeRows` (spill-aware: pending
inputs are held as `SpillableColumnarBatch` so HBM pressure can evict them
mid-concat). Join build sides use a `RequireSingleBatch`-style goal. The
same targets drive HOST-side coalescing of fetched shuffle blocks: the
exchange reduce path and `HostToDeviceExec` concatenate Arrow tables to
target size *before* the H→D upload, so one upload and one downstream
dispatch replace one per block (reference `GpuShuffleCoalesceExec`).

## Dispatch & sync accounting

Besides dispatch counts, every BLOCKING device→host transfer (a
`np.asarray`/`.item()`/`jax.device_get` of a device value — each one stalls
the host until the device has drained) is attributed to the operator that caused it
via the process-wide **sync ledger** (`profiling.SyncLedger`). All blocking
syncs in the engine route through one audited helper
(`columnar/vector.py: audited_sync*`), enforced statically by tracelint
rule TL011; the ledger records `{operator: {kind: count}}` where kind names
the reason (`rows` — a compaction/filter row count, `bounds` — exchange
split bounds, `pairs` — join pair count, `chars` — string gather sizing,
`batch` — batch materialization at the D→H boundary, ...).

* `SyncLedger.get().snapshot()` returns per-operator counts;
  `total()` the process-wide sum.
* With deferred compaction + coalescing on, a healthy general-path run
  shows blocking syncs per partition bounded by O(exchanges) — one `bounds`
  sync per map batch and one `batch` materialization per boundary — not
  O(operators×batches). A regression shows up as a per-operator `rows`
  count that scales with batch count.
* `TpuMetric` row counts accumulate device-side when a batch's row count is
  still deferred (`add_lazy`) and materialize at metric read time (query
  end), so metric bookkeeping itself never forces a sync.

## Query timeline tracing

`spark.rapids.tpu.trace.enabled` arms the per-query span/event tracer
(`spark_rapids_tpu/obs/`): one ring-buffered, thread-aware record per query
tying every operator's time to its dispatches, blocking syncs, HBM
allocations/spills/semaphore waits, shuffle map/reduce/fetch-retries,
transient-error retries and chaos injections. Tracing is CONCURRENT: each
query gets its own tracer routed by thread-local scopes (up to
`spark.rapids.tpu.trace.maxConcurrentQueries` at once; a query beyond the
cap runs untraced and increments the `trace.dropped_queries` registry
counter — never silently). Three views export from the same record: a
Chrome trace (perfetto / `chrome://tracing`),
`session.explain("metrics")` (the executed plan annotated per node with its
actual metrics, dispatch and sync counts), and the machine-readable
diagnostics bundle `session.last_query_profile()` whose per-operator counts
reconcile against its OWN query's `calls_by_kind` / sync-ledger deltas even
when other queries run concurrently. See docs/observability.md for the span
model, event catalogue and bundle schema.

## Always-on metrics + crash flight recorder

Independent of tracing, the `spark.rapids.tpu.obs.*` surface keeps the
serving-era aggregate layer always on: a process-wide metrics registry
(`spark.rapids.tpu.obs.metrics.enabled`, default on — query latency and
rows/s log2-bucket histograms with p50/p95/p99 readouts, HBM high-water and
pressure counters, spill bytes, cache hit rates, device-retry/chaos/fetch-
retry counts; read via `session.metrics_snapshot()` or `python -m
tools.obs_report`) and a crash flight recorder
(`spark.rapids.tpu.obs.flightRecorderEvents`) whose ring of recent notable
events lands — together with a full registry snapshot and HBM/semaphore/
spill state — in a postmortem bundle under
`spark.rapids.tpu.obs.postmortemDir` whenever a fatal device error, an
exhausted transient-retry loop, or a genuine HBM budget OOM kills a query.
docs/observability.md documents the registry naming scheme and the
postmortem schema.

## Mesh efficiency profiler + collective watchdog

On a mesh session, every collective exchange additionally records a
per-exchange efficiency profile (`spark_rapids_tpu/obs/mesh_profile.py`):
the phase walls (host staging / program launch / collective wait /
compact), the per-chip send/recv rows and bytes from the already-synced
sizing counters (ZERO extra device syncs), and a skew table — max/median
per-chip rows, the imbalance factor, and the straggler chip id when one
chip's share exceeds `spark.rapids.tpu.obs.meshStragglerFactor` × the
median. Profiles land in `last_query_profile()['mesh']`,
`session.metrics_snapshot()` (with the `mesh.skew_imbalance` /
`mesh.straggler_wait_ms` registry histograms) and `python -m tools.obs_report
--mesh`. A
collective blocked past `spark.rapids.tpu.obs.collectiveWatchdogMs` trips
the watchdog WHILE still waiting (flight-recorder event +
`mesh.watchdog_fired` counter — a hung chip is otherwise indistinguishable
from a slow one); past `spark.rapids.tpu.obs.collectiveWatchdogFatalMs` it
dumps a postmortem bundle. Mesh-session exchanges routed per-map record
WHY (`mesh.per_map_exchange{reason}`, `explain("metrics")`
`per_map=` annotations). See docs/observability.md "Mesh profiling".

## Device parquet decode

With `spark.rapids.tpu.parquet.deviceDecode.enabled` (default on) parquet
scans stop decoding on the host: the host does only footer/row-group
metadata, the Thrift page-header walk, page decompression, and the
RLE/bit-packed run-header walk, then stages raw page bytes into HBM and
runs ONE cached decode program per row group (bit-unpacking, RLE/dictionary
run expansion, dictionary gather, definition-level → validity expansion
with null compaction into the padded batch layout, PLAIN fixed-width
reinterpret — the reference's semaphore-then-cuDF-device-decode shape,
GpuParquetScan.scala:1983). Launches are recorded under the
`parquet_decode` kind in the dispatch accounting, so a scan costs
O(row-groups) dispatches, not O(pages) or O(columns). BYTE_ARRAY
string/binary columns decode into the engine's offsets+bytes device
layout (PLAIN length-prefix walks host-side, dictionary pages ship raw
bytes + the index run table; the device program cumsums row lengths into
int32 offsets and byte-gathers the chars), and RLE_DICTIONARY string
columns surface the parquet dictionary as a device `dict_encoding` so
string group keys feed the key-encode programs as int32 codes. Columns
the device cannot decode (nested, INT96, FIXED_LEN_BYTE_ARRAY, exotic
encodings) automatically
demote to per-column host pyarrow decode zipped into the same batch;
corrupt/truncated pages heal per row group via host re-read
(`spark.rapids.tpu.parquet.deviceDecode.verify` adds a paranoid
bit-identity cross-check); encrypted files raise the reference's clean
message naming the file and the CPU fallback route. Coverage matrix and
fallback rules: docs/io.md.

## Mesh data plane (sharded multi-chip execution)

With `spark.rapids.tpu.mesh.enabled` and `spark.rapids.shuffle.mode=ICI` a
session becomes a MESH SESSION: the planner re-plans hash exchanges to
exactly mesh-size reduce partitions (`spark.rapids.tpu.mesh.alignPartitions`)
and marks every fixed-width exchange collective, so each one materializes
as ONE `lax.all_to_all` (hash) or shard-0 funnel (single) over the
interconnect (`spark.rapids.tpu.mesh.collectiveExchange.enabled`) instead
of per-map catalog puts — the reference's UCX transport re-expressed as an
XLA collective. Exchange-time per-shard row/byte counters double as the
AQE partition statistics (no block is ever fetched to answer planning),
the session's root pull batches every chip's partition into one grouped
launch (`spark.rapids.tpu.dispatch.partitionBatch`), collective launches
land in the dispatch accounting under the `mesh_collective` kind inside
`mesh.exchange` timeline spans, and the lost-shard / slow-link chaos sites
(`mesh.shard`, `mesh.link`) heal through the same FetchFailed lineage
recovery as any lost map. String/binary payloads ride the collective as
int32 dictionary codes plus ONE broadcast dictionary per exchange
(`spark.rapids.tpu.exchange.dictionaryEncode.enabled` — the analogue of
the reference's compressed shuffle batches): the map side encodes across
all shards, the reduce side decodes on read with a device gather and
keeps the codes as each column's `dict_encoding` for downstream group
keys; an exchange past the cardinality/2^31-byte guards
(`spark.rapids.tpu.exchange.dictionaryEncode.maxCardinality`) falls back
per-map with reason `dictionary_overflow`. Only nested or host-only
payloads transparently keep the per-map
device-resident path. Design and fault model: docs/distributed.md.

## Robustness

Batch-level work survives memory pressure via spill + retry/split
(`spark.rapids.memory.*`), transient XLA errors heal through bounded
backoff (`spark.rapids.tpu.deviceRetry.*`), shuffle blocks carry xxhash64
checksums whose mismatch triggers lineage re-materialization
(`spark.rapids.tpu.shuffle.checksum.enabled`,
`spark.rapids.tpu.shuffle.fetchRetry.maxAttempts`), and the whole stack is
validated under the seeded chaos fault injector
(`spark.rapids.tpu.test.chaos.*`). The unified story — sites, fault kinds,
and recovery paths — is in docs/robustness.md.

## Query lifecycle & multi-tenant scheduling

Every query submits through the process-wide scheduler service
(serving/scheduler.py — many session frontends, one device owner):

* **Admission control.** A submission enters a bounded FIFO queue
  (`spark.rapids.tpu.sched.maxQueuedQueries`) drained round-robin across
  sessions; it is admitted when a concurrency slot is free
  (`spark.rapids.tpu.sched.maxConcurrentQueries`) and HBM usage is under
  `spark.rapids.tpu.sched.hbmAdmissionWatermark` × budget (waived when
  nothing is running). Past the queue bound, submission fails fast with
  the typed `QueryQueueFull` backpressure error — load sheds at the
  front door instead of stacking working sets until HBM pressure OOMs
  every query on the device.
* **Deadlines & cancellation.** Each query carries a cancel token and an
  optional deadline (`spark.rapids.tpu.query.timeoutMs`,
  `df.collect(timeout=seconds)`, `session.cancel()`). Cancellation is
  cooperative: checkpoints at every task boundary (partition-task start,
  batch pull, exchange map task, reduce fetch, mesh collective launch,
  UDF worker round-trip) observe the token and unwind through the
  TL020-audited release paths, so a cancelled or timed-out query returns
  ALL permits, HBM, spill files and its tracer to baseline.
* **Fault isolation.** A fatal device error (or an exhausted per-query
  retry budget, `spark.rapids.tpu.query.retryBudget`) fails that query
  alone: with concurrent queries in flight the process is NOT exited —
  the failure is quarantined (postmortem dump + `query.quarantined`
  counter) and healthy neighbors run to completion.

State machine, cancellation semantics, and the fault-isolation matrix:
docs/robustness.md "Query lifecycle".
"""

REGISTRY = ConfRegistry()
_conf = REGISTRY.conf

# ---------------------------------------------------------------------------
# Core enablement (reference RapidsConf.scala: spark.rapids.sql.enabled et al.)
# ---------------------------------------------------------------------------
SQL_ENABLED = _conf("spark.rapids.sql.enabled").doc(
    "Enable (true) or disable (false) TPU acceleration of SQL plans."
).commonly_used().boolean(True)

SQL_MODE = _conf("spark.rapids.sql.mode").doc(
    "executeOnTPU runs converted plans on the TPU; explainOnly only reports what would "
    "run on the TPU (reference GpuOverrides.scala:4579-4584) and executes on CPU."
).check(lambda v: None if v in ("executeontpu", "explainonly", "executeOnTPU", "explainOnly")
        else (_ for _ in ()).throw(ValueError(f"invalid sql.mode {v}"))).string("executeOnTPU")

EXPLAIN = _conf("spark.rapids.sql.explain").doc(
    "NONE, NOT_ON_TPU (log reasons operators fall back to CPU) or ALL."
).commonly_used().string("NOT_ON_TPU")

TEST_ASSERT_ON_TPU = _conf("spark.rapids.sql.test.enabled").doc(
    "Testing only: fail if any operator in the plan did not convert to the TPU "
    "(reference GpuTransitionOverrides.assertIsOnTheGpu, GpuTransitionOverrides.scala:616)."
).internal().boolean(False)

ALLOW_CPU_FALLBACK_EXPRS = _conf("spark.rapids.sql.cpuExpressions.enabled").doc(
    "Allow individual expressions without a TPU kernel to run on the host inside a "
    "TPU-resident plan (per-expression fallback)."
).boolean(True)

INCOMPATIBLE_OPS = _conf("spark.rapids.sql.incompatibleOps.enabled").doc(
    "Enable operators whose results differ from Spark in corner cases "
    "(reference RapidsConf incompatibleOps)."
).boolean(True)

ANSI_ENABLED = _conf("spark.sql.ansi.enabled").doc(
    "ANSI mode: arithmetic overflow and invalid casts raise instead of returning null."
).boolean(False)

CASE_SENSITIVE = _conf("spark.sql.caseSensitive").doc(
    "Case-sensitive attribute resolution."
).boolean(False)

SESSION_TZ = _conf("spark.sql.session.timeZone").doc(
    "Session timezone for timestamp semantics."
).string("UTC")

# ---------------------------------------------------------------------------
# Batching / memory (reference RapidsConf.scala:544-567, 464, 508)
# ---------------------------------------------------------------------------
CONCURRENT_TPU_TASKS = _conf("spark.rapids.tpu.concurrentTpuTasks").doc(
    "Number of concurrent tasks that may hold TPU HBM at once; gated by the TPU "
    "semaphore (reference GpuSemaphore, RapidsConf.scala:544-551 default 2)."
).commonly_used().integer(2)

BATCH_SIZE_BYTES = _conf("spark.rapids.sql.batchSizeBytes").doc(
    "Target size in bytes of output batches (reference GPU_BATCH_SIZE_BYTES default 1GiB "
    "max 2GiB, RapidsConf.scala:559-567). Smaller default on TPU: static-shape compilation "
    "favors stable bucketed capacities."
).commonly_used().bytes(512 * 1024 * 1024)

BATCH_SIZE_ROWS = _conf("spark.rapids.sql.batchSizeRows").doc(
    "Target maximum rows per columnar batch."
).integer(1 << 20)

HBM_ALLOC_FRACTION = _conf("spark.rapids.memory.tpu.allocFraction").doc(
    "Fraction of TPU HBM budgeted for columnar data (reference RMM_ALLOC_FRACTION, "
    "RapidsConf.scala:464). XLA owns the physical allocator; this bounds our accounting."
).startup_only().double(0.75)

HOST_SPILL_STORAGE_SIZE = _conf("spark.rapids.memory.host.spillStorageSize").doc(
    "Amount of host memory used to cache spilled device batches before disk "
    "(reference HOST_SPILL_STORAGE_SIZE, RapidsConf.scala:508)."
).startup_only().bytes(1 << 30)

LEAK_TRACKING_DEBUG = _conf("spark.rapids.memory.debug.leakTracking").doc(
    "Capture creation stacks for every registered device resource and "
    "raise on double-close (reference MemoryCleaner leak tracking, "
    "Plugin.scala:581-596). Always-on cheap tracking reports leak counts "
    "at shutdown even when this is off.").boolean(False)

OOM_RETRY_MAX = _conf("spark.rapids.memory.tpu.oomMaxRetries").doc(
    "Retries of an allocation after synchronizing + spilling before declaring OOM."
).integer(3)

TASK_RETRY_LIMIT = _conf("spark.rapids.memory.tpu.taskRetryLimit").doc(
    "How many times the task-level retry framework re-runs a batch on "
    "TpuRetryOOM (splitting on TpuSplitAndRetryOOM) before giving up "
    "(reference RmmRapidsRetryIterator bound)."
).integer(8)

COALESCE_ENABLED = _conf("spark.rapids.tpu.coalesce.enabled").doc(
    "Batch coalescing for the general path (reference GpuCoalesceBatches + "
    "GpuShuffleCoalesceExec): concatenate undersized batches up to "
    "spark.rapids.sql.batchSizeBytes / batchSizeRows before batch-hungry "
    "operators (joins, aggregates, sorts, fused segments), and concatenate "
    "fetched shuffle blocks HOST-side to the same target before the "
    "host→device upload. On a high-dispatch-latency link every batch pays "
    "a fixed launch+sync cost, so fewer, fuller batches are the difference "
    "between O(batches) and O(exchanges) round trips per operator."
).commonly_used().boolean(True)

DEFERRED_COMPACTION = _conf(
    "spark.rapids.tpu.batch.deferredCompaction.enabled").doc(
    "Defer the filter/join compaction row-count sync: `compact` keeps the "
    "bucketed padded capacity and carries the kept-row count as a DEVICE "
    "scalar, so a filter→project→serialize chain syncs once at the "
    "exchange/collect boundary (the count rides the same device_get as the "
    "data) instead of one blocking scalar read per batch per operator. "
    "Consumers that need the host row count materialize it transparently; "
    "results are bit-identical either way."
).boolean(True)

# ---------------------------------------------------------------------------
# Shuffle (reference RapidsConf.scala:1663-1677, 1855-1866)
# ---------------------------------------------------------------------------
SHUFFLE_MODE = _conf("spark.rapids.shuffle.mode").doc(
    "MULTITHREADED (host Arrow-serialized shuffle files, parallel writer/reader threads) "
    "or ICI (device-resident all-to-all over the TPU interconnect within a mesh) "
    "(reference SHUFFLE_MANAGER_MODE: MULTITHREADED/UCX/CACHE_ONLY)."
).string("MULTITHREADED")

SHUFFLE_WRITER_THREADS = _conf("spark.rapids.shuffle.multiThreaded.writer.threads").doc(
    "Threads for the multithreaded shuffle writer (reference RapidsConf.scala:1855)."
).integer(8)

MESH_ENABLED = _conf("spark.rapids.tpu.mesh.enabled").doc(
    "Execute hash exchanges as one collective all_to_all over a "
    "jax.sharding.Mesh when the device topology allows it (the UCX-mode data "
    "plane of the reference, shuffle-plugin/UCXShuffleTransport.scala, "
    "re-expressed as an XLA collective over ICI). Requires "
    "spark.rapids.shuffle.mode=ICI and shuffle partitions == mesh size."
).boolean(False)

MESH_SIZE = _conf("spark.rapids.tpu.mesh.size").doc(
    "Mesh size (number of devices) for the collective exchange; 0 = all "
    "visible devices."
).integer(0)

MESH_COLLECTIVE_ENABLED = _conf(
    "spark.rapids.tpu.mesh.collectiveExchange.enabled").doc(
    "Materialize eligible exchanges of a mesh session as ONE fabric "
    "collective (lax.all_to_all for hash partitioning, the shard-0 funnel "
    "for single partitioning) instead of per-map catalog puts. Off keeps "
    "the per-map device-resident ICI path (every block still device-side, "
    "but one materialization per map partition). Requires "
    "spark.rapids.tpu.mesh.enabled and spark.rapids.shuffle.mode=ICI."
).boolean(True)

EXCHANGE_DICT_ENCODE_ENABLED = _conf(
    "spark.rapids.tpu.exchange.dictionaryEncode.enabled").doc(
    "Let string/binary exchange payloads ride the mesh collective as "
    "fixed-width int32 dictionary codes plus ONE per-exchange broadcast "
    "dictionary (the TPU analogue of the reference's compressed shuffle "
    "batches, RapidsShuffleCompression): the map side dictionary-encodes "
    "each string column across all shards, the lax.all_to_all moves only "
    "the codes, and the reduce side decodes on read with a device gather "
    "— the rebuilt columns keep the codes as their dict_encoding so "
    "string-keyed downstream aggregation consumes them directly. Requires "
    "a mesh session; exchanges whose dictionary trips the cardinality or "
    "2^31-byte guards fall back to the per-map path with reason "
    "dictionary_overflow. Off = string-payload exchanges always ride the "
    "per-map device-resident path."
).boolean(True)

EXCHANGE_DICT_MAX_CARDINALITY = _conf(
    "spark.rapids.tpu.exchange.dictionaryEncode.maxCardinality").doc(
    "Cardinality guard for spark.rapids.tpu.exchange.dictionaryEncode."
    "enabled: an exchange whose string columns hold more distinct values "
    "than this (or more than 2^31 distinct bytes — the int32 offsets "
    "range) is not worth a broadcast dictionary and falls back to the "
    "per-map path (reason dictionary_overflow in "
    "mesh.per_map_exchange{reason} and explain(\"metrics\"))."
).integer(1 << 20)

MESH_ALIGN_PARTITIONS = _conf(
    "spark.rapids.tpu.mesh.alignPartitions").doc(
    "When a mesh session is active, the planner re-plans hash exchanges to "
    "exactly mesh-size reduce partitions so every exchange is collective-"
    "eligible (the on-device murmur3 % n routing must match the shard "
    "count). Partition count is an execution detail — results are "
    "identical at any count — so mesh sessions stop depending on the user "
    "hand-tuning spark.sql.shuffle.partitions to the topology."
).boolean(True)

EXCHANGE_OVERLAP_ENABLED = _conf(
    "spark.rapids.tpu.exchange.overlap.enabled").doc(
    "Segment eligible collective exchanges so segment k+1's all_to_all is "
    "in flight on the fabric while the fused post-collective compact "
    "consumes segment k (exchange/compute overlap, "
    "parallel/mesh.py). Every segment scatters to the same final row "
    "positions the unsegmented program uses, so results are bit-identical "
    "at any segment count; the exchange still records exactly ONE "
    "mesh_collective launch (segments count under mesh_overlap_segment). "
    "Correctness-first default: off — each exchange runs as one fused "
    "program."
).boolean(False)

EXCHANGE_OVERLAP_SEGMENTS = _conf(
    "spark.rapids.tpu.exchange.overlap.segments").doc(
    "Segment count K for spark.rapids.tpu.exchange.overlap.enabled: the "
    "collective payload splits into K slot-axis segments, double-buffered "
    "so at most one segment's transfer overlaps one segment's compact. "
    "Values <= 1 disable segmentation."
).integer(2)

EXCHANGE_OVERLAP_MIN_ROWS = _conf(
    "spark.rapids.tpu.exchange.overlap.minSlotRows").doc(
    "Minimum per-bucket slot capacity (rows) for the segmented overlap "
    "path to engage: below it, per-segment launch overhead dominates "
    "whatever transfer time the overlap could hide and the exchange runs "
    "unsegmented (the sizing sync already knows the capacity, so the "
    "decision costs nothing)."
).integer(1024)

COMPILED_AGG_ENABLED = _conf("spark.rapids.tpu.agg.compiledStage.enabled").doc(
    "Fuse eligible scan->filter->project->groupBy pipelines into ONE jitted "
    "XLA program with a direct-indexed group table (small key domains only). "
    "Eliminates per-expression dispatch latency — the TPU analogue of the "
    "reference's fused aggregation iterator chain "
    "(GpuAggregateExec.scala:549). Ineligible or overflowing stages fall "
    "back to the general sort-based aggregate transparently."
).boolean(True)

COMPILED_AGG_MAX_GROUPS = _conf("spark.rapids.tpu.agg.compiled.maxGroups").doc(
    "Largest combined group-key domain the compiled aggregation stage may "
    "direct-index; beyond this the general sort-based path runs."
).integer(4096)

OPJIT_ENABLED = _conf("spark.rapids.tpu.opjit.enabled").doc(
    "Jit-compile the GENERAL execution path's per-operator device "
    "transforms (projection/filter expression forests, join key encoding, "
    "hash partitioning, the sort-based aggregate's sort and reduce phases) "
    "into XLA executables cached process-wide by a structural fingerprint "
    "plus bucketed batch shape. Collapses the eager path's per-op dispatch "
    "storm (one launch and one compile per op) into one launch per operator "
    "per batch shape. Unlike the compiled whole-stage paths there is no "
    "eligibility window: subtrees that cannot trace (host-assisted "
    "expressions, ANSI host-sync checks, string kernels sizing on data) "
    "split the trace at the host boundary and stay eager."
).commonly_used().boolean(True)

OPJIT_CACHE_SIZE = _conf("spark.rapids.tpu.opjit.cacheSize").doc(
    "LRU bound on the general-path executable cache "
    "(spark.rapids.tpu.opjit.enabled); evicting an entry drops its "
    "compiled program."
).integer(256)

OPJIT_FUSE_STAGES = _conf("spark.rapids.tpu.opjit.fuseStages").doc(
    "Whole-stage segment fusion for the general path: collapse maximal "
    "chains of adjacent project/filter operators into one fused segment "
    "whose entire expression pipeline traces into a SINGLE cached "
    "executable per batch shape — one dispatch per batch for the whole "
    "chain instead of one per operator. Host-assisted expressions split "
    "the segment at the operator boundary (device-pure prefix/suffix stay "
    "fused); untraceable segments degrade to the per-operator programs "
    "with identical results. Requires spark.rapids.tpu.opjit.enabled."
).commonly_used().boolean(True)

OPJIT_FUSE_JOINS = _conf("spark.rapids.tpu.opjit.fuseJoins").doc(
    "Let fused stage segments absorb an inner equi-join: the build side "
    "materializes ONCE per partition (one cached build program: key eval + "
    "encode + hash + sort), and each probe batch runs the upstream "
    "projection/filter chain, probe-key encode and hash-range probe as one "
    "cached program, then pair expansion, verification, both-side gathers "
    "and the downstream chain as a second — two launches plus the inherent "
    "pair-count sync per probe batch instead of one launch per operator. "
    "String keys, residual-match-sensitive join types and host-assisted "
    "expressions degrade to the per-operator join with identical results. "
    "Requires spark.rapids.tpu.opjit.fuseStages."
).commonly_used().boolean(True)

OPJIT_FUSE_AGGS = _conf("spark.rapids.tpu.opjit.fuseAggs").doc(
    "Run the sort-based grouped aggregate's whole update — grouping-key "
    "eval, encode, stable sort, segment boundaries, every measure update "
    "and finalization, and the group-key gather — as ONE cached executable "
    "with a fixed-size (input-capacity-bucketed) group table, so the group "
    "count stays a DEVICE scalar instead of syncing between the sort and "
    "reduce phases. Fused stage segments also absorb such an aggregate as "
    "their final stage. Unsupported aggregates (collect/percentile "
    "family, decimal accumulators, variable-width inputs) degrade to the "
    "two-phase aggsort/aggreduce path with identical results. Requires "
    "spark.rapids.tpu.opjit.enabled."
).commonly_used().boolean(True)

DISPATCH_PARTITION_BATCH = _conf(
    "spark.rapids.tpu.dispatch.partitionBatch").doc(
    "Batched multi-partition dispatch: the exchange map side and the fused "
    "segment executor process up to this many partitions per program "
    "launch — member batches enter ONE cached grouped program (each padded "
    "to its capacity bucket; a composite member×partition sort key keeps "
    "per-partition identity) so the hash-partition encode+split pair and "
    "the segment transform launch once per partition GROUP, and the split "
    "bounds of the whole group ride one device→host readback. The shuffle "
    "pipeline pool schedules partition groups instead of single "
    "partitions. 1 disables grouping (per-partition dispatch, the PR 2 "
    "behavior); block identity, ordering and lineage recovery are "
    "unchanged either way."
).commonly_used().integer(8)

SHUFFLE_PIPELINE_ENABLED = _conf(
    "spark.rapids.tpu.shuffle.pipeline.enabled").doc(
    "Pipelined exchange materialization: run a shuffle's map tasks "
    "concurrently through a bounded thread pool (device work gated by the "
    "TPU semaphore) so one map's deferred host commit I/O overlaps the "
    "next map's device work, and prefetch the reduce side's "
    "deserialize+upload while downstream computes (reference "
    "RapidsShuffleThreadedWriterBase / ...ReaderBase)."
).commonly_used().boolean(True)

SHUFFLE_PIPELINE_MAP_THREADS = _conf(
    "spark.rapids.tpu.shuffle.pipeline.mapThreads").doc(
    "Maximum concurrent map tasks while materializing one exchange "
    "(spark.rapids.tpu.shuffle.pipeline.enabled). Device-side concurrency "
    "is still bounded by spark.rapids.tpu.concurrentTpuTasks; extra "
    "threads overlap host serialization and file I/O with device work."
).integer(4)

SHUFFLE_PIPELINE_PREFETCH = _conf(
    "spark.rapids.tpu.shuffle.pipeline.prefetchDepth").doc(
    "How many reduce-side shuffle blocks the exchange read path "
    "deserializes and uploads ahead of the consumer "
    "(spark.rapids.tpu.shuffle.pipeline.enabled). 0 disables read-side "
    "prefetch."
).integer(2)

PARQUET_CHUNK_BYTES = _conf(
    "spark.rapids.sql.reader.chunked.maxDecodeBytes").doc(
    "PERFILE parquet reads stream row groups in chunks whose compressed "
    "footprint stays under this many bytes, bounding host decode memory "
    "(reference chunked reader, GpuParquetScan + "
    "spark.rapids.sql.reader.chunked). 0 disables chunking."
).integer(256 << 20)

PARQUET_REBASE_MODE_READ = _conf(
    "spark.rapids.sql.parquet.datetimeRebaseModeInRead").doc(
    "Rebase handling for parquet files WITHOUT the Spark legacy-calendar "
    "footer marker: CORRECTED reads values as proleptic Gregorian (modern "
    "writers), LEGACY forces the hybrid Julian->proleptic rebase. Marked "
    "files always rebase (reference datetimeRebaseUtils.scala)."
).string("CORRECTED")

PARQUET_DEVICE_DECODE_ENABLED = _conf(
    "spark.rapids.tpu.parquet.deviceDecode.enabled").doc(
    "Decode parquet pages ON DEVICE for the flat fixed-width column "
    "classes (PLAIN / RLE_DICTIONARY / RLE int32/int64/float/double/"
    "boolean/date/timestamp-micros, with definition-level nulls): the host "
    "does only footer/row-group metadata, the page-header walk and page "
    "decompression, then stages raw page bytes into HBM and runs ONE "
    "cached decode program per row group (reference GpuParquetScan "
    "semaphore-then-cuDF-decode). Columns the device cannot decode "
    "(strings, nested, INT96, exotic encodings) automatically demote to "
    "host pyarrow decode per column and zip into the same batch; decode "
    "errors heal per row group via host re-read. Note: the device path "
    "streams files serially per partition, one row group at a time — "
    "spark.rapids.sql.format.parquet.reader.type and the chunked-reader "
    "byte limit govern the HOST path only (per-row-group staging is the "
    "device path's memory bound, the reference's chunked-decode shape). "
    "Off = the original whole-table host pyarrow decode + upload path."
).boolean(True)

PARQUET_DEVICE_DECODE_VERIFY = _conf(
    "spark.rapids.tpu.parquet.deviceDecode.verify").doc(
    "Paranoia cross-check for spark.rapids.tpu.parquet.deviceDecode."
    "enabled: after each device-decoded row group, re-decode the same "
    "columns with host pyarrow and require bit-identical results; a "
    "mismatch (e.g. corrupted staged bytes that slipped past the "
    "structural page checks) falls the row group back to the host decode. "
    "Debug/soak tool — roughly doubles scan cost."
).boolean(False)

COMPILED_JOIN_ENABLED = _conf(
    "spark.rapids.tpu.join.compiledStage.enabled").doc(
    "Fuse eligible star-shaped join pipelines "
    "(fact scan->filter->project -> chain of many-to-one equi-joins -> "
    "groupBy) into ONE jitted XLA program per fact batch: dimension tables "
    "build as sorted device arrays, the fact side probes them with "
    "searchsorted + gather inside the trace, and the aggregation groups by "
    "the dimension row index (dense codes, segment reductions). Kills the "
    "per-partition program-launch storm of the shuffled-join path on "
    "high-dispatch-latency links. Ineligible stages (non-equi conditions, "
    "duplicate build keys, outer joins) fall back transparently."
).boolean(True)

COMPILED_JOIN_MAX_DIM_ROWS = _conf(
    "spark.rapids.tpu.join.compiled.maxDimRows").doc(
    "Largest build-side (dimension) row count the compiled join stage will "
    "materialize as device probe arrays; beyond this the general shuffled "
    "join path runs."
).integer(1 << 22)

SHUFFLE_READER_THREADS = _conf("spark.rapids.shuffle.multiThreaded.reader.threads").doc(
    "Threads for the multithreaded shuffle reader (reference RapidsConf.scala:1866)."
).integer(8)

SHUFFLE_COMPRESSION_CODEC = _conf("spark.rapids.shuffle.compression.codec").doc(
    "Codec for shuffle batch buffers: none, zstd, lz4 (reference nvcomp LZ4/ZSTD codecs)."
).string("zstd")

SHUFFLE_PARTITIONS = _conf("spark.sql.shuffle.partitions").doc(
    "Default number of shuffle partitions."
).integer(16)

# ---------------------------------------------------------------------------
# I/O (reference RapidsConf.scala:1067-1088 and chunked-reader confs)
# ---------------------------------------------------------------------------
PARQUET_READER_TYPE = _conf("spark.rapids.sql.format.parquet.reader.type").doc(
    "AUTO, PERFILE, COALESCING or MULTITHREADED multi-file reader strategy "
    "(reference GpuMultiFileReader, RapidsConf.scala:1067-1088)."
).string("AUTO")

MULTITHREAD_READ_NUM_THREADS = _conf("spark.rapids.sql.multiThreadedRead.numThreads").doc(
    "Thread-pool size for multithreaded file reading."
).integer(8)

PARQUET_ENABLED = _conf("spark.rapids.sql.format.parquet.enabled").doc(
    "Enable TPU parquet scans/writes.").boolean(True)
CSV_ENABLED = _conf("spark.rapids.sql.format.csv.enabled").doc(
    "Enable TPU CSV scans.").boolean(True)
JSON_ENABLED = _conf("spark.rapids.sql.format.json.enabled").doc(
    "Enable TPU JSON scans.").boolean(True)
ORC_ENABLED = _conf("spark.rapids.sql.format.orc.enabled").doc(
    "Enable TPU ORC scans/writes.").boolean(True)
AVRO_ENABLED = _conf("spark.rapids.sql.format.avro.enabled").doc(
    "Enable TPU Avro scans.").boolean(True)
HIVE_TEXT_ENABLED = _conf("spark.rapids.sql.format.hive.text.enabled").doc(
    "Enable TPU Hive delimited-text scans/writes.").boolean(True)
AQE_COALESCE_ENABLED = _conf(
    "spark.sql.adaptive.coalescePartitions.enabled").doc(
    "Coalesce small shuffle partitions after materialization using map "
    "output sizes (reference GpuCustomShuffleReaderExec / AQE coalesced "
    "partition specs).").boolean(False)
AQE_ADVISORY_PARTITION_BYTES = _conf(
    "spark.sql.adaptive.advisoryPartitionSizeInBytes").doc(
    "Target combined size of a coalesced shuffle-read partition."
).bytes(64 * (1 << 20))
AQE_SKEW_JOIN_ENABLED = _conf(
    "spark.sql.adaptive.skewJoin.enabled").doc(
    "Split skewed shuffle partitions into map-range slices on one join side "
    "and replicate the other side's matching partition (reference "
    "OptimizeSkewedJoin + PartialReducerPartitionSpec).").boolean(False)
AQE_SKEW_THRESHOLD = _conf(
    "spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes").doc(
    "A shuffle partition is skew-eligible only above this size."
).bytes(256 * (1 << 20))
AQE_SKEW_FACTOR = _conf(
    "spark.sql.adaptive.skewJoin.skewedPartitionFactor").doc(
    "A partition is skewed when larger than this factor times the median "
    "partition size (and above the threshold)."
).integer(5)
CACHE_BATCH_ROWS = _conf("spark.rapids.sql.cache.batchSizeRows").doc(
    "Rows per parquet-compressed cached batch in df.cache() (reference "
    "ParquetCachedBatchSerializer per-batch encoding)."
).integer(1 << 18)
CACHE_HOST_LIMIT = _conf("spark.rapids.sql.cache.hostMemoryLimit").doc(
    "Host-memory budget for cached-relation blobs; overflow spills whole "
    "compressed batches to local disk (0 disables the cap)."
).bytes(0)
FILECACHE_ENABLED = _conf("spark.rapids.filecache.enabled").doc(
    "Cache remote scan inputs (s3/gs/hdfs/...) on local disk (reference: "
    "the spark-rapids-private FileCache; SURVEY.md §1 notes the TPU build "
    "implements it directly).").boolean(False)
FILECACHE_PATH = _conf("spark.rapids.filecache.path").doc(
    "Local directory for the file cache (defaults to a temp dir)."
).string(None)
FILECACHE_MAX_BYTES = _conf("spark.rapids.filecache.maxBytes").doc(
    "File-cache size budget; least-recently-used files are evicted."
).bytes(100 * (1 << 30))
CORE_DUMP_DIR = _conf("spark.rapids.tpu.coreDump.dir").doc(
    "When set, fatal device errors write a diagnostic bundle (device "
    "topology, HBM accounting, task metrics, traceback) here before the "
    "executor exits (reference GpuCoreDumpHandler + "
    "spark.rapids.gpu.coreDump.*).").string(None)
FATAL_ERROR_EXIT = _conf("spark.rapids.tpu.fatalError.exit").doc(
    "Exit the process on a fatal device error so a cluster manager can "
    "reschedule (reference RapidsExecutorPlugin.logGpuDebugInfoAndExit). "
    "Off by default: this engine runs in the driver process, so exiting "
    "would kill the user's application — enable it only when running as a "
    "managed executor.").boolean(False)
DEBUG_DUMP_PATH = _conf("spark.rapids.sql.debug.dumpPath").doc(
    "When set, operators dump their last good batch to parquet under this "
    "directory on failure (reference DumpUtils.scala).").string(None)
OPTIMIZER_ENABLED = _conf("spark.rapids.sql.optimizer.enabled").doc(
    "Cost-based optimizer: revert plan sections whose estimated TPU cost "
    "(incl. transitions) exceeds the CPU cost (reference "
    "CostBasedOptimizer.scala).").boolean(False)
OPTIMIZER_CPU_ROW_COST = _conf(
    "spark.rapids.sql.optimizer.cpu.exec.defaultRowCost").doc(
    "Default per-row CPU operator cost for the CBO.").double(0.0002)
OPTIMIZER_TPU_ROW_COST = _conf(
    "spark.rapids.sql.optimizer.tpu.exec.defaultRowCost").doc(
    "Default per-row TPU operator cost for the CBO.").double(0.0001)
OPTIMIZER_TRANSITION_ROW_COST = _conf(
    "spark.rapids.sql.optimizer.transitionRowCost").doc(
    "Per-row cost charged for each row↔columnar transition at a section "
    "boundary. Kept low by default: every pipeline here starts host-side, "
    "so the upload edge is priced as one amortized copy, not a per-operator "
    "penalty.").double(0.00002)
LOGICAL_COLUMN_PRUNING = _conf(
    "spark.rapids.tpu.optimizer.columnPruning.enabled").doc(
    "Logical column pruning: the planner inserts projections restricted "
    "to the columns an operator's ancestors actually reference, so "
    "exchanges carry fixed-width/dict-coded payloads without hand-written "
    "selects (docs/serving.md \"Plan cache & logical optimizer\")."
).boolean(True)
LOGICAL_PUSHDOWN = _conf(
    "spark.rapids.tpu.optimizer.pushdown.enabled").doc(
    "Logical filter/projection pushdown through explicit exchanges "
    "(hash-partitioned Repartition) and pure-rename projections, so rows "
    "are dropped before they are shuffled."
).boolean(True)
LOGICAL_JOIN_STRATEGY = _conf(
    "spark.rapids.tpu.optimizer.joinStrategy.enabled").doc(
    "Cost-based build-side choice: swap a join's inputs when the "
    "row-count estimate (plan/cbo.py RowCountPlanVisitor) says the left "
    "side is much smaller than the right, so the smaller side becomes "
    "the build/broadcast side (reference CostBasedOptimizer.scala). The "
    "original output column order is restored by a projection."
).boolean(True)
LOGICAL_JOIN_SWAP_RATIO = _conf(
    "spark.rapids.tpu.optimizer.joinStrategy.swapRatio").doc(
    "Hysteresis for the cost-based build-side swap: the estimated right "
    "(build) side must exceed the left side by this factor before the "
    "sides are swapped, so near-equal estimates (which are noisy) never "
    "flip the plan shape."
).double(1.5)
PLAN_CACHE_ENABLED = _conf("spark.rapids.tpu.plan.cache.enabled").doc(
    "Process-wide plan cache owned by the serving scheduler: a "
    "normalized-logical-plan + schema + conf fingerprint maps to the "
    "fully converted physical plan with literal parameter slots; hits "
    "bypass physical planning and override conversion and only re-bind "
    "literal slots (docs/serving.md \"Plan cache & logical optimizer\")."
).boolean(True)
PLAN_CACHE_MAX_ENTRIES = _conf("spark.rapids.tpu.plan.cache.maxEntries").doc(
    "Plan-cache capacity; least-recently-used entries are evicted past "
    "this bound."
).integer(256)
UDF_COMPILER_ENABLED = _conf("spark.rapids.sql.udfCompiler.enabled").doc(
    "Translate row python UDF bytecode into columnar device expressions "
    "where possible (reference udf-compiler/ LogicalPlanRules); "
    "untranslatable UDFs keep the row fallback.").boolean(False)
PYTHON_UDF_WORKERS = _conf("spark.rapids.sql.python.numWorkers").doc(
    "Number of separate python worker processes for pandas/arrow UDF "
    "execution (Arrow-IPC exchange; reference GpuArrowEvalPythonExec + "
    "python/rapids/worker.py). 0 runs UDFs in-process. UDFs that cannot "
    "pickle always run in-process.").integer(0)
CONCURRENT_PYTHON_WORKERS = _conf(
    "spark.rapids.python.concurrentPythonWorkers").doc(
    "Admission semaphore: how many python UDF workers may run "
    "concurrently (reference PythonWorkerSemaphore.scala:98). 0 means "
    "as many as numWorkers.").integer(0)

# ---------------------------------------------------------------------------
# Operator toggles (reference: spark.rapids.sql.exec.* generated per rule)
# ---------------------------------------------------------------------------
HASH_AGG_ENABLED = _conf("spark.rapids.sql.exec.HashAggregateExec").doc(
    "Enable TPU hash aggregation.").boolean(True)
IN_MEMORY_SCAN_ENABLED = _conf("spark.rapids.sql.exec.InMemoryTableScanExec").doc(
    "Enable the TPU device-cached relation scan.").boolean(True)
SORT_ENABLED = _conf("spark.rapids.sql.exec.SortExec").doc(
    "Enable TPU sort.").boolean(True)
JOIN_ENABLED = _conf("spark.rapids.sql.exec.ShuffledHashJoinExec").doc(
    "Enable TPU shuffled hash join.").boolean(True)
BROADCAST_JOIN_ENABLED = _conf("spark.rapids.sql.exec.BroadcastHashJoinExec").doc(
    "Enable TPU broadcast hash join.").boolean(True)
WINDOW_ENABLED = _conf("spark.rapids.sql.exec.WindowExec").doc(
    "Enable TPU window functions.").boolean(True)
PROJECT_ENABLED = _conf("spark.rapids.sql.exec.ProjectExec").doc(
    "Enable TPU projection.").boolean(True)
RANGE_ENABLED = _conf("spark.rapids.sql.exec.RangeExec").doc(
    "Enable TPU range.").boolean(True)
UNION_ENABLED = _conf("spark.rapids.sql.exec.UnionExec").doc(
    "Enable TPU union.").boolean(True)
LOCAL_LIMIT_ENABLED = _conf("spark.rapids.sql.exec.LocalLimitExec").doc(
    "Enable TPU local limit.").boolean(True)
GLOBAL_LIMIT_ENABLED = _conf("spark.rapids.sql.exec.GlobalLimitExec").doc(
    "Enable TPU global limit.").boolean(True)
TOPN_ENABLED = _conf("spark.rapids.sql.exec.TakeOrderedAndProjectExec").doc(
    "Enable TPU top-N (sort+limit fusion).").boolean(True)
SAMPLE_ENABLED = _conf("spark.rapids.sql.exec.SampleExec").doc(
    "Enable TPU sampling.").boolean(True)
BNLJ_ENABLED = _conf("spark.rapids.sql.exec.BroadcastNestedLoopJoinExec").doc(
    "Enable TPU broadcast nested-loop join.").boolean(True)
EXCHANGE_ENABLED = _conf("spark.rapids.sql.exec.ShuffleExchangeExec").doc(
    "Enable TPU shuffle exchange.").boolean(True)
FILE_SCAN_ENABLED = _conf("spark.rapids.sql.exec.FileSourceScanExec").doc(
    "Enable TPU file-source scans.").boolean(True)
GENERATE_ENABLED = _conf("spark.rapids.sql.exec.GenerateExec").doc(
    "Enable TPU generate (explode/posexplode/stack/json_tuple).").boolean(True)
EXPAND_ENABLED = _conf("spark.rapids.sql.exec.ExpandExec").doc(
    "Enable TPU expand (grouping sets).").boolean(True)
FILTER_ENABLED = _conf("spark.rapids.sql.exec.FilterExec").doc(
    "Enable TPU filter.").boolean(True)

CARTESIAN_ENABLED = _conf("spark.rapids.sql.exec.CartesianProductExec").doc(
    "Enable the TPU cartesian product.").boolean(True)
WRITE_EXEC_ENABLED = _conf("spark.rapids.sql.exec.DataWritingCommandExec").doc(
    "Enable the TPU data-writing command (writes run through the override "
    "engine with tagging and metrics).").boolean(True)
SUBQUERY_BROADCAST_ENABLED = _conf(
    "spark.rapids.sql.exec.SubqueryBroadcastExec").doc(
    "Enable the TPU subquery broadcast (dynamic partition pruning key "
    "collection).").boolean(True)
SYMMETRIC_JOIN_ENABLED = _conf(
    "spark.rapids.sql.join.useShuffledSymmetricHashJoin").doc(
    "Use the symmetric shuffled hash join, which picks the build side "
    "per partition by materialized size instead of always building on the "
    "right (reference GpuShuffledSymmetricHashJoinExec)."
).boolean(True)
PARQUET_WRITE_ENABLED = _conf(
    "spark.rapids.sql.format.parquet.write.enabled").doc(
    "Enable accelerated parquet writes.").boolean(True)
ORC_WRITE_ENABLED = _conf("spark.rapids.sql.format.orc.write.enabled").doc(
    "Enable accelerated ORC writes.").boolean(True)

STABLE_SORT = _conf("spark.rapids.sql.stableSort.enabled").doc(
    "Force stable sorts (reference RapidsConf stableSort)."
).boolean(False)

AUTO_BROADCAST_JOIN_THRESHOLD = _conf("spark.sql.autoBroadcastJoinThreshold").doc(
    "Broadcast the build side of an equi-join when its estimated size is below "
    "this many bytes (-1 disables)."
).bytes(10 * 1024 * 1024)

JOIN_SIZED_BUILD_HEURISTIC = _conf("spark.rapids.sql.join.buildSideRows.max").doc(
    "Max build-side rows before a shuffled hash join sub-partitions its inputs "
    "(reference GpuSubPartitionHashJoin)."
).integer(1 << 22)

# ---------------------------------------------------------------------------
# Metrics / profiling / debug (reference GpuExec.scala:41-61, profiler.scala)
# ---------------------------------------------------------------------------
METRICS_LEVEL = _conf("spark.rapids.sql.metrics.level").doc(
    "ESSENTIAL, MODERATE, or DEBUG metric verbosity (reference GpuMetric levels)."
).string("MODERATE")

PROFILE_PATH_PREFIX = _conf("spark.rapids.profile.pathPrefix").doc(
    "If set, write jax profiler traces for task execution under this path "
    "(reference spark.rapids.profile.* CUPTI profiler)."
).string(None)

TRACE_ENABLED = _conf("spark.rapids.tpu.trace.enabled").doc(
    "Query timeline tracing (docs/observability.md): record a span tree "
    "per query — query → partition task → operator → shuffle map task — "
    "with instant events for opjit/compiled dispatches (kind + cache "
    "hit/miss), audited device→host syncs, HBM alloc/spill/semaphore "
    "waits, shuffle map/reduce/fetch-retry, transient device-error "
    "retries, and chaos injections. Exported as Chrome trace-event JSON "
    "(perfetto-loadable), session.explain(\"metrics\"), and the "
    "session.last_query_profile() diagnostics bundle. Near-zero overhead "
    "when off (a module-flag check per site)."
).commonly_used().boolean(False)

TRACE_BUFFER_EVENTS = _conf("spark.rapids.tpu.trace.bufferEvents").doc(
    "Ring-buffer capacity of the query tracer in records (one span costs "
    "two records, one instant event one). On overflow the oldest records "
    "are overwritten and the diagnostics bundle reports the drop count "
    "(its reconciliation downgrades to 'overflow' instead of disagreeing "
    "silently)."
).integer(262144)

TRACE_CATEGORIES = _conf("spark.rapids.tpu.trace.categories").doc(
    "Comma-separated event/span categories to record (op, task, dispatch, "
    "sync, memory, shuffle, shuffle.map, retry, chaos); empty records "
    "everything. Note that filtering out 'dispatch' or 'sync' makes the "
    "bundle's reconciliation against calls_by_kind / the SyncLedger "
    "report a mismatch by construction."
).string_list([])

TRACE_TAG = _conf("spark.rapids.tpu.trace.tag").doc(
    "Stem prefix for traced-query names and their artifact files "
    "(<tag>-<n>.trace.json instead of query-<n>.trace.json), so that "
    "artifacts of different runs never collide."
).string(None)

TRACE_DIR = _conf("spark.rapids.tpu.trace.dir").doc(
    "When set (and tracing is enabled), every traced query writes its "
    "Chrome trace (<query>.trace.json) and diagnostics bundle "
    "(<query>.profile.json) under this directory; the paths are recorded "
    "in last_query_profile()['artifacts']."
).string(None)

TRACE_MAX_CONCURRENT = _conf(
    "spark.rapids.tpu.trace.maxConcurrentQueries").doc(
    "Capacity cap on simultaneously traced queries (each armed tracer "
    "owns one ring buffer of bufferEvents records). Tracing is per-query: "
    "N concurrent sessions each trace their own query with independent "
    "span trees and reconciliation. A query arriving beyond the cap runs "
    "untraced and increments the always-on trace.dropped_queries registry "
    "counter — never a silent drop (docs/observability.md)."
).integer(16)

OBS_METRICS_ENABLED = _conf("spark.rapids.tpu.obs.metrics.enabled").doc(
    "The always-on process-wide metrics registry (docs/observability.md "
    "\"Metrics registry\"): counters, gauges and log2-bucket histograms — "
    "query latency p50/p95/p99 and rows/s, HBM high-water and pressure "
    "events, spill bytes, cache hit rates, device-retry and chaos counts. "
    "Read via session.metrics_snapshot() or `python -m tools.obs_report`. "
    "The hot path is one dict lookup plus an in-place add; disable only "
    "to rule the registry out while debugging."
).boolean(True)

OBS_FLIGHT_EVENTS = _conf("spark.rapids.tpu.obs.flightRecorderEvents").doc(
    "Ring capacity of the always-on crash flight recorder (notable events "
    "only: query begin/end, chaos injections, device retries, HBM "
    "pressure/OOM, disk spills, fetch retries). The last events land in "
    "the postmortem bundle when a query dies hard."
).integer(512)

OBS_COLLECTIVE_WATCHDOG_MS = _conf(
    "spark.rapids.tpu.obs.collectiveWatchdogMs").doc(
    "Collective watchdog (docs/observability.md \"Mesh profiling\"): a "
    "mesh collective exchange whose launch+wait window exceeds this many "
    "milliseconds emits a flight-recorder event (mesh.watchdog) and the "
    "mesh.watchdog_fired registry counter WHILE the wait is still "
    "blocked — on real hardware a hung chip manifests exactly as an "
    "unbounded collective wait, and without the watchdog it is "
    "indistinguishable from a slow one. 0 disables."
).integer(30000)

OBS_COLLECTIVE_WATCHDOG_FATAL_MS = _conf(
    "spark.rapids.tpu.obs.collectiveWatchdogFatalMs").doc(
    "When > 0, a collective still blocked after this many milliseconds "
    "dumps a postmortem bundle under spark.rapids.tpu.obs.postmortemDir "
    "(the incident artifact exists even if the process never returns "
    "from the wait) and counts mesh.watchdog_fatal. Keep well above "
    "collectiveWatchdogMs; 0 (default) disables the fatal tier."
).integer(0)

OBS_MESH_STRAGGLER_FACTOR = _conf(
    "spark.rapids.tpu.obs.meshStragglerFactor").doc(
    "Straggler threshold for the mesh efficiency profiler: an exchange "
    "whose heaviest chip receives more than this multiple of the median "
    "per-chip rows reports that chip as the straggler (skew table in "
    "last_query_profile()['mesh']) and feeds "
    "the mesh.straggler_wait_ms histogram."
).double(2.0)

OBS_POSTMORTEM_DIR = _conf("spark.rapids.tpu.obs.postmortemDir").doc(
    "When set, a fatal device error, an exhausted transient-retry loop, "
    "or a genuine HBM budget OOM writes a postmortem bundle "
    "(postmortem-<reason>-<ms>.json) under this directory: the flight "
    "recorder's last-K events, the full metrics-registry snapshot, "
    "HBM/semaphore/spill state, the active query names and the failure "
    "itself (docs/observability.md \"Postmortem bundle\")."
).string(None)

TEST_RETRY_OOM_INJECTION = _conf("spark.rapids.memory.tpu.state.debug.retryOomInjection").doc(
    "Testing only: inject TpuRetryOOM/TpuSplitAndRetryOOM at allocation points "
    "(reference RmmSpark.forceRetryOOM test hooks)."
).internal().string(None)

# ---------------------------------------------------------------------------
# Robustness: transient device-error retry, shuffle integrity, and the seeded
# chaos fault-injection harness (docs/robustness.md; reference
# RmmSpark.forceRetryOOM / the spark-rapids fault-injection tool, SURVEY §7)
# ---------------------------------------------------------------------------
DEVICE_RETRY_MAX_ATTEMPTS = _conf("spark.rapids.tpu.deviceRetry.maxAttempts").doc(
    "How many times a device dispatch (opjit program call, compiled-stage "
    "launch, ICI block fetch, pipelined shuffle map task) is re-attempted "
    "after a TRANSIENT device/runtime error (XLA status UNAVAILABLE, "
    "RESOURCE_EXHAUSTED, ABORTED, CANCELLED) before the error propagates. "
    "Fatal statuses (INTERNAL, DATA_LOSS, ...) are never retried — they go "
    "straight to the fatal-failure hook (spark.rapids.tpu.coreDump.dir)."
).integer(4)

DEVICE_RETRY_BACKOFF_BASE_MS = _conf(
    "spark.rapids.tpu.deviceRetry.backoffBaseMs").doc(
    "Base delay of the transient-device-error retry backoff; attempt n "
    "sleeps min(base * 2^(n-1), backoffMaxMs) scaled by a random jitter in "
    "[0.5, 1.0]. Blocked time accumulates in the deviceRetryBlockTimeNs "
    "task metric."
).double(10.0)

DEVICE_RETRY_BACKOFF_MAX_MS = _conf(
    "spark.rapids.tpu.deviceRetry.backoffMaxMs").doc(
    "Upper bound on a single transient-retry backoff sleep."
).double(2000.0)

# ---------------------------------------------------------------------------
# Query lifecycle & multi-tenant scheduler (docs/robustness.md "Query
# lifecycle"; serving/scheduler.py — the GpuSemaphore-admission analogue
# lifted from per-task to per-query, SURVEY §2.4/§7)
# ---------------------------------------------------------------------------
QUERY_TIMEOUT_MS = _conf("spark.rapids.tpu.query.timeoutMs").doc(
    "Default per-query deadline in milliseconds (0 disables). A query "
    "past its deadline is cancelled COOPERATIVELY: the next checkpoint "
    "(partition-task start, batch pull, exchange map task / reduce "
    "fetch, mesh collective launch, UDF worker round-trip) raises "
    "QueryDeadlineExceeded and the unwind releases every permit, HBM "
    "byte, spill file and the query's tracer. df.collect(timeout=seconds)"
    " overrides it per call; session.cancel() cancels without a deadline."
).commonly_used().integer(0)

QUERY_RETRY_BUDGET = _conf("spark.rapids.tpu.query.retryBudget").doc(
    "Total TRANSIENT device-error retries one query may consume across "
    "all of its tasks (each site's attempts stay bounded by "
    "spark.rapids.tpu.deviceRetry.maxAttempts). Past the budget the next "
    "transient error fails that query alone — a flapping query cannot "
    "sit in retry/backoff loops holding the shared pool's permits while "
    "healthy queries queue behind it."
).integer(64)

SCHED_MAX_CONCURRENT = _conf(
    "spark.rapids.tpu.sched.maxConcurrentQueries").doc(
    "How many admitted queries may execute concurrently against the "
    "device pool (the per-query analogue of concurrentTpuTasks: admitted "
    "queries' tasks still contend on the TpuSemaphore). Queued "
    "submissions past this bound wait FIFO with round-robin fairness "
    "across sessions."
).commonly_used().integer(8)

SCHED_MAX_QUEUE = _conf("spark.rapids.tpu.sched.maxQueuedQueries").doc(
    "Bound on the scheduler's admission queue across all sessions. A "
    "submission past the bound is rejected immediately with the typed "
    "QueryQueueFull backpressure error — shedding load at the front door "
    "instead of stacking working sets until HBM pressure OOMs every "
    "query on the device."
).integer(64)

SCHED_HBM_WATERMARK = _conf(
    "spark.rapids.tpu.sched.hbmAdmissionWatermark").doc(
    "Admit a queued query only while HbmBudget usage is at or below this "
    "fraction of the budget (and a concurrency slot is free). Waived "
    "when no query is running, so admission always makes progress even "
    "if parked state keeps usage high."
).double(0.9)

QUERY_PRIORITY = _conf("spark.rapids.tpu.query.priority").doc(
    "SLO priority class for this session's queries: 'interactive', "
    "'batch' or 'background' (docs/serving.md). Admission is strict "
    "class precedence with earliest-deadline-first within a class; "
    "under sustained overload the scheduler sheds the LOWEST queued or "
    "running class first, returning a typed QueryShed result with a "
    "retry-after hint. df.collect(priority=...) overrides per call."
).commonly_used().string("interactive")

SCHED_CLASS_AGING_MS = _conf("spark.rapids.tpu.sched.classAgingMs").doc(
    "Anti-starvation bound for the SLO class queues: a ticket queued "
    "longer than this is promoted over class precedence (oldest such "
    "ticket first), so background work still drains under a persistent "
    "interactive load. 0 disables aging (strict precedence only)."
).double(10000.0)

SCHED_TENANT_HBM_QUOTA = _conf(
    "spark.rapids.tpu.sched.tenantHbmQuota").doc(
    "Per-tenant HBM quota as a fraction of the HbmBudget, layered ON TOP "
    "of the global admission watermark: a session whose live queries' "
    "attributed device bytes exceed quota x budget has its next query "
    "queue (sched.quota_defer_total) even when the device has headroom. "
    "<= 0 disables per-tenant quotas (the default)."
).double(0.0)

SCHED_SHED_AFTER_MS = _conf("spark.rapids.tpu.sched.shedAfterMs").doc(
    "Sustained-overload load-shedding bound: when a queued query has "
    "waited past this with every concurrency slot held and a STRICTLY "
    "lower class running, the scheduler sheds the lowest running class "
    "through the cooperative cancel token (one victim per admission "
    "pass; the unwind is the TL020-proven release path). The shed "
    "client gets a typed QueryShed result with a retry-after hint. "
    "0 disables overload shedding; queue-full shedding of a strictly "
    "lower queued class is always on."
).double(5000.0)

SHUFFLE_CHECKSUM_ENABLED = _conf(
    "spark.rapids.tpu.shuffle.checksum.enabled").doc(
    "Embed an xxhash64 checksum in every serialized shuffle block and "
    "verify it on read (the Spark analogue is SPARK-35275 shuffle "
    "checksums). A mismatched or truncated block raises FetchFailedError "
    "so the exchange re-materializes the producing map task instead of "
    "surfacing an arbitrary deserialization error."
).boolean(True)

SHUFFLE_FETCH_RETRY_MAX = _conf(
    "spark.rapids.tpu.shuffle.fetchRetry.maxAttempts").doc(
    "How many times a reduce task re-materializes lost/corrupted map "
    "outputs (FetchFailedError) before giving up; the final error chains "
    "the last FetchFailedError as its cause (Spark: stage-retry bound)."
).integer(4)

CHAOS_ENABLED = _conf("spark.rapids.tpu.test.chaos.enabled").doc(
    "Testing only: arm the seeded chaos fault injector. Named injection "
    "sites woven through the stack (hbm.alloc, spill.to_host, "
    "spill.to_disk, device.dispatch, shuffle.serialize, shuffle.write, "
    "shuffle.read, ici.fetch, pipeline.task) draw from per-site PRNGs and "
    "raise configured fault kinds at the configured probability "
    "(docs/robustness.md)."
).boolean(False)

CHAOS_SEED = _conf("spark.rapids.tpu.test.chaos.seed").doc(
    "Chaos injector seed. Each site derives an independent deterministic "
    "PRNG stream from (seed, site), so a run's injection trace is "
    "replayable per site regardless of thread interleaving."
).integer(0)

CHAOS_SITES = _conf("spark.rapids.tpu.test.chaos.sites").doc(
    "Comma-separated injection sites to arm; empty means every site."
).string_list([])

CHAOS_KINDS = _conf("spark.rapids.tpu.test.chaos.kinds").doc(
    "Comma-separated fault kinds to draw from (retry_oom, split_oom, "
    "transient, fatal, corrupt, truncate, io_error, latency); empty means "
    "every kind applicable at the site. OOM kinds only fire inside a "
    "retry-framework scope (where they are healable by design); corrupt/"
    "truncate only apply at byte-stream sites."
).string_list([])

CHAOS_PROBABILITY = _conf("spark.rapids.tpu.test.chaos.probability").doc(
    "Per-site-visit probability of injecting a fault."
).double(0.05)

CHAOS_MAX_INJECTIONS = _conf("spark.rapids.tpu.test.chaos.maxInjections").doc(
    "Cap on total randomized injections per configure (0 = unbounded) — a "
    "guardrail so high probabilities cannot starve a query forever."
).integer(0)

CHAOS_LATENCY_MS = _conf("spark.rapids.tpu.test.chaos.latencyMs").doc(
    "Upper bound of the injected delay for the `latency` fault kind."
).double(2.0)


# ---------------------------------------------------------------------------
# Device-subset sizing knobs (kernels consult these through the session's
# apply_kernel_tunables at session construction)
# ---------------------------------------------------------------------------

REGEX_MAX_DEVICE_ROW_BYTES = _conf(
    "spark.rapids.sql.regexp.maxDeviceRowBytes").doc(
    "Longest string row the device regex DFA walks (rlike); longer rows "
    "route the batch to the host engine (reference "
    "spark.rapids.sql.regexp.enabled + RegexComplexityEstimator sizing)."
).integer(4096)

REGEX_MAX_SPAN_ROW_BYTES = _conf(
    "spark.rapids.sql.regexp.maxSpanRowBytes").doc(
    "Longest string row for device regexp_replace/extract span matching "
    "(the walk is O(bytes x row_len))."
).integer(512)

JSON_DEVICE_SCAN_MAX_ROW_BYTES = _conf(
    "spark.rapids.sql.json.maxDeviceRowBytes").doc(
    "Longest JSON document the device get_json_object scan processes; "
    "longer rows route to the host engine."
).integer(4096)

HASH_DEVICE_MAX_STRING_BYTES = _conf(
    "spark.rapids.tpu.hash.maxDeviceStringBytes").doc(
    "Longest string a device hash kernel (murmur3/xxhash64/hive-hash) "
    "processes with the padded byte-matrix loop; columns with longer rows "
    "hash on the host (O(rows x max_len) device cost)."
).integer(4096)

REGEX_MAX_DFA_STATES = _conf(
    "spark.rapids.tpu.regex.maxDfaStates").doc(
    "Upper bound on device regex DFA states; patterns compiling larger "
    "fall back to the host engine (reference regex transpiler state cap)."
).integer(128)

COMPILED_JOIN_DIM_CACHE_SIZE = _conf(
    "spark.rapids.tpu.join.compiled.dimCacheSize").doc(
    "LRU entries in the cross-execution dimension build cache of the "
    "compiled star-join stage; each entry pins its HBM key/payload arrays."
).integer(8)

EXECUTOR_HEARTBEAT_TIMEOUT_SECONDS = _conf(
    "spark.rapids.shuffle.executor.heartbeatTimeoutSeconds").doc(
    "A multi-process executor worker missing heartbeats for this long is "
    "declared lost and its tasks re-run (reference "
    "RapidsShuffleHeartbeatManager intervals)."
).double(3.0)

UDF_WORKER_TIMEOUT_SECONDS = _conf(
    "spark.rapids.sql.python.workerTimeoutSeconds").doc(
    "Seconds a python UDF may run in its worker before the worker is "
    "killed and replaced (reference python worker watchdog)."
).integer(120)

SHUFFLE_HEARTBEAT_TIMEOUT_SECONDS = _conf(
    "spark.rapids.shuffle.heartbeat.timeoutSeconds").doc(
    "Peer liveness window for the shuffle heartbeat registry; peers silent "
    "longer than this are reported lost and their map outputs invalidated "
    "(reference RapidsShuffleHeartbeatManager timeout)."
).integer(30)

CAST_FLOAT_TO_STRING_ENABLED = _conf(
    "spark.rapids.sql.castFloatToString.enabled").doc(
    "Enable float->string casts on TPU (Java-exact shortest-round-trip "
    "formatting; reference castFloatToString incompatibility switch)."
).boolean(True)

CAST_STRING_TO_FLOAT_ENABLED = _conf(
    "spark.rapids.sql.castStringToFloat.enabled").doc(
    "Enable string->float casts on TPU (reference castStringToFloat "
    "incompatibility switch)."
).boolean(True)

CAST_STRING_TO_TIMESTAMP_ENABLED = _conf(
    "spark.rapids.sql.castStringToTimestamp.enabled").doc(
    "Enable string->timestamp casts on TPU (reference "
    "castStringToTimestamp incompatibility switch)."
).boolean(True)

VARIABLE_FLOAT_AGG_ENABLED = _conf(
    "spark.rapids.sql.variableFloatAgg.enabled").doc(
    "Allow float aggregations whose result can vary run to run with "
    "parallelism (sum/avg ordering; reference variableFloatAgg switch). "
    "When false, float sum/avg aggregations fall back to the CPU."
).boolean(True)

BUCKETING_WRITE_ENABLED = _conf(
    "spark.rapids.sql.format.write.bucketing.enabled").doc(
    "Enable bucketBy writes (per-bucket files with a bucket-spec sidecar; "
    "reference GpuFileFormatWriter bucketing)."
).boolean(True)

BUCKETING_READ_PRUNE_ENABLED = _conf(
    "spark.rapids.sql.format.read.bucketPruning.enabled").doc(
    "Prune bucketed files by equality filters on the bucket column at scan "
    "time (reference GpuFileSourceScanExec bucket pruning)."
).boolean(True)


class RapidsConf:
    """Immutable snapshot of settings, one per query compilation.

    Reference: `new RapidsConf(plan.conf)` per-query (GpuOverrides.scala:4565).
    """

    def __init__(self, settings: Optional[Dict[str, str]] = None):
        self._settings = dict(settings or {})
        self._cache: Dict[str, Any] = {}

    def get(self, entry: ConfEntry) -> Any:
        if entry.key not in self._cache:
            self._cache[entry.key] = entry.get(self._settings)
        return self._cache[entry.key]

    def get_raw(self, key: str, default: Optional[str] = None) -> Optional[str]:
        return self._settings.get(key, default)

    def is_op_enabled(self, key: str, default: bool = True) -> bool:
        raw = self._settings.get(key)
        return default if raw is None else _parse_bool(raw)

    # Convenience accessors used on hot paths
    @property
    def sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def explain_only(self) -> bool:
        return str(self.get(SQL_MODE)).lower() == "explainonly"

    @property
    def ansi_enabled(self) -> bool:
        return self.get(ANSI_ENABLED)

    @property
    def batch_size_rows(self) -> int:
        return self.get(BATCH_SIZE_ROWS)

    @property
    def batch_size_bytes(self) -> int:
        return self.get(BATCH_SIZE_BYTES)

    def with_overrides(self, **kv: str) -> "RapidsConf":
        s = dict(self._settings)
        s.update({k.replace("__", "."): v for k, v in kv.items()})
        return RapidsConf(s)


def declare_expression_flags(names) -> None:
    """One `spark.rapids.sql.expression.<Name>` boolean entry per registered
    expression rule — the reference generates exactly this conf per
    GpuOverrides rule and lists them in the RapidsConf docs. The tagging
    layer (plan/meta.py) consults these keys on every wrapped expression;
    declaring them here types and documents them. Called by
    plan/typechecks.py once its rule registry is populated."""
    for n in sorted(set(names)):
        key = f"spark.rapids.sql.expression.{n}"
        if key in REGISTRY.entries:
            continue
        _conf(key).doc(f"Enable expression {n} on TPU.").boolean(True)


_DEFAULT = RapidsConf()


def default_conf() -> RapidsConf:
    return _DEFAULT
