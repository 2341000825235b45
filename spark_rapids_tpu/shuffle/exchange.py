"""Shuffle exchange execs: repartition data across N output partitions.

Reference: GpuShuffleExchangeExecBase.scala (prepareBatchShuffleDependency:277 —
partition on device then hand slices to the shuffle manager) + ShuffledBatchRDD.
Map side runs once per exchange (memoized, like Spark materializing a shuffle
stage); reduce side reads its partition's blocks through the multithreaded
manager and re-uploads to device.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..columnar.batch import TpuColumnarBatch
from ..config import SHUFFLE_PARTITIONS
from ..expressions.base import AttributeReference, Expression
from ..obs import flight, metrics
from ..obs import tracer as obs
from ..serving import query_context as qlc
from .manager import TpuShuffleManager
from .partitioner import (hash_partition_ids, hash_split_parts,
                          hash_split_parts_grouped, np_hash_partition_ids,
                          round_robin_partition_ids, split_by_partition)
from ..execs.base import (CpuExec, PhysicalPlan, TaskContext, TpuExec, bind_all)


class _DictionaryOverflow(Exception):
    """A collective exchange's string payload is not worth a broadcast
    dictionary (cardinality guard, or >2^31 distinct bytes — beyond the
    int32 offsets range); the exchange falls back to the per-map path
    with reason ``dictionary_overflow``."""


def materialize_exchanges(plan: PhysicalPlan, ctx: TaskContext) -> None:
    """Mesh session: before the chips' tasks fan out over `plan`
    (`parallel/mesh.py::run_chip_tasks`), the exchanges in it materialize
    from the calling thread. A chip's task then finds its blocks there:
    none drives a collective of its own while its siblings wait, and which
    thread stages an exchange does not hang on which chip got there first."""
    for node in plan.collect_nodes():
        if isinstance(node, _ExchangeBase):
            node._ensure_materialized(ctx)


class _ExchangeBase:
    """Shared map-side materialization (runs once, guarded)."""

    def _init_exchange(self, partitioning: str, keys, num_partitions: int):
        self.partitioning = partitioning
        self.keys = keys
        self._n_out = num_partitions
        self._mat_lock = threading.Lock()
        self._shuffle_id: Optional[int] = None
        self._n_maps = 0

    def num_partitions(self) -> int:
        return self._n_out

    def additional_metrics(self):
        return {"shuffleRecordsWritten": "MODERATE", "dataSize": "MODERATE",
                "shufflePartitions": "DEBUG"}

    def query_counters(self):
        return [("exchange.rows", self.metrics["shuffleRecordsWritten"]),
                ("exchange.bytes", self.metrics["dataSize"]),
                ("exchange.partitions", self.metrics["shufflePartitions"])]

    def _count_block(self, rows: int, nbytes: int) -> None:
        """One map-output block put or written (both numbers are on the
        host there); pool threads count into the same metrics."""
        self.metrics["shuffleRecordsWritten"].add(rows)
        self.metrics["dataSize"].add(nbytes)

    def _shuffle_mode(self, ctx: TaskContext) -> str:
        from ..config import SHUFFLE_MODE
        return str(ctx.conf.get(SHUFFLE_MODE)).upper()

    def _map_task_threads(self, ctx: TaskContext) -> int:
        from ..config import (SHUFFLE_PIPELINE_ENABLED,
                              SHUFFLE_PIPELINE_MAP_THREADS)
        if not ctx.conf.get(SHUFFLE_PIPELINE_ENABLED):
            return 1
        return max(1, int(ctx.conf.get(SHUFFLE_PIPELINE_MAP_THREADS)))

    def _prefetch_depth(self, ctx: TaskContext) -> int:
        from ..config import (SHUFFLE_PIPELINE_ENABLED,
                              SHUFFLE_PIPELINE_PREFETCH)
        if not ctx.conf.get(SHUFFLE_PIPELINE_ENABLED):
            return 0
        return max(0, int(ctx.conf.get(SHUFFLE_PIPELINE_PREFETCH)))

    def _ensure_materialized(self, ctx: TaskContext) -> None:
        with self._mat_lock:
            if self._shuffle_id is not None:
                return
            mgr = TpuShuffleManager.get(ctx.conf)
            sid = mgr.new_shuffle_id()
            child = self.children[0]
            # map-task spans on pool threads (empty span stacks) nest under
            # this materialization span via the captured parent id; the
            # query lifecycle binding rides along the same way, so a
            # cancel/deadline trips map tasks on pool threads too
            self._obs_parent = obs.current_span()
            self._query_ctx = qlc.current()
            try:
                with obs.span(f"exchange s{sid} materialize", cat="shuffle",
                              shuffle=sid) as mat_span:
                    if mat_span is not None:
                        self._obs_parent = mat_span
                    if self._try_materialize_collective(sid, ctx):
                        self._n_maps = 1  # one collective "map": whole
                        self._shuffle_id = sid  # exchange
                        self.metrics["shufflePartitions"].add(self._n_out)
                        return
                    self._n_maps = child.num_partitions()
                    threads = self._map_task_threads(ctx)
                    # batched multi-partition dispatch: the unit of
                    # scheduling is a partition GROUP (spark.rapids.tpu.
                    # dispatch.partitionBatch); group size 1 is exactly the
                    # PR 2 per-partition behavior
                    group = self._map_group_size(ctx) if self._n_maps > 1 \
                        else 1
                    groups = [list(range(s, min(s + group, self._n_maps)))
                              for s in range(0, self._n_maps, max(1, group))]
                    if threads > 1 and len(groups) > 1:
                        self._materialize_maps_pipelined(sid, ctx, mgr,
                                                         threads, groups)
                    else:
                        for ids in groups:
                            self._run_group_guarded(sid, ids, ctx, mgr)
                    self._shuffle_id = sid
                    self.metrics["shufflePartitions"].add(self._n_out)
            except BaseException:
                # A cancel/shed/deadline trip (or any map-task error)
                # unwinding MID-materialization leaves blocks already
                # committed under `sid` while self._shuffle_id is still
                # None — cleanup_shuffle keys off _shuffle_id and would
                # never visit them, so each such unwind would strand the
                # finished maps' device blocks in the catalog for the
                # life of the process.
                self._abort_materialization(sid, ctx.conf)
                raise

    def _run_map_guarded(self, sid: int, map_id: int, ctx: TaskContext,
                         mgr, gate_device: bool = False) -> None:
        """One map task under the chaos `pipeline.task` site and the
        transient-device-error retry: a map task is idempotent (block files
        are keyed (map, reduce); the ICI catalog replaces on put), so an
        UNAVAILABLE hiccup re-runs the task instead of failing the query."""
        from ..chaos import inject
        from ..failure import with_device_retry

        def attempt() -> None:
            qlc.checkpoint(f"exchange.map s{sid}m{map_id}")
            inject("pipeline.task", detail=f"s{sid}m{map_id}")
            self._materialize_map(sid, map_id, ctx, mgr, gate_device)

        # bind the owning query on this (possibly pool) thread: the
        # checkpoint above, the per-query retry budget, and any nested
        # checkpoints in the member pull all route to the right query
        with qlc.bind(getattr(self, "_query_ctx", None)):
            with_device_retry(attempt, ctx.conf)

    def _map_group_size(self, ctx: TaskContext) -> int:
        """How many map partitions one scheduled task processes (batched
        multi-partition dispatch). 1 — per-partition tasks — except for the
        TPU exchange in MULTITHREADED mode, which reads
        spark.rapids.tpu.dispatch.partitionBatch."""
        return 1

    def _run_group_guarded(self, sid: int, ids: List[int], ctx: TaskContext,
                           mgr, gate_device: bool = False) -> None:
        """One partition GROUP as a schedulable unit. Idempotent exactly
        like a single map task — a retry rewrites every member's block
        files, keyed (map, reduce) — so the same chaos site and transient
        device-error retry wrap the whole group."""
        if len(ids) == 1:
            self._run_map_guarded(sid, ids[0], ctx, mgr, gate_device)
            return
        from ..chaos import inject
        from ..failure import with_device_retry

        def attempt() -> None:
            qlc.checkpoint(f"exchange.group s{sid}g{ids[0]}-{ids[-1]}")
            inject("pipeline.task", detail=f"s{sid}g{ids[0]}-{ids[-1]}")
            self._materialize_map_group(sid, ids, ctx, mgr)

        with qlc.bind(getattr(self, "_query_ctx", None)):
            with_device_retry(attempt, ctx.conf)

    def _materialize_maps_pipelined(self, sid: int, ctx: TaskContext, mgr,
                                    n_threads: int,
                                    groups: Optional[List[List[int]]] = None
                                    ) -> None:
        """Pipelined map-side materialization (reference
        RapidsShuffleThreadedWriterBase): map tasks run concurrently on a
        bounded pool, device work gated per task by the TPU semaphore, and
        each task's deferred host commit (file serialization I/O, released
        from the semaphore) overlaps sibling maps' device work. Block files
        are keyed (map, reduce) so completion order cannot change results.

        Failure discipline: the first failing map cancels every sibling
        that has not started yet (running ones finish — their semaphore
        permits and in-flight byte reservations release on their own error
        paths), and its error propagates after all submitted work has
        settled, so no map task is still running when the caller sees the
        failure."""
        # Pre-materialize nested exchanges serially first: a concurrent map
        # task must never trigger a recursive materialization while sibling
        # maps hold device permits — the upstream exchange's own map tasks
        # would starve for permits and deadlock.
        for node in self.children[0].collect_nodes():
            if isinstance(node, _ExchangeBase):
                node._ensure_materialized(ctx)
        if groups is None:
            groups = [[m] for m in range(self._n_maps)]
        from concurrent.futures import CancelledError, ThreadPoolExecutor
        pool = ThreadPoolExecutor(
            max_workers=min(n_threads, len(groups)),
            thread_name_prefix="exchange-map")
        try:
            futs = [pool.submit(self._run_group_guarded, sid, ids, ctx, mgr,
                                True)
                    for ids in groups]
            errors = []
            for f in futs:  # wait for ALL non-cancelled maps: no map task
                # may still be running when the error propagates
                try:
                    f.result()
                except CancelledError:
                    continue
                except BaseException as e:  # noqa: BLE001
                    if not errors:
                        # fail fast: not-yet-started siblings are pointless
                        # work (and would delay the error) — cancel them
                        for g in futs:
                            g.cancel()
                    errors.append(e)
            if errors:
                raise errors[0]
        finally:
            pool.shutdown(wait=True)

    def _try_materialize_collective(self, sid: int, ctx: TaskContext) -> bool:
        """Mesh collective data plane; overridden by the device exchange."""
        return False

    def _materialize_map(self, sid: int, map_id: int, ctx: TaskContext,
                         mgr, gate_device: bool = False) -> None:
        from ..profiling import sync_scope
        map_ctx = TaskContext(map_id, ctx.conf)
        # pipelined map tasks run on pool threads with a fresh (empty)
        # sync-scope stack: anchor ledger attribution to this exchange;
        # nested operator pulls re-attribute via their own scopes. The obs
        # map-task span nests under the materialization span cross-thread
        # via the captured parent id.
        with sync_scope(self.node_name()), \
                obs.span(f"map s{sid}m{map_id}", cat="shuffle.map",
                         parent=getattr(self, "_obs_parent", None),
                         shuffle=sid, map=map_id), \
                obs.phase("exchange.map"):
            try:
                if gate_device and isinstance(self, TpuExec):
                    # pipelined map tasks take a permit up front so
                    # concurrent device work stays bounded by
                    # concurrentTpuTasks (lazy acquisition would let every
                    # pool thread dispatch at once)
                    from ..memory.semaphore import TpuSemaphore
                    TpuSemaphore.get(ctx.conf).acquire_if_necessary(map_ctx)
                commit = self._run_map_task(sid, map_id, map_ctx, mgr)
            finally:
                map_ctx.complete()  # releases the semaphore, if held
            if commit is not None:
                commit()  # host-side file I/O runs OFF the device semaphore

    def _run_map_task(self, sid: int, map_id: int, map_ctx: TaskContext,
                      mgr):
        """Returns a deferred host-commit callable, or None if the output
        was committed device-side (ICI)."""
        tables = self._partition_map_task(map_id, map_ctx)
        return lambda: mgr.write_map_output(sid, map_id, tables)

    def partition_sizes(self, ctx: TaskContext) -> List[int]:
        """Post-materialization byte size per reduce partition (the map
        output statistics AQE plans against). ICI mode serves DEVICE-SIDE
        counters: the collective keeps the exchange-time per-shard byte
        counts, and the per-map catalog tracks block sizes at put time —
        neither path fetches (or unspills) a block to answer AQE."""
        import os
        self._ensure_materialized(ctx)
        if getattr(self, "_collective", False):
            return list(self._collective_sizes)
        sizes = [0] * self._n_out
        if self._shuffle_mode(ctx) == "ICI":
            from .ici import IciShuffleCatalog
            catalog = IciShuffleCatalog.get()
            mgr2 = TpuShuffleManager.get(ctx.conf)
            # same bounded FetchFailed recovery as the read path (a lost
            # map's sizes are unknowable until its output is re-run), but
            # the sizes themselves come from catalog metadata, not blocks
            return self._ici_recovering_fetch(
                -1, ctx, mgr2,
                lambda: catalog.reduce_sizes(self._shuffle_id, self._n_maps,
                                             self._n_out))
        mgr = TpuShuffleManager.get(ctx.conf)
        for r in range(self._n_out):
            for m in range(self._n_maps):
                p = mgr._path(self._shuffle_id, m, r)
                if os.path.exists(p):
                    sizes[r] += os.path.getsize(p)
        return sizes

    def partition_row_counts(self, ctx: TaskContext) -> Optional[List[int]]:
        """Exact per-reduce ROW counts when the exchange materialized
        collectively (from the device-side sizing counters); None when only
        byte sizes are known (per-map paths)."""
        self._ensure_materialized(ctx)
        if getattr(self, "_collective", False):
            return list(self._collective_rows)
        return None

    def map_block_sizes(self, reduce_id: int, ctx: TaskContext) -> List[int]:
        """Per-map byte sizes of one reduce partition — the granularity AQE
        skew splitting slices on (reference PartialReducerPartitionSpec maps).
        A collective exchange materializes ONE fused block per reduce
        partition, but its row order is (source shard asc, stable), so the
        per-SOURCE row counts from the sizing sync are its map statistics:
        slice m == source shard m, and a contiguous group of sources is a
        contiguous row range of the block (execute_partition_maps serves it
        by slicing — no per-map blocks needed). Returns [] only when the
        exchange truly has nothing to slice on."""
        import os
        self._ensure_materialized(ctx)
        if getattr(self, "_collective", False):
            src = getattr(self, "_collective_src_rows", None)
            if src is None or reduce_id >= len(src):
                return []
            rb = int(getattr(self, "_collective_row_bytes", 0))
            return [int(n) * rb for n in src[reduce_id]]
        if self._shuffle_mode(ctx) == "ICI":
            from .ici import IciShuffleCatalog
            catalog = IciShuffleCatalog.get()
            if self._n_maps <= 1:
                return []
            return catalog.block_sizes(self._shuffle_id, reduce_id,
                                       self._n_maps)
        mgr = TpuShuffleManager.get(ctx.conf)
        out = []
        for m in range(self._n_maps):
            p = mgr._path(self._shuffle_id, m, reduce_id)
            out.append(os.path.getsize(p) if os.path.exists(p) else 0)
        return out

    def _fetch_retry_limit(self, ctx: TaskContext) -> int:
        from ..config import SHUFFLE_FETCH_RETRY_MAX
        return max(1, int(ctx.conf.get(SHUFFLE_FETCH_RETRY_MAX)))

    def _fetch_tables(self, idx: int, ctx: TaskContext, mgr,
                      map_ids=None) -> Iterator:
        """MULTITHREADED-mode reduce fetch with lineage recovery: streams
        one reduce partition's arrow tables in map order; a FetchFailedError
        (corrupt/truncated block detected by the checksum, unreadable file)
        re-materializes the producing map tasks and resumes with the maps
        not yet consumed — already-yielded blocks are never re-yielded. The
        attempt count is conf-bounded (spark.rapids.tpu.shuffle.fetchRetry.
        maxAttempts); the terminal error chains the last FetchFailedError
        as its cause (Spark: FetchFailed → bounded stage retries)."""
        from .ici import FetchFailedError
        limit = self._fetch_retry_limit(ctx)
        pending = list(map_ids) if map_ids is not None \
            else list(range(self._n_maps))
        failures = 0
        while pending:
            # reduce-fetch cancellation boundary: runs on the consumer
            # thread (bound) or a prefetch worker (bound via inheritance)
            qlc.checkpoint(f"exchange.fetch s{self._shuffle_id}r{idx}")
            it = mgr.iter_partition_sources(self._shuffle_id, idx,
                                            self._n_maps,
                                            map_ids=list(pending))
            try:
                for m, t in it:
                    pending.remove(m)
                    if t is not None:
                        yield t
            except FetchFailedError as ff:
                failures += 1
                metrics.counter_inc("shuffle.fetch_retries")
                flight.note("shuffle.fetchRetry", shuffle=self._shuffle_id,
                            reduce=idx, maps=list(ff.map_ids),
                            attempt=failures)
                if obs._ACTIVE:
                    obs.event("shuffle.fetchRetry", cat="shuffle",
                              shuffle=self._shuffle_id, reduce=idx,
                              maps=list(ff.map_ids), attempt=failures)
                if failures > limit:  # maxAttempts counts RECOVERY rounds
                    raise RuntimeError(
                        f"shuffle {self._shuffle_id} reduce {idx}: block "
                        f"fetch failed after {limit} re-materialization "
                        f"attempts (spark.rapids.tpu.shuffle.fetchRetry."
                        f"maxAttempts={limit})") from ff
                with self._mat_lock:
                    for mm in ff.map_ids:
                        self._run_map_guarded(self._shuffle_id, mm, ctx,
                                              mgr)

    def _ici_fetch_blocks(self, idx: int, ctx: TaskContext, mgr, catalog,
                          metric=None) -> List:
        """ICI-mode reduce fetch with conf-bounded lineage recovery:
        transient runtime errors heal via with_device_retry, a
        FetchFailedError (lost peer, invalidated output, corrupted spill
        tier) re-runs the missing map tasks."""
        def fetch():
            if metric is not None:
                with metric.timed():
                    return list(catalog.iter_blocks(
                        self._shuffle_id, idx, self._n_maps))
            return list(catalog.iter_blocks(self._shuffle_id, idx,
                                            self._n_maps))

        return self._ici_recovering_fetch(idx, ctx, mgr, fetch)

    def _ici_recovering_fetch(self, idx: int, ctx: TaskContext, mgr, fetch):
        """Run `fetch` (blocks, sizes, any catalog read) under the shared
        ICI recovery discipline: with_device_retry for transients, bounded
        re-materialization of exactly the maps a FetchFailedError names."""
        from ..failure import with_device_retry
        from .ici import FetchFailedError
        limit = self._fetch_retry_limit(ctx)
        failures = 0
        while True:
            qlc.checkpoint(f"exchange.fetch s{self._shuffle_id}r{idx}")
            try:
                return with_device_retry(fetch, ctx.conf)
            except FetchFailedError as ff:
                failures += 1
                metrics.counter_inc("shuffle.fetch_retries")
                flight.note("shuffle.fetchRetry", shuffle=self._shuffle_id,
                            reduce=idx, maps=list(ff.map_ids),
                            attempt=failures)
                if obs._ACTIVE:
                    obs.event("shuffle.fetchRetry", cat="shuffle",
                              shuffle=self._shuffle_id, reduce=idx,
                              maps=list(ff.map_ids), attempt=failures)
                if failures > limit:  # same accounting as _fetch_tables:
                    # maxAttempts counts recovery rounds, and no map is
                    # re-run whose output could never be fetched again
                    raise RuntimeError(
                        f"shuffle {self._shuffle_id} reduce {idx}: "
                        f"re-materialization failed after {limit} attempts "
                        f"(spark.rapids.tpu.shuffle.fetchRetry.maxAttempts)"
                    ) from ff
                with self._mat_lock:
                    for map_id in ff.map_ids:
                        self._run_map_guarded(self._shuffle_id, map_id,
                                              ctx, mgr)

    def cleanup_shuffle(self, conf) -> None:
        """Release this exchange's shuffle blocks/files and allow
        re-materialization (called at query end by the session)."""
        with self._mat_lock:
            sid = self._shuffle_id
            self._shuffle_id = None
        if sid is None:
            return
        self._abort_materialization(sid, conf)

    def _abort_materialization(self, sid: int, conf) -> None:
        """Release every block/file committed under `sid` regardless of
        whether _shuffle_id was ever set — shared by the normal query-end
        release and the mid-materialization unwind path."""
        from .ici import IciShuffleCatalog
        IciShuffleCatalog.get().cleanup(sid)
        TpuShuffleManager.get(conf).cleanup(sid)
        close_dicts = getattr(self, "_close_dicts", None)
        if close_dicts is not None:  # dictionary-exchange broadcast state
            close_dicts()


class TpuShuffleExchangeExec(_ExchangeBase, TpuExec):
    def __init__(self, child: PhysicalPlan, partitioning: str,
                 keys: Sequence[Expression], num_partitions: int):
        TpuExec.__init__(self, [child])
        self._init_exchange(partitioning, bind_all(list(keys), child.output),
                            num_partitions)

    @property
    def output(self):
        return self.children[0].output

    def node_desc(self) -> str:
        base = f"TpuShuffleExchange[{self.partitioning}, n={self._n_out}"
        # "why not collective" surfaced where the plan is read
        # (explain("metrics"), the bundle's plan tree): a mesh-session
        # exchange that rode the per-map path says why
        # (obs/mesh_profile.py)
        reason = getattr(self, "_collective_reason", None)
        if reason and not getattr(self, "_collective", False):
            return f"{base}, per_map={reason}]"
        return base + "]"

    def additional_metrics(self):
        return {**super().additional_metrics(),
                "partitionTime": "MODERATE", "serializationTime": "MODERATE",
                "deserializationTime": "MODERATE",
                "dictionaryEncodeTime": "MODERATE"}

    def _collective_mesh(self, ctx: TaskContext):
        """The mesh this exchange's collective would run on, or None.
        Plan-time selection (plan/overrides.py sets `collective_planned`
        when a mesh session is active) covers hash AND single
        partitionings; un-planned exchanges (hand-assembled plans, tests)
        keep the dynamic hash-only eligibility check. Every decline
        records its reason on the node (the plan-time reason from
        overrides.py is kept unless a runtime check finds a different
        cause)."""
        if self._shuffle_mode(ctx) != "ICI":
            return None
        from ..parallel.mesh import (MeshContext, collective_payload,
                                     mesh_session_active)
        # reasons are only meaningful inside a mesh session — a plain ICI
        # session's per-map exchanges are not "fallbacks" from anything
        in_mesh_session = mesh_session_active(ctx.conf) is not None

        def decline(reason: str):
            if in_mesh_session:
                self._collective_reason = reason
            return None

        from ..config import MESH_COLLECTIVE_ENABLED
        if not ctx.conf.get(MESH_COLLECTIVE_ENABLED):
            return decline("collective_conf_off")
        payload = collective_payload(self.output, ctx.conf)
        if payload is None:
            return decline("string_or_nested_payload")
        # "dict": string columns ride the fabric as int32 codes + one
        # broadcast dictionary per exchange (encode pass at materialize,
        # decode-on-read) — spark.rapids.tpu.exchange.dictionaryEncode
        self._dict_payload = payload == "dict"
        if getattr(self, "collective_planned", False):
            mesh = mesh_session_active(ctx.conf)
        elif self.partitioning == "hash":
            mesh = MeshContext.get(ctx.conf, self._n_out)
        else:
            return decline(f"partitioning_{self.partitioning}")
        if mesh is None:
            return decline("mesh_unavailable")
        # hash routing computes murmur3 % n_shards on-device: the reduce
        # partition count must equal the mesh size exactly (the planner's
        # alignPartitions pass guarantees this for mesh sessions)
        if self.partitioning == "hash" \
                and mesh.devices.size != self._n_out:
            return decline("partitions_misaligned")
        return mesh

    def _try_materialize_collective(self, sid: int, ctx: TaskContext) -> bool:
        """ICI-mesh data plane (reference UCX mode, shuffle-plugin/
        UCXShuffleTransport.scala): ONE jitted all_to_all moves every shard's
        hash-bucketed rows to its reduce partition's shard (or funnels every
        shard's rows to shard 0 for single partitioning — the partial→final
        aggregation merge). Used when a mesh session is active (planner
        selection) or the exchange is a hash partitioning onto exactly
        mesh-size partitions, and all columns have fixed-width device
        layouts. Results land in the device-resident catalog keyed as a
        single collective map output, with the exchange-time per-shard
        row/byte counters kept as the partition statistics AQE plans
        against; FetchFailed recovery re-runs the collective."""
        # a re-materialization (next query after cleanup_shuffle) must not
        # inherit the previous query's collective verdict: if this attempt
        # declines or falls back, the per-map path owns the shuffle id
        self._collective = False
        self._close_dicts()
        mesh = self._collective_mesh(ctx)
        if mesh is None:
            reason = getattr(self, "_collective_reason", None)
            if reason:
                # mesh-session exchange routed per-map: count the reason
                # (mesh.per_map_exchange{reason}) for the multichip
                # summary / explain("metrics") — obs/mesh_profile.py
                self._count_fallback(sid, reason)
            return False
        from ..columnar.batch import concat_batches
        from ..failure import with_device_retry
        from ..memory.hbm import TpuOOM
        from ..memory.spill import SpillableColumnarBatch
        from ..parallel.mesh import (mesh_hash_exchange, mesh_single_exchange,
                                     on_chip, run_chip_tasks)
        from ..profiling import sync_scope
        from .ici import IciShuffleCatalog
        n_dev = mesh.devices.size
        chips = list(mesh.devices.flat)
        child = self.children[0]
        materialize_exchanges(child, ctx)
        # collect per-shard groups as SPILLABLE batches so HBM pressure from
        # later map partitions can evict earlier outputs (the per-map ICI path
        # gets this from the catalog; the collective must provide it itself).
        # Map partition m runs on chip m % n (`run_chip_tasks`: the chips at
        # once) and its batches stay there: group r is appended to by chip
        # r's task alone.
        groups: List[List[SpillableColumnarBatch]] = [[] for _ in range(n_dev)]

        def pull(m: int) -> None:
            qlc.checkpoint(f"exchange.map s{sid}m{m}")
            mctx = TaskContext(m, ctx.conf)
            try:
                for b in child.execute_partition(m, mctx):
                    if b.num_rows:
                        groups[m % n_dev].append(SpillableColumnarBatch(b))
            finally:
                mctx.complete()

        try:
            with obs.phase("mesh.stage"):
                run_chip_tasks(ctx.conf, range(child.num_partitions()), pull)
            if not any(groups):
                IciShuffleCatalog.get().mark_map_complete(sid, 0)
                self._collective = True
                self._collective_rows = [0] * self._n_out
                self._collective_sizes = [0] * self._n_out
                self._collective_seq = None
                self._collective_src_rows = None
                self._collective_row_bytes = 0
                return True

            def stage(r: int):
                """Shard group r as one batch on chip r, and its rows'
                destination ids."""
                if not groups[r]:
                    return None, None
                got = [sb.get_batch() for sb in groups[r]]
                b = concat_batches(got) if len(got) > 1 else got[0]
                # partition ids hash the ORIGINAL key values (a dictionary
                # code is exchange-local; hashing it would break
                # co-partitioning with sibling exchanges)
                pids = hash_partition_ids(b, self.keys, n_dev, ctx,
                                          metrics=self.metrics) \
                    if self.partitioning == "hash" else None
                return b, pids

            def run_collective():
                # idempotent: a transient fault on the fabric (chaos
                # mesh.link) re-stages from the still-open spillables —
                # and a lost-map recovery re-runs the dictionary ENCODE
                # along with everything else (the dictionaries are a pure
                # function of the still-open map outputs)
                with self.metrics["partitionTime"].timed(), \
                        sync_scope(self.node_name()):
                    with obs.phase("mesh.stage"):
                        staged = run_chip_tasks(ctx.conf, range(n_dev), stage)
                    batches = [staged[r][0] for r in range(n_dev)]
                    names = [a.name for a in self.output]
                    if getattr(self, "_dict_payload", False):
                        batches = self._encode_dict_payload(batches, ctx,
                                                            chips)
                    if self.partitioning == "single":
                        return mesh_single_exchange(mesh, batches, names,
                                                    shuffle_id=sid,
                                                    conf=ctx.conf)
                    return mesh_hash_exchange(
                        mesh, batches, [staged[r][1] for r in range(n_dev)],
                        names, shuffle_id=sid, conf=ctx.conf)

            result = with_device_retry(run_collective, ctx.conf)
        except _DictionaryOverflow:
            # the broadcast dictionary is not worth it (cardinality guard,
            # or >2^31 distinct bytes — beyond int32 offsets): the per-map
            # device-resident path carries raw strings natively
            self._collective_reason = "dictionary_overflow"
            self._count_fallback(sid, "dictionary_overflow")
            IciShuffleCatalog.get().cleanup(sid)
            self._close_dicts()
            return False
        except TpuOOM:
            # memory pressure while staging the collective: the per-map path
            # has the full incremental-spill discipline; drop any partial
            # state for this shuffle id and let the caller run per-map
            self._collective_reason = "staging_oom"
            self._count_fallback(sid, "staging_oom")
            IciShuffleCatalog.get().cleanup(sid)
            self._close_dicts()
            return False
        finally:
            for g in groups:
                for sb in g:
                    sb.close()
        catalog = IciShuffleCatalog.get()
        for r in range(self._n_out):
            blk = result.batches[r]
            if result.rows[r]:
                catalog.put_block(sid, 0, r, blk, owner="mesh-collective")
                self._count_block(result.rows[r], result.bytes[r])
        catalog.mark_map_complete(sid, 0)
        self._collective = True
        self.mesh_metric("meshExchanges").add(1)
        self.mesh_metric("meshPerMapFallbacks")
        self.mesh_metric("meshRowsMoved").add(result.rows_moved)
        self.mesh_metric("meshBytesMoved").add(
            result.rows_moved * result.row_bytes)
        self.mesh_metric("meshReplicatedBytes").add(result.replicated_bytes)
        # device-side partition statistics: exact per-reduce row/byte counts
        # from the exchange's sizing counters — partition_sizes (AQE) serves
        # these without fetching (or unspilling) a single block
        self._collective_rows = list(result.rows[: self._n_out])
        self._collective_sizes = list(result.bytes[: self._n_out])
        # per-SOURCE row split of each reduce block (the sizing counts'
        # columns): the fused block's row order is (source asc, stable),
        # so AQE skew slicing serves a contiguous source range as a
        # contiguous row slice (map_block_sizes / execute_partition_maps)
        self._collective_src_rows = None if result.src_rows is None \
            else [list(sr) for sr in result.src_rows[: self._n_out]]
        self._collective_row_bytes = int(result.row_bytes)
        # profile seq: the consumer read's flow event references it so the
        # Chrome export ties producer exchange → consumer read
        self._collective_seq = (result.profile or {}).get("seq")
        return True

    def _count_fallback(self, sid: int, reason: str) -> None:
        """A mesh-session exchange routed per-map: the process-wide
        `mesh.per_map_exchange{reason}` (obs/mesh_profile.py, for the
        multichip summary and explain("metrics")) and this query's
        `mesh.per_map_fallbacks`, whole and by reason."""
        from ..obs import mesh_profile as _mprof
        _mprof.record_fallback(sid, reason)
        self.mesh_metric("meshPerMapFallbacks").add(1)
        self.mesh_metric(f"meshPerMapFallbacks.{reason}").add(1)

    def mesh_counters(self):
        out = super().mesh_counters()
        for key, m in list(self.metrics.items()):
            if key == "meshExchanges":
                out.append(("mesh.exchanges", m))
            elif key.startswith("meshPerMapFallbacks"):
                out.append(("mesh.per_map_fallbacks"
                            + key[len("meshPerMapFallbacks"):], m))
        return out

    def _close_dicts(self) -> None:
        dcols = getattr(self, "_dict_cols", None)
        if dcols:
            for sb in dcols.values():
                sb.close()
        self._dict_cols = None

    def _encode_dict_payload(self, batches, ctx: TaskContext, chips=None):
        """Map-side dictionary-encode pass of the collective exchange:
        build ONE dictionary per string/binary column across ALL shards'
        map outputs, replace each column with its int32 codes (nulls ride
        the code validity), and park the dictionaries as SPILLABLE device
        batches on the exchange — under HBM pressure they spill and
        restore through the same v2 framing + checksum tier as any
        shuffle block, and `cleanup_shuffle` releases them with the
        blocks. The fabric then moves fixed-width codes instead of raw
        bytes (reference analogue: nvcomp-compressed shuffle batches);
        the reduce side decodes on read (`_decode_dict_block`). Raises
        `_DictionaryOverflow` past the cardinality / 2^31-byte guards."""
        import time

        import pyarrow as pa
        import pyarrow.compute as pc

        from ..columnar.vector import TpuColumnVector
        from ..config import EXCHANGE_DICT_MAX_CARDINALITY
        from ..memory.spill import SpillableColumnarBatch
        from ..parallel import mesh as _mesh
        from ..types import BinaryType, IntegerType, StringType
        t0 = time.perf_counter_ns()
        self._close_dicts()
        str_ords = [i for i, a in enumerate(self.output)
                    if isinstance(a.dtype, (StringType, BinaryType))]
        max_card = int(ctx.conf.get(EXCHANGE_DICT_MAX_CARDINALITY))
        dict_cols: Dict[int, SpillableColumnarBatch] = {}
        codes_by_shard: Dict[int, Dict[int, TpuColumnVector]] = {}
        try:
            with self.metrics["dictionaryEncodeTime"].timed():
                for o in str_ords:
                    per = [b.columns[o].to_arrow() if b is not None
                           else None for b in batches]
                    per = [a.combine_chunks()
                           if isinstance(a, pa.ChunkedArray) else a
                           for a in per]
                    from ..types import to_arrow as _t2a
                    chunks = [a for a in per if a is not None and len(a)]
                    combined = pa.chunked_array(
                        chunks or [], type=_t2a(self.output[o].dtype))
                    uniq = pc.unique(combined).drop_null()
                    nbytes = pc.sum(pc.binary_length(uniq)).as_py() or 0
                    if len(uniq) > max_card or nbytes >= (1 << 31):
                        raise _DictionaryOverflow(
                            f"ordinal {o}: {len(uniq)} distinct values / "
                            f"{nbytes} bytes")
                    dcol = TpuColumnVector.from_arrow(uniq)
                    dict_cols[o] = SpillableColumnarBatch(
                        TpuColumnarBatch([dcol], len(uniq)))
                    for shard, arr in enumerate(per):
                        if arr is None:
                            continue
                        b = batches[shard]
                        codes = pc.index_in(arr, value_set=uniq)
                        vals = np.asarray(
                            codes.fill_null(0).to_numpy(
                                zero_copy_only=False)).astype(np.int32)
                        validity = (np.asarray(codes.is_valid())
                                    if codes.null_count else None)
                        with _mesh.on_chip(chips[shard] if chips else None):
                            codes_by_shard.setdefault(shard, {})[o] = \
                                TpuColumnVector.from_numpy(
                                    IntegerType(), vals, validity,
                                    capacity=b.capacity)
        except BaseException:
            for sb in dict_cols.values():
                sb.close()
            raise
        out = []
        for shard, b in enumerate(batches):
            if b is None:
                out.append(None)
                continue
            cols = list(b.columns)
            for o, c in codes_by_shard.get(shard, {}).items():
                cols[o] = c
            out.append(TpuColumnarBatch(cols, b.num_rows, b.names))
        self._dict_cols = dict_cols
        _mesh.record_dict_encode(time.perf_counter_ns() - t0)
        return out

    def _decode_dict_block(self, b: TpuColumnarBatch) -> TpuColumnarBatch:
        """Reduce-side decode-on-read of a dictionary-encoded collective
        block: codes + the exchange's broadcast dictionary → materialized
        string columns via the device ragged gather, with the codes kept
        as each column's `dict_encoding` so a string-keyed downstream
        aggregation consumes them directly."""
        # under the materialization lock: a sibling chip's task that lost a
        # shard may be re-running the collective, which closes these
        # dictionaries and parks the same ones anew
        with self._mat_lock:
            dcols = getattr(self, "_dict_cols", None)
            if not dcols or not getattr(self, "_collective", False):
                return b
            dicts = {o: sb.get_batch().columns[0] for o, sb in dcols.items()}
        from ..columnar.batch import decode_dictionary_column
        cols = list(b.columns)
        for o, dcol in dicts.items():
            cols[o] = decode_dictionary_column(dcol, cols[o], b.num_rows,
                                               b.capacity)
        return TpuColumnarBatch(cols, b.num_rows, b.names)

    def _materialize_map(self, sid: int, map_id: int, ctx: TaskContext,
                         mgr, gate_device: bool = False) -> None:
        if getattr(self, "_collective", False):
            # collective recovery: re-run the whole exchange (a lost block in
            # mesh mode means the collective result was invalidated). The
            # per-map fallback is NOT sound here — map id 0 covers the whole
            # child, not child partition 0 — so a failed re-run must raise.
            if not self._try_materialize_collective(sid, ctx):
                raise RuntimeError(
                    f"shuffle {sid}: collective re-materialization failed "
                    f"(mesh no longer eligible)")
            return
        super()._materialize_map(sid, map_id, ctx, mgr, gate_device)

    def _chaos_lost_shard(self, idx: int, catalog) -> None:
        """Chaos `mesh.shard`: a shard's HBM lost the collective output
        (peer chip dropped). Converts the injected io_error into catalog
        invalidation so the fetch path raises FetchFailedError and the
        existing lineage recovery re-runs the collective — exactly how a
        real lost peer heals (Spark: lost executor → stage retry)."""
        if not getattr(self, "_collective", False):
            return
        from ..chaos import inject
        try:
            inject("mesh.shard", detail=f"s{self._shuffle_id}r{idx}")
        except OSError:
            catalog.invalidate_map(self._shuffle_id, 0)

    def _device_parts(self, map_id: int, ctx: TaskContext) -> Iterator[List]:
        """Device partition-split of each input batch (shared by both
        shuffle modes; reference prepareBatchShuffleDependency:277)."""
        n = self._n_out
        for batch in self.children[0].execute_partition(map_id, ctx):
            # a deferred-compaction batch skips the empty check rather than
            # force its count: the split plan handles empty inputs (all
            # bounds equal) and its bounds readback IS the chain's one sync
            if not batch.has_pending_rows and batch.num_rows == 0:
                continue
            with self.metrics["partitionTime"].timed():
                if self.partitioning == "hash":
                    # encode+split as ONE cached executable when the keys
                    # trace (opjit.partition_split_plan)
                    parts = hash_split_parts(batch, self.keys, n, ctx,
                                             metrics=self.metrics)
                elif self.partitioning in ("roundrobin", "coalesce"):
                    pids = round_robin_partition_ids(batch, n, map_id)
                    parts = split_by_partition(batch, pids, n)
                elif self.partitioning == "single":
                    parts = [batch] + [None] * (n - 1)
                else:
                    raise NotImplementedError(self.partitioning)
            yield parts

    def _partition_map_task(self, map_id: int, ctx: TaskContext) -> List:
        """MULTITHREADED mode map task: split on device, serialize to host."""
        import pyarrow as pa
        n = self._n_out
        acc: List[List] = [[] for _ in range(n)]
        for parts in self._device_parts(map_id, ctx):
            with self.metrics["serializationTime"].timed():
                for p, sub in enumerate(parts):
                    if sub is not None and sub.num_rows:
                        acc[p].append(sub.to_arrow())
        out = []
        for p in range(n):
            out.append(pa.concat_tables(acc[p]) if acc[p] else None)
            if acc[p]:
                self._count_block(out[-1].num_rows, out[-1].nbytes)
        return out

    def _run_map_task(self, sid: int, map_id: int, map_ctx: TaskContext,
                      mgr):
        if self._shuffle_mode(map_ctx) == "ICI":
            # ICI / device-resident mode (reference UCX RapidsCachingWriter):
            # blocks stay on device as spillable batches — no serialization;
            # the device-side commit happens here, under the semaphore (it IS
            # device work), so there is no deferred host commit
            from ..columnar.batch import concat_batches
            from .ici import IciShuffleCatalog, ShuffleHeartbeatManager
            catalog = IciShuffleCatalog.get()
            hb = ShuffleHeartbeatManager.get()
            from ..config import SHUFFLE_HEARTBEAT_TIMEOUT_SECONDS
            hb.timeout_s = float(map_ctx.conf.get(
                SHUFFLE_HEARTBEAT_TIMEOUT_SECONDS))
            hb.register_peer(f"executor-{map_id}")
            acc: List[List[TpuColumnarBatch]] = [[] for _ in range(self._n_out)]
            for parts in self._device_parts(map_id, map_ctx):
                for p, sub in enumerate(parts):
                    if sub is not None and sub.num_rows:
                        acc[p].append(sub)
            for p, batches in enumerate(acc):
                if batches:
                    blk = batches[0] if len(batches) == 1 \
                        else concat_batches(batches)
                    self._count_block(blk.num_rows, blk.device_memory_size())
                    catalog.put_block(sid, map_id, p, blk,
                                      owner=f"executor-{map_id}")
            catalog.mark_map_complete(sid, map_id)
            return None
        tables = self._partition_map_task(map_id, map_ctx)
        return lambda: mgr.write_map_output(sid, map_id, tables)

    # --- batched multi-partition dispatch ---------------------------------
    def _map_group_size(self, ctx: TaskContext) -> int:
        """Both shuffle modes group: MULTITHREADED defers each member's
        host commit off the permit as before, ICI commits device-resident
        blocks to the catalog under the group permit (each member still
        owns its blocks — lineage recovery re-runs SINGLE maps). The ICI
        collective path is tried before grouping and wins when eligible."""
        from ..config import DISPATCH_PARTITION_BATCH
        try:
            return max(1, int(ctx.conf.get(DISPATCH_PARTITION_BATCH)))
        except (TypeError, ValueError):
            return 1

    def _materialize_map_group(self, sid: int, ids: List[int],
                               ctx: TaskContext, mgr) -> None:
        """One map GROUP (spark.rapids.tpu.dispatch.partitionBatch): members
        pull through the child's multi-partition entry point
        (execute_partitions — a fused segment runs same-layout member
        batches as ONE grouped launch) and their hash splits run grouped
        launches with ONE bounds readback per launch. Block identity is
        unchanged: each member's tables commit under its own map id, so
        reduce reads and lineage recovery (which re-runs SINGLE maps via
        _materialize_map) never observe the grouping."""
        from ..memory.semaphore import TpuSemaphore
        from ..profiling import sync_scope
        # Pre-materialize nested exchanges BEFORE taking the group permit:
        # the group holds its one permit across the whole member pull, and a
        # nested exchange materializing inside that window would block on
        # fresh map contexts waiting for the permit this thread already
        # holds — a single-thread self-deadlock the pipelined path avoids
        # the same way. (Grouping can collapse the map side to ONE group,
        # which routes even pipeline-enabled plans through this serial path.)
        for node in self.children[0].collect_nodes():
            if isinstance(node, _ExchangeBase):
                node._ensure_materialized(ctx)
        sem = TpuSemaphore.get(ctx.conf)
        group_ctx = TaskContext(ids[0], ctx.conf)
        member_ctxs: Dict[int, TaskContext] = {}

        def ctx_of(i: int) -> TaskContext:
            mc = member_ctxs.get(i)
            if mc is None:
                mc = member_ctxs[i] = TaskContext(i, ctx.conf)
                # members ride the group's one permit: G members blocking
                # for their own permits from one pool thread would deadlock
                # the pool against concurrentTpuTasks
                sem.adopt(group_ctx, mc)
            return mc

        with sync_scope(self.node_name()), \
                obs.span(f"map s{sid}g{ids[0]}-{ids[-1]}", cat="shuffle.map",
                         parent=getattr(self, "_obs_parent", None),
                         shuffle=sid, maps=list(ids)), \
                obs.phase("exchange.map"):
            try:
                # ONE permit for the whole group — the group is one unit of
                # device work (member batches share grouped launches)
                sem.acquire_if_necessary(group_ctx)
                commits = self._run_map_group_task(sid, ids, ctx_of, mgr)
            finally:
                for mc in member_ctxs.values():
                    mc.complete()
                group_ctx.complete()  # releases the permit
            for commit in commits:
                commit()  # host-side file I/O runs OFF the device semaphore

    def _run_map_group_task(self, sid: int, ids: List[int], ctx_of,
                            mgr) -> List:
        import pyarrow as pa
        ici = self._shuffle_mode(ctx_of(ids[0])) == "ICI"
        if ici:
            # device-resident sink (reference UCX RapidsCachingWriter):
            # blocks stay on device and commit to the catalog HERE, under
            # the group permit (the put IS device work) — no host commit
            from ..config import SHUFFLE_HEARTBEAT_TIMEOUT_SECONDS
            from .ici import IciShuffleCatalog, ShuffleHeartbeatManager
            catalog = IciShuffleCatalog.get()
            hb = ShuffleHeartbeatManager.get()
            hb.timeout_s = float(ctx_of(ids[0]).conf.get(
                SHUFFLE_HEARTBEAT_TIMEOUT_SECONDS))
            for i in ids:
                hb.register_peer(f"executor-{i}")
        n = self._n_out
        group = len(ids)
        acc: Dict[int, List[List]] = {i: [[] for _ in range(n)] for i in ids}
        pending: List[Tuple[int, TpuColumnarBatch]] = []

        def sink(i: int, parts) -> None:
            if ici:
                for p, sub in enumerate(parts):
                    if sub is not None and sub.num_rows:
                        acc[i][p].append(sub)
                return
            with self.metrics["serializationTime"].timed():
                for p, sub in enumerate(parts):
                    if sub is not None and sub.num_rows:
                        acc[i][p].append(sub.to_arrow())

        def flush() -> None:
            if not pending:
                return
            lanes, pending[:] = list(pending), []
            with self.metrics["partitionTime"].timed():
                parts_per_lane = None
                if len(lanes) > 1:
                    # N lanes' encode+split in ONE launch, ONE bounds
                    # readback (opjit "exchsplitg")
                    parts_per_lane = hash_split_parts_grouped(
                        [b for _, b in lanes], self.keys, n,
                        ctx_of(lanes[0][0]), metrics=self.metrics)
                if parts_per_lane is None:  # untraceable keys: per-batch
                    parts_per_lane = [
                        hash_split_parts(b, self.keys, n, ctx_of(i),
                                         metrics=self.metrics)
                        for i, b in lanes]
            for (i, _), parts in zip(lanes, parts_per_lane):
                sink(i, parts)

        for i, batch in self.children[0].execute_partitions(list(ids),
                                                            ctx_of):
            if not batch.has_pending_rows and batch.num_rows == 0:
                continue
            if self.partitioning == "hash":
                pending.append((i, batch))
                if len(pending) >= group:
                    flush()
                continue
            with self.metrics["partitionTime"].timed():
                if self.partitioning in ("roundrobin", "coalesce"):
                    pids = round_robin_partition_ids(batch, n, i)
                    parts = split_by_partition(batch, pids, n)
                elif self.partitioning == "single":
                    parts = [batch] + [None] * (n - 1)
                else:
                    raise NotImplementedError(self.partitioning)
            sink(i, parts)
        flush()
        if ici:
            from ..columnar.batch import concat_batches
            for i in ids:
                for p, batches in enumerate(acc[i]):
                    if batches:
                        blk = batches[0] if len(batches) == 1 \
                            else concat_batches(batches)
                        self._count_block(blk.num_rows,
                                          blk.device_memory_size())
                        catalog.put_block(sid, i, p, blk,
                                          owner=f"executor-{i}")
                catalog.mark_map_complete(sid, i)
            return []
        commits = []
        for i in ids:
            tables = [pa.concat_tables(a) if a else None for a in acc[i]]
            for t in tables:
                if t is not None:
                    self._count_block(t.num_rows, t.nbytes)
            commits.append(
                lambda t=tables, m=i: mgr.write_map_output(sid, m, t))
        return commits

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        self._ensure_materialized(ctx)
        names = [a.name for a in self.output]
        if self._shuffle_mode(ctx) == "ICI":
            # device-resident read (reference RapidsCachingReader): local
            # catalog hit, no host round trip; blocks unspill if evicted.
            # FetchFailed (peer lost, output invalidated, corrupted spill
            # tier) re-runs the missing map tasks — Spark's stage-retry
            # analogue, conf-bounded with the cause chained.
            from .ici import IciShuffleCatalog
            catalog = IciShuffleCatalog.get()
            mgr = TpuShuffleManager.get(ctx.conf)
            self._chaos_lost_shard(idx, catalog)
            if obs._ACTIVE and getattr(self, "_collective", False) \
                    and getattr(self, "_collective_seq", None) is not None:
                # consumer side of the producer→consumer flow: the Chrome
                # export ties this read back to the collective exchange
                # that produced the block (flow id = the profile seq)
                obs.event("mesh.read", cat="shuffle",
                          exchange_seq=self._collective_seq,
                          shuffle=self._shuffle_id, reduce=idx)
            with obs.phase("exchange.fetch"):
                blocks = self._ici_fetch_blocks(
                    idx, ctx, mgr, catalog,
                    metric=self.metrics["deserializationTime"])
            for b in blocks:
                if b.num_rows:
                    # dictionary-encoded collective blocks decode on read
                    # (codes + broadcast dictionary → device strings)
                    yield self._decode_dict_block(
                        self._on_readers_chip(b)).rename(names)
            return
        # pipelined read (reference RapidsShuffleThreadedReaderBase): blocks
        # stream from the reader pool in map order while the NEXT block's
        # deserialize+upload is prefetched on a worker thread — downstream
        # device compute overlaps the host→device upload instead of waiting on it.
        # With coalescing on, fetched map blocks first concatenate HOST-side
        # up to the batch-size targets (reference GpuShuffleCoalesceExec):
        # one upload and one downstream dispatch per target-sized batch
        # instead of one per map block.
        mgr = TpuShuffleManager.get(ctx.conf)
        yield from _pipelined_upload(self, self._fetch_tables(idx, ctx, mgr),
                                     names, ctx)

    def _on_readers_chip(self, b: TpuColumnarBatch) -> TpuColumnarBatch:
        """Mesh session: a collective block read by its own partition's
        task is on that task's chip already. A per-map block (the fallback)
        is where its MAP task ran, and a skew slice or a coalesced group
        (AQE) may be read by another partition's task: the reader takes it
        over — rows and bytes that changed chip."""
        from ..parallel.mesh import current_chip
        here = current_chip()
        if here is None or not b.columns \
                or here in b.columns[0].data.devices():
            return b
        return self.move_to_chip(b, here)

    def execute_partition_maps(self, idx: int, map_ids: Sequence[int],
                               ctx: TaskContext) -> Iterator:
        """One reduce partition restricted to a subset of map outputs — a
        skew SLICE (reference PartialReducerPartitionSpec read). On the
        collective path "map" means SOURCE SHARD: the fused block's rows
        are ordered (source asc, stable) and the per-source row counts are
        host-known from the sizing sync, so a contiguous source group is
        served as one device slice of the block — the skewed reduce
        partition splits without ever having had per-map blocks."""
        self._ensure_materialized(ctx)
        names = [a.name for a in self.output]
        if getattr(self, "_collective", False) \
                and getattr(self, "_collective_src_rows", None) is not None:
            from ..columnar.batch import slice_batch
            from .ici import IciShuffleCatalog
            src = self._collective_src_rows[idx]
            ms = sorted(int(m) for m in map_ids)
            assert ms == list(range(ms[0], ms[-1] + 1)), \
                f"collective skew slice must be a contiguous source " \
                f"range, got {ms}"  # _slices builds groups in source order
            start = sum(src[s] for s in range(ms[0]))
            length = sum(src[s] for s in ms)
            if not length:
                return
            catalog = IciShuffleCatalog.get()
            mgr = TpuShuffleManager.get(ctx.conf)
            blocks = self._ici_fetch_blocks(
                idx, ctx, mgr, catalog,
                metric=self.metrics["deserializationTime"])
            for b in blocks:  # exactly one fused block per reduce part
                if b.num_rows:
                    full = self._decode_dict_block(b).rename(names)
                    yield self._on_readers_chip(
                        slice_batch(full, start, length))
            return
        if self._shuffle_mode(ctx) == "ICI":
            from ..failure import with_device_retry
            from .ici import IciShuffleCatalog
            catalog = IciShuffleCatalog.get()
            blocks = with_device_retry(
                lambda: list(catalog.iter_blocks(self._shuffle_id, idx,
                                                 self._n_maps,
                                                 map_ids=list(map_ids))),
                ctx.conf)
            for b in blocks:
                if b.num_rows:
                    yield self._decode_dict_block(
                        self._on_readers_chip(b)).rename(names)
            return
        mgr = TpuShuffleManager.get(ctx.conf)
        yield from _pipelined_upload(
            self, self._fetch_tables(idx, ctx, mgr, map_ids=list(map_ids)),
            names, ctx, account_output=True)


class CpuShuffleExchangeExec(_ExchangeBase, CpuExec):
    def __init__(self, child: PhysicalPlan, partitioning: str,
                 keys: Sequence[Expression], num_partitions: int):
        CpuExec.__init__(self, [child])
        self._init_exchange(partitioning, bind_all(list(keys), child.output),
                            num_partitions)

    @property
    def output(self):
        return self.children[0].output

    def node_desc(self) -> str:
        return f"CpuShuffleExchange[{self.partitioning}, n={self._n_out}]"

    def _partition_map_task(self, map_id: int, ctx: TaskContext) -> List:
        import pyarrow as pa
        n = self._n_out
        acc: List[List] = [[] for _ in range(n)]
        for t in self.children[0].execute_partition(map_id, ctx):
            if t.num_rows == 0:
                continue
            if self.partitioning == "hash":
                pids = np_hash_partition_ids(t, self.keys, n, ctx)
            elif self.partitioning in ("roundrobin", "coalesce"):
                pids = (np.arange(t.num_rows) + map_id) % n
            elif self.partitioning == "single":
                acc[0].append(t)
                continue
            else:
                raise NotImplementedError(self.partitioning)
            for p in range(n):
                sel = np.nonzero(pids == p)[0]
                if len(sel):
                    acc[p].append(t.take(pa.array(sel)))
        out = [pa.concat_tables(a) if a else None for a in acc]
        for t in out:
            if t is not None:
                self._count_block(t.num_rows, t.nbytes)
        return out

    def execute_partition(self, idx: int, ctx: TaskContext) -> Iterator:
        self._ensure_materialized(ctx)
        mgr = TpuShuffleManager.get(ctx.conf)
        names = [a.name for a in self.output]
        for t in self._fetch_tables(idx, ctx, mgr):
            if t.num_rows:
                yield t.rename_columns(names)


class TpuShuffleReaderExec(TpuExec):
    """AQE shuffle reader (reference GpuCustomShuffleReaderExec,
    execution/GpuCustomShuffleReaderExec.scala:37): reads the materialized
    exchange with a coalesced partition spec — small reduce partitions are
    grouped up to the advisory size, so downstream tasks see fewer,
    better-filled partitions. (Skew splitting is handled at the join level
    by sub-partitioning, execs/joins.py, where key co-location is not
    required to survive.)"""

    def __init__(self, child, advisory_bytes: int, conf=None):
        super().__init__([child])
        self.advisory_bytes = advisory_bytes
        # planner conf snapshot, threaded in AT CONSTRUCTION: num_partitions
        # materializes the child exchange, and doing that under default_conf
        # would let AQE specs diverge between planning and execution
        # (different shuffle mode / pipeline tunables / partition counts)
        self._conf = conf
        self._specs: Optional[List[List[int]]] = None

    @property
    def output(self):
        return self.children[0].output

    def node_desc(self) -> str:
        n = len(self._specs) if self._specs is not None else "?"
        return f"TpuShuffleReader[coalesced, n={n}]"

    def _ensure_specs(self, ctx: TaskContext) -> List[List[int]]:
        if self._specs is None:
            sizes = self.children[0].partition_sizes(ctx)
            specs: List[List[int]] = []
            cur: List[int] = []
            cur_bytes = 0
            for r, sz in enumerate(sizes):
                if cur and cur_bytes + sz > self.advisory_bytes:
                    specs.append(cur)
                    cur, cur_bytes = [], 0
                cur.append(r)
                cur_bytes += sz
            if cur:
                specs.append(cur)
            self._specs = specs or [[0]]
        return self._specs

    def num_partitions(self) -> int:
        from ..execs.base import TaskContext
        from ..config import default_conf
        # sizes require materialization; the planner threads its conf
        # snapshot through the constructor (default_conf only covers readers
        # built outside the override engine, e.g. hand-assembled test plans)
        ctx = TaskContext(0, self._conf or default_conf())
        try:
            return len(self._ensure_specs(ctx))
        finally:
            ctx.complete()

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        specs = self._ensure_specs(ctx)
        yield from _read_reduce_group(self.children[0], specs[idx], ctx,
                                      [a.name for a in self.output])


def _pipelined_upload(exch, tables_it, names, ctx: TaskContext,
                      account_output: bool = False
                      ) -> Iterator[TpuColumnarBatch]:
    """Shared concat+upload tail for the exchange reduce read and the AQE
    grouped read: host-coalesce fetched Arrow tables to the batch targets
    (when enabled, reference GpuShuffleCoalesceExec), then upload on a
    prefetch worker so downstream device compute overlaps the upload, with
    waits attributed to the exchange's deserializationTime under a ledger
    scope. `account_output` feeds the exchange's output metrics — only for
    callers that bypass exch.execute_partition (whose wrapper otherwise
    accounts them; double-counting if both ran)."""
    from ..execs.coalesce import (coalesce_arrow_stream, coalesce_enabled,
                                  coalesce_targets)
    from ..profiling import sync_scope
    from ..utils.pipeline import prefetch_iterator
    deser = exch.metrics["deserializationTime"]
    out_rows = exch.metrics["numOutputRows"]
    out_batches = exch.metrics["numOutputBatches"]

    def _upload() -> Iterator[TpuColumnarBatch]:
        # deserializationTime covers producing a device-ready batch: waiting
        # on the pool's read+deserialize AND the upload (the actual decode
        # runs on reader threads, so only its non-overlapped wait is
        # attributable to this task). sync_scope: this generator's frames
        # run on the prefetch worker thread (empty scope stack) — anchor
        # ledger attribution
        it = tables_it
        if coalesce_enabled(ctx.conf):
            it = coalesce_arrow_stream(it, *coalesce_targets(ctx.conf))
        # `exchange.fetch`: one lap a fetch + upload, flushed however the
        # reader leaves the generator
        laps = obs.PhaseLaps()
        try:
            while True:
                with deser.timed(), sync_scope(exch.node_name()), \
                        laps.lap("exchange.fetch"):
                    t = next(it, None)
                    b = (TpuColumnarBatch.from_arrow(t)
                         if t is not None and t.num_rows else None)
                if t is None:
                    return
                if b is not None:
                    if obs._ACTIVE:
                        # one reduce-side block fetched+uploaded (the row
                        # count stays out of the args: an event must never
                        # force a deferred device count — TL012)
                        obs.event("shuffle.read", cat="shuffle")
                    if account_output:
                        out_rows.add(b.num_rows)
                        out_batches.add(1)
                    yield b.rename(names)
        finally:
            laps.flush()

    yield from prefetch_iterator(_upload(), exch._prefetch_depth(ctx))


def _read_reduce_group(exch, reduce_ids, ctx: TaskContext,
                       names) -> Iterator:
    """Read a group of reduce partitions through an AQE reader. In
    MULTITHREADED mode with coalescing on, the group's fetched Arrow blocks
    concatenate HOST-side across reduce-partition boundaries up to the
    batch-size targets before the upload (reference GpuShuffleCoalesceExec
    under GpuCustomShuffleReaderExec) — grouping small partitions is only a
    win if they also merge into fewer uploads/dispatches."""
    from ..execs.coalesce import coalesce_enabled
    if coalesce_enabled(ctx.conf) \
            and isinstance(exch, TpuShuffleExchangeExec) \
            and exch._shuffle_mode(ctx) == "MULTITHREADED":
        exch._ensure_materialized(ctx)
        mgr = TpuShuffleManager.get(ctx.conf)

        def tables():
            for rid in reduce_ids:
                yield from exch._fetch_tables(rid, ctx, mgr)

        # account_output: this path bypasses exch.execute_partition, whose
        # wrapper would otherwise feed the exchange's output metrics
        yield from _pipelined_upload(exch, tables(), names, ctx,
                                     account_output=True)
        return
    for reduce_id in reduce_ids:
        yield from exch.execute_partition(reduce_id, ctx)


def plan_cpu_exchange(plan, conf):
    from ..plan.planner import plan_physical
    child = plan_physical(plan.children[0], conf)
    part = plan.partitioning
    n = plan.num_partitions
    if part == "coalesce" and n >= child.num_partitions():
        return child  # coalesce to >= current count: no-op
    return CpuShuffleExchangeExec(child, "hash" if plan.keys else part,
                                  plan.keys, n)
