"""Mesh context + the collective (ICI) data plane for the exec-layer shuffle.

This is the framework integration of the UCX-mode shuffle (SURVEY.md §2.7:
shuffle-plugin/ UCXShuffleTransport.scala, RapidsShuffleInternalManagerBase.
scala:238): when a jax.sharding.Mesh is configured, `TpuShuffleExchangeExec`
routes its exchange through ONE jitted `shard_map` program whose
`lax.all_to_all` moves every column's rows between shards over the
interconnect — XLA schedules the ICI transfers that the reference hand-codes
as UCX transactions. The exchange is collective: all map inputs are sharded
row-wise over the mesh, re-bucketed by murmur3(key) % n_shards on-device
(hash partitioning) or funneled to shard 0 (single partitioning — the
partial→final aggregation / global-limit merge funnel), and each shard
receives exactly its reduce partition.

Static-shape strategy (XLA cannot size buffers data-dependently):
  1. partition ids are computed per shard-group batch with the normal
     expression path (shuffle/partitioner.py);
  2. ONE audited host sync reads the per-(shard, dest) counts and picks a
     bucketed slot capacity — the analogue of the reference sizing
     contiguousSplit slices before handing them to the transport. The SAME
     counts are the exchange's device-side partition statistics: exact
     per-reduce AND per-source row/byte sizes are known at exchange time,
     so AQE planning (`partition_sizes`, skew `map_block_sizes`) never
     re-fetches blocks;
  3. the jitted exchange scatters rows into [n_shards, slot_cap] send
     buffers, `all_to_all`s them, and — because the per-source counts are
     host-known — FUSES the post-collective compact into the same program:
     received slot (src s, pos p) scatters straight to its final row
     `bases[s] + p` (`bases` = exclusive cumsum of this shard's receive
     counts), reproducing bit-for-bit the (src asc, stable) order the old
     host-side compact produced, with zero host round-trips. Reduce block
     `r` leaves the program as chip `r`'s shard of a sharded output and
     stays there: nothing is gathered, and the partition task that reads
     it runs on that chip (`run_chip_tasks`).

Placement (docs/distributed.md "Placement and the task model"): in a mesh
session partition `p` belongs to chip `p % n` — its cached batches live
there (`DataFrame.device_cache`), its task runs there on the chip's worker
thread under `jax.default_device`, and the collective's global inputs are
assembled from the per-chip arrays where they already are
(`jax.make_array_from_single_device_arrays`: no concatenation on chip 0,
no re-sharding). Only the fresh destination ids are DONATED to the
exchange program: the column arrays may be a cached relation's own
buffers. Constant pad pieces (empty-shard columns, destination fills) come
from a small process-wide staging pool keyed by (kind, capacity, dtype,
fill, chip) — `mesh.staging_reuse_hits` counts the copies that no longer
happen.

Exchange/compute overlap (`spark.rapids.tpu.exchange.overlap.*`, default
OFF — correctness first): the payload splits into K segments along the
slot axis; segment k+1's all_to_all is in flight while the fused compact
consumes segment k into donated accumulators. Every segment scatters to
the SAME final row positions the unsegmented program uses, so results are
bit-identical at any K. Chaos `mesh.link` fires per segment
(`detail="s<id>seg<k>"`); a mid-segment fault abandons the donated
accumulators and the caller's with_device_retry re-stages from the still-
open spillables, so no donated buffer is ever applied twice.

Compiled programs are cached by (mesh, capacity, slot_cap, column dtypes)
so steady-state queries reuse one executable. Every exchange lands in the
process-wide dispatch accounting as ONE kind "mesh_collective" launch
(`opjit.record_external_dispatch`) — O(exchanges) regardless of overlap;
segment launches count separately under "mesh_overlap_segment" — and,
when the query tracer is armed, inside a `mesh.exchange` span carrying the
per-chip send-row breakdown and the stage/launch/wait timing split
(docs/observability.md).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import (Callable, Dict, Iterable, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..columnar.batch import TpuColumnarBatch, _repad
from ..columnar.vector import (TpuColumnVector, audited_device_get,
                               bucket_capacity, row_mask)
from ..config import (EXCHANGE_OVERLAP_ENABLED, EXCHANGE_OVERLAP_MIN_ROWS,
                      EXCHANGE_OVERLAP_SEGMENTS, MESH_ENABLED, MESH_SIZE,
                      SHUFFLE_MODE)
from ..obs import tracer as obs

_AXIS = "data"


class MeshContext:
    """Process-wide mesh handle (the TPU analogue of the executor's device
    topology discovered via the shuffle heartbeat, Plugin.scala:436-447)."""

    _lock = threading.Lock()
    _meshes: Dict[int, Mesh] = {}

    @classmethod
    def get(cls, conf, n: Optional[int] = None) -> Optional[Mesh]:
        """Mesh of exactly `n` devices (default: the configured/maximum
        size); None when disabled or the topology is too small."""
        if not conf.get(MESH_ENABLED):
            return None
        limit = conf.get(MESH_SIZE)
        devs = jax.devices()
        avail = min(limit, len(devs)) if limit else len(devs)
        n = n if n is not None else avail
        if n > avail or n < 2:
            return None
        with cls._lock:
            if n not in cls._meshes:
                cls._meshes[n] = Mesh(np.array(devs[:n]), (_AXIS,))
            return cls._meshes[n]

    @classmethod
    def reset_for_tests(cls) -> None:
        with cls._lock:
            cls._meshes = {}
        reset_staging_pool()


def mesh_session_active(conf) -> Optional[Mesh]:
    """The mesh this session's PLANNER should target, or None. A mesh
    session is active when the mesh is enabled, the shuffle mode is ICI
    (the collective commits device-resident blocks to the ICI catalog) and
    the topology offers >= 2 devices — the condition under which
    plan/overrides.py selects the collective exchange and aligns hash
    partition counts to the mesh."""
    if str(conf.get(SHUFFLE_MODE)).upper() != "ICI":
        return None
    return MeshContext.get(conf)


def session_chips(conf) -> Optional[Tuple]:
    """The chips of this session's mesh in mesh order (chip `r` owns
    partitions `p % n == r`), or None outside a mesh session."""
    mesh = mesh_session_active(conf)
    return None if mesh is None else tuple(mesh.devices.flat)


class _Placement(threading.local):
    """The chip this thread's work is placed on (None: not placed)."""
    chip = None


_placement = _Placement()


def current_chip():
    """The chip the calling thread computes on inside `on_chip`, else None."""
    return _placement.chip


@contextlib.contextmanager
def on_chip(device):
    """The calling thread's work is placed on `device` for the scope:
    fresh arrays and programs over uncommitted operands land there
    (`jax.default_device`, thread-local) and `current_chip()` says so — a
    pull of another chip's partition inside the scope is computed there and
    moved here (`TpuExec._placed`). A no-op for None."""
    if device is None:
        yield
        return
    prev = _placement.chip
    _placement.chip = device
    try:
        with jax.default_device(device):
            yield
    finally:
        _placement.chip = prev


def chip_iter(it, device):
    """Drive iterator `it` with every `next` under `on_chip(device)`: the
    placement never leaks into the consumer between yields."""
    while True:
        with on_chip(device):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


def replicated_bytes(batch: TpuColumnarBatch) -> int:
    """Bytes of `batch` held on more than one chip: each fixed-width or
    offsets buffer's size times the chips it is on beyond the first
    (sharding metadata, no sync). 0 for a batch on one chip alone."""
    total = 0
    for c in batch.columns:
        for buf in (c.data, c.validity, c.offsets):
            if isinstance(buf, jax.Array):
                total += int(buf.nbytes) * (len(buf.devices()) - 1)
    return total


def run_chip_tasks(conf, ids: Sequence[int], task: Callable[[int], object],
                   ) -> Dict[int, object]:
    """`{i: task(i)}` for the partitions `ids`. In a mesh session partition
    `i`'s task runs on the worker thread of chip `i % n` under
    `on_chip(chip)` — one task slot a chip (the reference's executor
    model), the chips at once; a chip's partitions run in id order.
    Outside one, or when a single chip has all the work, the tasks run in
    order on the calling thread.

    Why threads and not one SPMD program per stage: a partition's work is
    driven by host decisions on its own data (a join's pair count sizes its
    output, a batch's capacity picks its program), so four partitions do
    not share shapes, and each chip's programs are the one-chip session's
    own, launched on operands committed to that chip.

    A worker binds the caller's query (cancellation, the phase table, HBM
    charges), its tracer span and its sync-ledger scope. The first failure
    stops the chips' remaining partitions and is raised once every worker
    has ended."""
    ids = list(ids)
    chips = session_chips(conf)
    if chips is None:
        return {i: task(i) for i in ids}
    n = len(chips)
    by_chip: Dict[int, List[int]] = {}
    for i in ids:
        by_chip.setdefault(i % n, []).append(i)
    if len(by_chip) <= 1:
        out = {}
        for i in ids:
            with on_chip(chips[i % n]):
                out[i] = task(i)
        return out
    from .. import profiling
    from ..serving import query_context as qlc
    qctx = qlc.current()
    parent = obs.current_span()
    scope = profiling.current_sync_scope()
    results: Dict[int, object] = {}
    errors: List[BaseException] = []
    stop = threading.Event()

    def work(r: int) -> None:
        try:
            with qlc.bind(qctx), obs.inherit(parent), \
                    profiling.sync_scope(scope), on_chip(chips[r]):
                for i in by_chip[r]:
                    if stop.is_set():
                        return
                    results[i] = task(i)
        except BaseException as e:  # noqa: BLE001 — re-raised by the caller
            stop.set()
            errors.append(e)

    threads = [threading.Thread(target=work, args=(r,), name=f"chip-{r}")
               for r in sorted(by_chip)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return results


def collective_payload(output, conf) -> Optional[str]:
    """Payload classification for the collective data plane (shared by the
    planner's exchange selection and the runtime eligibility check):

    * ``"fixed"`` — every column has a fixed-width device layout; the
      all_to_all carries the raw buffers;
    * ``"dict"`` — the variable-width columns are all strings/binary
      (offsets+bytes device layout): they ride as int32 dictionary codes
      plus one broadcast dictionary per exchange
      (``spark.rapids.tpu.exchange.dictionaryEncode.enabled``), the TPU
      analogue of the reference's compressed shuffle batches;
    * ``None`` — nested or host-only payloads: per-map path.
    """
    from ..columnar.vector import device_layout_ok
    from ..config import EXCHANGE_DICT_ENCODE_ENABLED
    from ..types import BinaryType, StringType, is_fixed_width
    has_var = False
    for a in output:
        if is_fixed_width(a.dtype) and device_layout_ok(a.dtype):
            continue
        if isinstance(a.dtype, (StringType, BinaryType)):
            has_var = True
            continue
        return None
    if not has_var:
        return "fixed"
    return "dict" if conf.get(EXCHANGE_DICT_ENCODE_ENABLED) else None


# compiled exchange cache: (mesh, cap, slot_cap, col sig) -> jitted fn.
# Guarded: collective exchanges can materialize from concurrent query
# threads (TL010 — same discipline as the opjit executable cache).
_CACHE_LOCK = threading.Lock()
_EXCHANGE_CACHE: Dict[Tuple, object] = {}

# staging pool: constant pad pieces (empty-shard columns, destination
# fills) keyed by (kind, capacity, dtype, fill). jax.Arrays are immutable
# and the pieces feed jnp.concatenate (which copies into the donated
# global input), so pooling them is safe even though the concatenated
# staging buffer itself is donated to the exchange program.
_POOL_LOCK = threading.Lock()
_STAGING_POOL: Dict[Tuple, jax.Array] = {}
_STAGING_POOL_MAX = 256


def _pooled_fill(kind: str, cap: int, dtype, fill,
                 device=None) -> Tuple[jax.Array, int]:
    """A pooled constant array (cap,) of `fill` on `device` (committed
    there; None: the default device); returns (array, hit)."""
    key = (kind, int(cap), str(jnp.dtype(dtype)), fill, device)
    with _POOL_LOCK:
        arr = _STAGING_POOL.get(key)
    if arr is not None:
        return arr, 1
    arr = jnp.full((cap,), fill, dtype)
    if device is not None:
        arr = jax.device_put(arr, device)
    with _POOL_LOCK:
        if len(_STAGING_POOL) < _STAGING_POOL_MAX:
            _STAGING_POOL[key] = arr
    return arr, 0


def reset_staging_pool() -> None:
    with _POOL_LOCK:
        _STAGING_POOL.clear()


def _donate(positions: Iterable[int]) -> Tuple[int, ...]:
    """Buffer-donation argnums for collective inputs this module made
    itself (the destination ids, the overlap path's send buffers and
    accumulators) — never the column arrays, which since the global inputs
    are assembled in place may be a cached relation's own buffers. The CPU
    backend does not implement donation (it warns and copies) — same gate
    as execs/opjit._donate. Donated staging is never retried in place: a
    faulted exchange re-stages from the spillables (with_device_retry
    around run_collective), so a donated buffer is consumed at most
    once."""
    return tuple(positions) if jax.default_backend() != "cpu" else ()


# collective-launch statistics (parallel/sharded.py and the O(exchanges)
# assertion read these next to opjit calls_by_kind["mesh_collective"]).
_STATS_LOCK = threading.Lock()
_STATS = {"launches": 0, "rows_sent": 0, "stage_ns": 0, "launch_ns": 0,
          "wait_ns": 0, "compact_ns": 0,
          # dictionary-encoded string exchanges (parallel/sharded.py's
          # string_collectives / dict_encode_ms keys)
          "dict_exchanges": 0, "dict_encode_ns": 0,
          # staging-pool reuse + segmented-overlap accounting (fused
          # dataplane keys: docs/distributed.md "Fused compact & overlap")
          "staging_reuse_hits": 0, "overlap_segments": 0}


def collective_stats() -> Dict[str, int]:
    with _STATS_LOCK:
        return dict(_STATS)


def reset_collective_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


def record_dict_encode(ns: int) -> None:
    """One exchange's map-side dictionary-encode pass completed (every
    value is host-known: a perf_counter wall — zero device syncs)."""
    with _STATS_LOCK:
        _STATS["dict_exchanges"] += 1
        _STATS["dict_encode_ns"] += ns


def _record_launch(rows: int, stage_ns: int, launch_ns: int,
                   wait_ns: int, compact_ns: int,
                   staging_reuse_hits: int = 0,
                   overlap_segments: int = 0) -> None:
    with _STATS_LOCK:
        _STATS["launches"] += 1
        _STATS["rows_sent"] += rows
        _STATS["stage_ns"] += stage_ns
        _STATS["launch_ns"] += launch_ns
        _STATS["wait_ns"] += wait_ns
        _STATS["compact_ns"] += compact_ns
        _STATS["staging_reuse_hits"] += staging_reuse_hits
        _STATS["overlap_segments"] += overlap_segments
    # always-on registry (docs/observability.md): the collective's blocking
    # wait is the fabric's user-visible latency — histogram it per launch
    # (rare: one per exchange) so a serving dashboard sees the tail;
    # the running totals above fold into metrics_snapshot() as-is
    from ..obs import metrics as _metrics
    _metrics.histogram_observe("mesh.collective_wait_ms", wait_ns / 1e6)
    _metrics.counter_inc("mesh.staging_reuse_hits", staging_reuse_hits)


class MeshExchangeResult(NamedTuple):
    """One collective exchange's outputs + its device-side statistics."""
    batches: List[TpuColumnarBatch]  # one compacted batch per reduce part
    rows: List[int]                  # exact received rows per reduce part
    bytes: List[int]                 # device bytes per reduce part
    profile: Optional[Dict] = None   # obs/mesh_profile.py record
    #: per reduce partition: rows contributed by each SOURCE shard (the
    #: sizing counts' column) — the fused block's row order is (source
    #: asc, stable), so a contiguous source range is a contiguous row
    #: range: AQE skew splitting slices on these (map_block_sizes)
    src_rows: Optional[List[List[int]]] = None
    row_bytes: int = 0               # device bytes per row (fixed layout)
    #: rows that changed chip: the collective's off-diagonal counts, plus
    #: rows of an input piece that was not on its chip when staged
    rows_moved: int = 0
    #: bytes of output blocks held on more than one chip (0: block r is on
    #: chip r alone)
    replicated_bytes: int = 0


def _build_exchange(mesh: Mesh, n_dev: int, slot_cap: int,
                    sig: Tuple[Tuple[str, bool], ...]):
    """ONE jitted program: shard_map all_to_all moving `len(sig)` columns +
    validity AND the fused post-collective compact — received slot (src s,
    pos p) scatters to final row `bases[s] + p` under the host-known
    per-source counts, so the outputs need no host-side compact at all.
    Returns one array a lane, sharded over the mesh: chip r's shard is
    reduce block r's lane and stays on chip r (no all-gather).
    `sig` is ((dtype_str, has_validity), ...)."""
    key = (mesh, n_dev, slot_cap, sig)
    with _CACHE_LOCK:
        fn = _EXCHANGE_CACHE.get(key)
    if fn is not None:
        return fn

    n_cols = len(sig)
    local = n_dev * slot_cap

    def exchange(dest, counts, *flat):
        # per-shard local views: dest [cap], counts [n_dev] (rows each
        # SOURCE shard sends to this shard), columns/validities [cap]
        cap = dest.shape[0]
        order = jnp.argsort(dest, stable=True)
        sorted_dest = jnp.take(dest, order)
        idx = jnp.arange(cap, dtype=jnp.int32)
        one = jnp.ones((cap,), jnp.int32)
        run_start = jnp.zeros((n_dev + 2,), jnp.int32).at[
            sorted_dest + 1].add(one, mode="drop")
        starts = jnp.cumsum(run_start)[:-1]
        pos_in_bucket = idx - jnp.take(starts, sorted_dest)
        keep = (sorted_dest < n_dev) & (pos_in_bucket < slot_cap)
        send_slot = jnp.where(keep, sorted_dest * slot_cap + pos_in_bucket,
                              local)
        # fused compact: the receive side's slot (s, p) is occupied iff
        # p < counts[s]; its final row is bases[s] + p — identical to the
        # (src asc, stable in-bucket) order the host compact produced
        slot_src = jnp.arange(local, dtype=jnp.int32) // slot_cap
        slot_pos = jnp.arange(local, dtype=jnp.int32) % slot_cap
        bases = jnp.concatenate([
            jnp.zeros((1,), jnp.int32),
            jnp.cumsum(counts)[:-1].astype(jnp.int32)])
        occupied = slot_pos < jnp.take(counts, slot_src)
        out_idx = jnp.where(occupied,
                            jnp.take(bases, slot_src) + slot_pos, local)

        def a2a(x):
            x = x.reshape(n_dev, slot_cap)
            return jax.lax.all_to_all(x, _AXIS, split_axis=0, concat_axis=0,
                                      tiled=False).reshape(-1)

        def move(x, fill, dt):
            buf = jnp.full((local,), fill, dt).at[send_slot].set(
                jnp.take(x, order), mode="drop")
            recv = a2a(buf)
            return jnp.full((local,), fill, dt).at[out_idx].set(
                recv, mode="drop")

        outs = []
        datas = flat[:n_cols]
        valids = flat[n_cols:]
        for (dt, has_v), d, v in zip(sig, datas, valids):
            outs.append(move(d, 0, d.dtype))
            if has_v:
                outs.append(move(v, False, jnp.bool_))
        return tuple(outs)

    spec = P(_AXIS)
    n_valid = sum(1 for _, has_v in sig if has_v)
    n_lanes = n_cols + n_valid
    n_flat = 2 * n_cols
    sm = jax.shard_map(exchange, mesh=mesh,
                   in_specs=tuple([spec] * (2 + n_flat)),
                   out_specs=tuple([spec] * n_lanes), check_vma=False)

    def mesh_exchange(dest, counts, *flat):
        # the device program's name in a trace: jit_mesh_exchange
        return sm(dest, counts, *flat)

    # no out_shardings: lane `l` leaves as ONE array sharded over the mesh,
    # whose shard on chip r IS reduce block r (`_chip_blocks`)
    fn = jax.jit(mesh_exchange, donate_argnums=_donate((0,)))
    with _CACHE_LOCK:
        _EXCHANGE_CACHE[key] = fn
    return fn


def _chip_blocks(mesh: Mesh, arr: jax.Array) -> List[jax.Array]:
    """The per-chip shards of a lane sharded over the mesh, in mesh order:
    single-device arrays, each on its own chip, nothing copied."""
    by_dev = {sh.device: sh.data for sh in arr.addressable_shards}
    return [by_dev[d] for d in mesh.devices.flat]


def _global(mesh: Mesh, pieces: List[jax.Array]) -> jax.Array:
    """One array sharded over the mesh from per-chip pieces of one shape,
    piece r already on chip r: assembled where the pieces are."""
    n = pieces[0].shape[0]
    return jax.make_array_from_single_device_arrays(
        (n * len(pieces),), NamedSharding(mesh, P(_AXIS)), pieces)


def _build_overlap(mesh: Mesh, n_dev: int, slot_cap: int, k_seg: int,
                   sig: Tuple[Tuple[str, bool], ...]):
    """The segmented exchange's cached programs (overlap mode):

    * ``prep``   — ONE dispatch computing every lane's send-layout buffer
                   (slot pitch padded to ``k_seg * seg_cap``);
    * ``a2a``    — per-segment all_to_all of all lanes; the segment index
                   is a TRACED scalar, so all K segments share one
                   executable;
    * ``comp``   — per-segment fused compact scattering the received
                   segment into DONATED accumulators at the same final
                   rows the unsegmented program uses (bit-identical at
                   any K);
    The accumulators leave sharded over the mesh, as `_build_exchange`'s
    lanes do. Returns (prep, a2a, comp, seg_cap)."""
    key = (mesh, n_dev, slot_cap, k_seg, sig, "overlap")
    with _CACHE_LOCK:
        progs = _EXCHANGE_CACHE.get(key)
    if progs is not None:
        return progs

    n_cols = len(sig)
    seg_cap = -(-slot_cap // k_seg)
    slot_capP = k_seg * seg_cap
    local = n_dev * slot_cap
    localP = n_dev * slot_capP
    n_valid = sum(1 for _, has_v in sig if has_v)
    n_lanes = n_cols + n_valid
    n_flat = 2 * n_cols

    def prepare(dest, *flat):
        cap = dest.shape[0]
        order = jnp.argsort(dest, stable=True)
        sorted_dest = jnp.take(dest, order)
        idx = jnp.arange(cap, dtype=jnp.int32)
        one = jnp.ones((cap,), jnp.int32)
        run_start = jnp.zeros((n_dev + 2,), jnp.int32).at[
            sorted_dest + 1].add(one, mode="drop")
        starts = jnp.cumsum(run_start)[:-1]
        pos_in_bucket = idx - jnp.take(starts, sorted_dest)
        keep = (sorted_dest < n_dev) & (pos_in_bucket < slot_cap)
        send_slot = jnp.where(keep,
                              sorted_dest * slot_capP + pos_in_bucket,
                              localP)
        outs = []
        datas = flat[:n_cols]
        valids = flat[n_cols:]
        for (dt, has_v), d, v in zip(sig, datas, valids):
            outs.append(jnp.full((localP,), 0, d.dtype).at[send_slot].set(
                jnp.take(d, order), mode="drop"))
            if has_v:
                outs.append(jnp.full((localP,), False, jnp.bool_).at[
                    send_slot].set(jnp.take(v, order), mode="drop"))
        return tuple(outs)

    def seg_a2a(k, *sends):
        outs = []
        for s in sends:
            x = s.reshape(n_dev, slot_capP)
            seg = jax.lax.dynamic_slice(
                x, (jnp.int32(0), (k * jnp.int32(seg_cap)).astype(jnp.int32)),
                (n_dev, seg_cap))
            outs.append(jax.lax.all_to_all(
                seg, _AXIS, split_axis=0, concat_axis=0,
                tiled=False).reshape(-1))
        return tuple(outs)

    def seg_compact(k, counts, *accseg):
        accs = accseg[:n_lanes]
        segs = accseg[n_lanes:]
        nloc = n_dev * seg_cap
        seg_src = jnp.arange(nloc, dtype=jnp.int32) // seg_cap
        seg_pos = jnp.arange(nloc, dtype=jnp.int32) % seg_cap
        p = k * seg_cap + seg_pos
        bases = jnp.concatenate([
            jnp.zeros((1,), jnp.int32),
            jnp.cumsum(counts)[:-1].astype(jnp.int32)])
        occupied = p < jnp.take(counts, seg_src)
        out_idx = jnp.where(occupied, jnp.take(bases, seg_src) + p, local)
        return tuple(acc.at[out_idx].set(seg, mode="drop")
                     for acc, seg in zip(accs, segs))

    spec = P(_AXIS)
    prep = jax.jit(
        jax.shard_map(prepare, mesh=mesh,
                  in_specs=tuple([spec] * (1 + n_flat)),
                  out_specs=tuple([spec] * n_lanes), check_vma=False),
        donate_argnums=_donate((0,)))
    a2a = jax.jit(
        jax.shard_map(seg_a2a, mesh=mesh,
                  in_specs=(P(),) + tuple([spec] * n_lanes),
                  out_specs=tuple([spec] * n_lanes), check_vma=False))
    comp = jax.jit(
        jax.shard_map(seg_compact, mesh=mesh,
                  in_specs=(P(), spec) + tuple([spec] * (2 * n_lanes)),
                  out_specs=tuple([spec] * n_lanes), check_vma=False),
        donate_argnums=_donate(range(2, 2 + 2 * n_lanes)))
    progs = (prep, a2a, comp, seg_cap)
    with _CACHE_LOCK:
        _EXCHANGE_CACHE[key] = progs
    return progs


def _overlap_segments(conf, slot_cap: int) -> int:
    """Segment count for this exchange, or 0 (unsegmented). Correctness-
    first default: overlap only when explicitly enabled AND the slot
    capacity clears the minimum (below it, per-segment launch overhead
    dominates whatever the fabric could hide)."""
    if conf is None or not conf.get(EXCHANGE_OVERLAP_ENABLED):
        return 0
    k = int(conf.get(EXCHANGE_OVERLAP_SEGMENTS))
    if k <= 1 or slot_cap < max(k, int(conf.get(EXCHANGE_OVERLAP_MIN_ROWS))):
        return 0
    return k


def _fixed_row_bytes(ref: TpuColumnarBatch, has_valid: List[bool]) -> int:
    """Device bytes per row of a fixed-width batch (carrier itemsize +
    1 byte per validity lane) — the row→byte scale for the device-side
    partition statistics."""
    total = 0
    for i, c in enumerate(ref.columns):
        total += int(np.dtype(c.data.dtype).itemsize)
        if has_valid[i]:
            total += 1
    return total


def mesh_hash_exchange(mesh: Mesh,
                       group_batches: List[Optional[TpuColumnarBatch]],
                       pids_list: List[Optional[jnp.ndarray]],
                       names: Sequence[str],
                       shuffle_id: int = -1,
                       partitioning: str = "hash",
                       conf=None) -> MeshExchangeResult:
    """Collective hash exchange: `group_batches[d]` is the (possibly empty)
    concatenated map input assigned to shard d, `pids_list[d]` its
    destination-partition ids. Returns one compacted device batch per reduce
    partition (= per shard) — compaction happens INSIDE the collective
    program (fused compact) under the host-known sizing counts — plus the
    exact per-reduce row/byte counts AND the per-source row split (the
    device-side statistics AQE plans coalescing and skew slicing against —
    no block fetch, no extra sync) and the exchange's efficiency profile
    (obs/mesh_profile.py: phase walls + per-chip skew, all from host
    values this function already holds). `conf` (optional — direct kernel
    callers may omit it) gates the segmented overlap path."""
    from ..chaos import inject
    from ..execs import opjit
    from ..obs import mesh_profile as mprof
    from ..serving.query_context import checkpoint as _cancel_checkpoint
    # collective-launch cancellation boundary: last stop before the
    # staging sync + fabric program — a cancelled/timed-out query never
    # launches the collective (docs/robustness.md "Query lifecycle")
    _cancel_checkpoint(f"mesh.collective s{shuffle_id}")
    n_dev = mesh.devices.size
    assert len(group_batches) == n_dev
    chips = list(mesh.devices.flat)
    t_stage0 = time.perf_counter_ns()
    with obs.phase("mesh.stage"):
        ref = next(b for b in group_batches if b is not None)
        dtypes = [c.dtype for c in ref.columns]
        cap = bucket_capacity(max([b.capacity for b in group_batches
                                   if b is not None] + [1]))

        # per-(shard, dest) counts -> slot capacity AND the exchange's
        # partition statistics (ONE audited host sync for all shards' pid
        # arrays; a per-shard np.asarray loop would pay one round trip
        # each on high-latency links)
        live = [(d, b, p) for d, (b, p) in enumerate(zip(group_batches,
                                                         pids_list))
                if b is not None and b.num_rows]
        fetched = audited_device_get([p for _d, _b, p in live],
                                     "mesh_counts") if live else []
        max_count = 1
        counts_m = np.zeros((n_dev, n_dev), np.int64)
        for (shard, b, _p), pids_np in zip(live, fetched):
            counts = np.bincount(np.asarray(pids_np)[: b.num_rows],
                                 minlength=n_dev)
            max_count = max(max_count, int(counts.max()))
            counts_m[shard] += counts
        recv_rows = counts_m.sum(axis=0)
        send_rows = counts_m.sum(axis=1)
        slot_cap = bucket_capacity(max_count)
        overlap_k = _overlap_segments(conf, slot_cap)

        # the global [n_dev * cap] inputs, assembled from per-chip pieces where
        # they are: piece r is shard group r's array, padded on chip r.
        # Constant pad pieces (empty shards, destination fills) come from the
        # staging pool, a copy a chip.
        reuse_hits = 0
        restaged_rows = 0

        def pad(kind: str, dtype, fill, r: int):
            nonlocal reuse_hits
            arr, hit = _pooled_fill(kind, cap, dtype, fill, chips[r])
            reuse_hits += hit
            return arr

        def place(arr, r: int, rows: int = 0):
            """`arr` as chip r's piece: itself when it is there (the placed
            session's case), else moved — rows that changed chip."""
            nonlocal restaged_rows
            if arr.committed and arr.devices() == {chips[r]}:
                return arr
            restaged_rows += rows
            return jax.device_put(arr, chips[r])

        sig = []
        col_data: List[List[jnp.ndarray]] = []
        col_valid: List[List[jnp.ndarray]] = []
        has_valid = [any(b is not None and b.columns[i].validity is not None
                         for b in group_batches)
                     for i in range(len(dtypes))]
        for i, dt in enumerate(dtypes):
            carrier = ref.columns[i].data.dtype
            sig.append((str(carrier), has_valid[i]))
            datas, valids = [], []
            for r, b in enumerate(group_batches):
                if b is None:
                    datas.append(pad("zeros", carrier, 0, r))
                    valids.append(pad("mask", jnp.bool_, False, r))
                    continue
                with on_chip(chips[r]):
                    c = _repad(b.columns[i], cap)
                    datas.append(place(c.data, r, b.num_rows if i == 0 else 0))
                    valids.append(place(
                        c.validity if c.validity is not None
                        else row_mask(b.num_rows, cap), r))
            col_data.append(datas)
            col_valid.append(valids)
        dests = []
        for r, (b, pids) in enumerate(zip(group_batches, pids_list)):
            if b is None or not b.num_rows:
                dests.append(pad("dest", jnp.int32, n_dev, r))
                continue
            with on_chip(chips[r]):
                p = place(jnp.asarray(pids), r)[:cap].astype(jnp.int32)
                if p.shape[0] < cap:
                    p = jnp.concatenate(
                        [p, jnp.full((cap - p.shape[0],), n_dev, jnp.int32)])
                # a fresh array (it is donated): never the caller's pids
                dests.append(place(
                    jnp.where(row_mask(b.num_rows, cap), p, n_dev), r))

        dest_g = _global(mesh, dests)
        counts_g = jax.device_put(
            np.ascontiguousarray(counts_m.T.astype(np.int32)).reshape(-1),
            NamedSharding(mesh, P(_AXIS)))
        flat = [_global(mesh, col_data[i]) for i in range(len(dtypes))] + \
               [_global(mesh, col_valid[i]) for i in range(len(dtypes))]
        input_devices = len({sh.device for a in (dest_g, *flat)
                             for sh in a.addressable_shards})
        if overlap_k:
            ovl = _build_overlap(mesh, n_dev, slot_cap, overlap_k, tuple(sig))
        else:
            fn = _build_exchange(mesh, n_dev, slot_cap, tuple(sig))
    t_launch0 = time.perf_counter_ns()
    # pre-allocated profile seq: the span args and the consumer read's
    # flow events reference the profile before it is recorded
    seq = mprof.alloc_seq()
    # the span covers launch → wait → block construction (staging_ms rides
    # as an arg: the per-chip send counts it reports only exist after the
    # sizing sync). The watchdog arms around ONLY the fabric window —
    # inject + launch + wait: chaos `mesh.link` (a slow or flapping ICI
    # link) injects inside it, so a stalled transfer trips the watchdog
    # exactly like a hung chip would. Latency sleeps here; a transient
    # error propagates to the caller's with_device_retry, which re-runs
    # the whole (idempotent) staging — donated buffers are abandoned, not
    # reused.
    with obs.span(f"mesh.exchange s{shuffle_id}",
                  cat="shuffle.collective", shuffle=shuffle_id,
                  n_dev=n_dev, slot_cap=slot_cap, exchange_seq=seq,
                  staging_ms=round((t_launch0 - t_stage0) / 1e6, 3),
                  overlap_segments=overlap_k,
                  per_chip_rows=[int(x) for x in send_rows]), \
            obs.phase("mesh.collective"):
        # launched under the mesh's first chip as default device whichever
        # thread materializes the exchange: jit keys its programs by that
        # context too, and a chip's worker that got here first would
        # otherwise compile the collective anew
        with mprof.collective_watchdog(shuffle_id, n_dev) as wd, \
                jax.default_device(chips[0]):
            if overlap_k:
                outs = _launch_overlapped(ovl, overlap_k, mesh, n_dev,
                                          slot_cap, tuple(sig),
                                          dest_g, counts_g, flat,
                                          shuffle_id)
            else:
                inject("mesh.link", detail=f"s{shuffle_id}")
                outs = fn(dest_g, counts_g, *flat)
            t_wait0 = time.perf_counter_ns()
            # the collective is the stage boundary: waiting for it here is
            # the exchange's one blocking device sync (no data moves to
            # host — the ledger records the wait so per-query sync
            # accounting stays exact)
            from ..profiling import record_sync
            record_sync("collective_wait")
            with obs.phase("mesh.wait", cat="wait"):
                jax.block_until_ready(outs)
            t_end = time.perf_counter_ns()
        opjit.record_external_dispatch("mesh_collective")

        # reduce block r's lanes are chip r's shards of the program's
        # outputs, where the collective left them. The compact already
        # happened INSIDE the dispatch: rows [0, recv_rows[r]) are final,
        # the tail is padding (zeros, validity False) — no host compact,
        # no per-partition sync (the counts were host-known from the
        # sizing sync).
        row_bytes = _fixed_row_bytes(ref, has_valid)
        lanes = [_chip_blocks(mesh, arr) for arr in outs]
        results: List[TpuColumnarBatch] = []
        sizes: List[int] = []
        for r in range(n_dev):
            cols = []
            li = 0
            for i, dt in enumerate(dtypes):
                data, li = lanes[li][r], li + 1
                v = None
                if has_valid[i]:
                    v, li = lanes[li][r], li + 1
                cols.append(TpuColumnVector(dt, data, v, int(recv_rows[r])))
            results.append(TpuColumnarBatch(cols, int(recv_rows[r]),
                                            list(names)))
            sizes.append(int(recv_rows[r]) * row_bytes)
        replicated = sum(replicated_bytes(b) for b in results)
        t_compact_end = time.perf_counter_ns()
        profile = mprof.record_exchange(
            seq, shuffle_id, partitioning, n_dev,
            send_rows=[int(x) for x in send_rows],
            recv_rows=[int(x) for x in recv_rows], recv_bytes=sizes,
            stage_ns=t_launch0 - t_stage0, launch_ns=t_wait0 - t_launch0,
            wait_ns=t_end - t_wait0, compact_ns=t_compact_end - t_end,
            watchdog_fired=wd.fired, compact_fused=True,
            staging_reuse_hits=reuse_hits, overlap_segments=overlap_k,
            input_devices=input_devices)
        if profile is not None:
            # the full attribution record as an instant event: the Chrome
            # export derives the per-device tracks + producer→consumer
            # flows from it (all values already host-side)
            obs.event("mesh.profile", cat="mesh", exchange_seq=seq,
                      shuffle=shuffle_id, n_dev=n_dev,
                      phases_ms=dict(profile["phases_ms"]),
                      recv_rows=list(profile["recv_rows"]),
                      skew=dict(profile["skew"]))
    _record_launch(int(send_rows.sum()), t_launch0 - t_stage0,
                   t_wait0 - t_launch0, t_end - t_wait0,
                   t_compact_end - t_end, staging_reuse_hits=reuse_hits,
                   overlap_segments=overlap_k)
    src_rows = [[int(counts_m[s][r]) for s in range(n_dev)]
                for r in range(n_dev)]
    moved = int(counts_m.sum() - np.trace(counts_m)) + restaged_rows
    return MeshExchangeResult(results, [int(x) for x in recv_rows], sizes,
                              profile, src_rows, row_bytes, moved,
                              replicated)


def _launch_overlapped(progs, k_seg: int, mesh: Mesh, n_dev: int,
                       slot_cap: int, sig: Tuple[Tuple[str, bool], ...],
                       dest_g, counts_g, flat, shuffle_id: int) -> Tuple:
    """Double-buffered segmented exchange: segment k+1's all_to_all is
    dispatched BEFORE segment k's fused compact, so the fabric moves the
    next segment while the compact consumes the current one. Every segment
    scatters to the same final rows the unsegmented program uses —
    bit-identical at any K. Chaos `mesh.link` fires per segment
    (mid-segment soak): a raised fault abandons the donated accumulators
    mid-flight and the caller re-stages — nothing is applied twice."""
    from ..chaos import inject
    from ..execs import opjit
    prep, a2a, comp, _seg_cap = progs
    sends = prep(dest_g, *flat)
    # fresh (never pooled) accumulators, made sharded: comp donates them
    # each segment
    sharding = NamedSharding(mesh, P(_AXIS))
    accs = []
    for dt, has_v in sig:
        accs.append(jnp.zeros((n_dev * n_dev * slot_cap,), jnp.dtype(dt),
                              device=sharding))
        if has_v:
            accs.append(jnp.zeros((n_dev * n_dev * slot_cap,), jnp.bool_,
                                  device=sharding))
    accs = tuple(accs)
    seg = a2a(jnp.int32(0), *sends)
    for k in range(k_seg):
        # next segment's collective goes on the stream BEFORE this
        # segment's compact — the overlap window
        nxt = a2a(jnp.int32(k + 1), *sends) if k + 1 < k_seg else None
        opjit.record_external_dispatch("mesh_overlap_segment")
        inject("mesh.link", detail=f"s{shuffle_id}seg{k}")
        accs = comp(jnp.int32(k), counts_g, *accs, *seg)
        seg = nxt
    return accs


def mesh_single_exchange(mesh: Mesh,
                         group_batches: List[Optional[TpuColumnarBatch]],
                         names: Sequence[str],
                         shuffle_id: int = -1,
                         conf=None) -> MeshExchangeResult:
    """Collective SINGLE-partition funnel: every shard's rows move to shard
    0 in one all_to_all — the fabric path for partial→final aggregation and
    global limit/top-N merges (the reduce-scatter analogue: per-shard
    partial states were already reduced locally by the partial stage; the
    collective carries only the states). Returns mesh-size results where
    only reduce partition 0 is non-empty.

    Cost note: this reuses the hash-exchange program with all-zero
    destinations, so each shard still ships a full [n_dev, slot_cap] send
    buffer — slot groups 1..n-1 are padding the receivers discard,
    ~n_dev× the payload in fabric traffic. Acceptable for the state-merge
    funnels this serves (payloads are per-shard partial STATES, already
    reduced); a ragged gather / all_gather layout is the follow-up if a
    row-heavy single exchange ever rides it (ROADMAP item 2)."""
    pids = []
    for chip, b in zip(mesh.devices.flat, group_batches):
        with on_chip(chip):
            pids.append(None if b is None
                        else jnp.zeros((b.capacity,), jnp.int32))
    return mesh_hash_exchange(mesh, group_batches, pids, names,
                              shuffle_id=shuffle_id, partitioning="single",
                              conf=conf)
