"""Join execs: TPU equi-join (sorted build + bucket-directory probe) and CPU oracle.

Reference: GpuShuffledHashJoinExec + GpuHashJoin trait (execution/GpuHashJoin.scala:994,
gather-map iterators :259-985), GpuBroadcastNestedLoopJoinExec, GpuSortMergeJoinMeta
(SMJ replaced by hash join on the accelerator — same policy here).

TPU algorithm (XLA-static-shape friendly — cuDF's dynamic hash table does not
map to TPU):
  1. composite 64-bit mix of the equi-key columns on both sides (null keys never
     match: rows with any null key are excluded from candidates)
  2. ONCE A BUILD (_join_prepare_build): sort the build side by hash and count
     its valid rows into a directory of 2^k buckets by the hash's top k bits,
     k from the build's capacity alone (16 buckets a build lane, at most
     2^24); a prefix sum makes row t of the directory the sorted positions
     [start, end) of bucket t
  3. a probe lane reads its bucket's row: one gather of two 32-bit words
     gives its candidate range, every build row of its bucket — the
     equal-hash run and at most a sixteenth of a false candidate a lane
     beside it, contiguous in the same sorted order
  4. expand ranges into candidate pairs (one host sync for the pair count →
     bucketed output capacity, like the reference's gather-map sizing)
  5. verify true key equality per pair (bucket neighbours, hash collisions
     and nulls filtered: the verified pairs and their order are those of an
     equal-hash search)
  6. join-type specific assembly: inner gathers both sides; left/right/full add
     null-extended unmatched rows; semi/anti reduce to per-row match flags.
Residual (non-equi) conditions evaluate over the joined batch and recompute
match bookkeeping, mirroring the reference's conditional-join iterators.
"""

from __future__ import annotations

from typing import Iterator, List, NamedTuple, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..columnar.batch import TpuColumnarBatch, compact, concat_batches, gather
from ..columnar.vector import TpuColumnVector, bucket_capacity, row_mask
from ..expressions.base import (AttributeReference, Expression, to_column)
from ..obs import tracer as _obs
from ..types import StringType
from .aggregates import _sortable_bits
from .base import (CpuExec, PhysicalPlan, TaskContext, TpuExec, bind_all,
                   bind_references)

# splitmix-style 64-bit mix plane for the join's composite hash (u64 is exact
# on TPU — XLA carries it as u32 pairs); collisions only add candidates to
# the verified-equality pass, never wrong results
_HASH_MIX = np.uint64(0x9E3779B97F4A7C15)
_HASH_INIT = np.uint64(0x243F6A8885A308D3)
_HASH_SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)
# the bucket directory: 2^_DIR_BITS_A_LANE buckets a build lane, so at most a
# sixteenth of a false candidate a probe lane; at most 2^_DIR_MAX_BITS rows of
# two int32 (128 MB), beyond which buckets fill and the equality pass sees more
_DIR_BITS_A_LANE = 4
_DIR_MAX_BITS = 24


def _mix64(h, v):
    h = (h ^ v) * _HASH_MIX
    return h ^ (h >> jnp.uint64(29))


def encode_fixed_key(bits):
    """One side's fixed-width key as its cross-side-comparable int64 codes.
    The eager path and the opjit traced encodes all call exactly this code
    (they must agree bit-for-bit). BIGINT is exact on every backend and
    DOUBLE arrives as utils/hw.f64_order_bits' int64, so one width serves
    all fixed-width keys and a side encodes without seeing the other."""
    return bits.astype(jnp.int64)


def encode_fixed_key_pair(lb, rb, l_validity, r_validity,
                          l_enc: list, r_enc: list) -> None:
    """Append one fixed-width key pair's codes to the per-side encode lists."""
    l_enc.append((encode_fixed_key(lb), l_validity))
    r_enc.append((encode_fixed_key(rb), r_validity))


def _encode_sides(left_cols: List[TpuColumnVector], right_cols: List[TpuColumnVector],
                  l_rows: int, r_rows: int, l_cap: int, r_cap: int):
    """Comparable per-key codes for both sides; string keys dictionary-encode
    over the UNION of both sides so codes are cross-side comparable."""
    l_enc, r_enc = [], []
    for lc, rc in zip(left_cols, right_cols):
        if isinstance(lc.dtype, StringType):
            import pyarrow as pa
            import pyarrow.compute as pc
            la, ra = lc.to_arrow(), rc.to_arrow()
            combined = pa.concat_arrays([la.cast(pa.string()), ra.cast(pa.string())])
            enc = pc.dictionary_encode(combined)
            if isinstance(enc, pa.ChunkedArray):
                enc = enc.combine_chunks()
            codes = np.asarray(enc.indices.fill_null(-1).to_numpy(zero_copy_only=False))
            lbuf = np.zeros(l_cap, np.int64)
            lbuf[:l_rows] = codes[:l_rows]
            rbuf = np.zeros(r_cap, np.int64)
            rbuf[:r_rows] = codes[l_rows:l_rows + r_rows]
            l_enc.append((jnp.asarray(lbuf), lc.validity))
            r_enc.append((jnp.asarray(rbuf), rc.validity))
        else:
            encode_fixed_key_pair(_sortable_bits(lc), _sortable_bits(rc),
                                  lc.validity, rc.validity, l_enc, r_enc)
    return l_enc, r_enc


import functools as _functools

import jax as _jax


def _composite_hash(vals, valids, rows):
    """64-bit mix of a side's int64 key codes, and the lanes that may match
    (inside `rows`, every key valid)."""
    cap = vals[0].shape[0]
    h = jnp.full((cap,), _HASH_INIT, jnp.uint64)
    ok = jnp.arange(cap) < rows
    for v, vd in zip(vals, valids):
        h = _mix64(h, v.view(jnp.uint64))
        ok = ok & vd
    return h, ok


class PreparedBuild(NamedTuple):
    """What every probe of one build side needs (_join_prepare_build)."""
    b_vals: tuple    # the int64 key codes, for the equality pass
    b_ok: object     # lanes inside the build's rows with every key valid
    order: object    # int32: build lanes by hash, the invalid ones last
    directory: object  # int32[2^k, 2]: a bucket's sorted positions [start, end)


def dir_bits(b_cap: int) -> int:
    """k of a build's directory: fixed by its capacity alone, so that programs
    stay keyed by capacities."""
    return min(max(b_cap - 1, 1).bit_length() + _DIR_BITS_A_LANE,
               _DIR_MAX_BITS)


@_functools.partial(_jax.jit, static_argnames=("bits",))
def _join_prepare_build(b_vals, b_valids, b_rows, bits: int) -> PreparedBuild:
    """The build half of the matcher, ONCE A BUILD: composite hash, a stable
    sort by hash with the invalid rows last, and the bucket directory — a
    bincount of the valid rows by their hash's top `bits` bits and a prefix
    sum: the valid rows below bucket t, which is where its run of the
    sorted build starts, beside where it ends (callers pass
    bits=dir_bits(b_cap))."""
    bh, b_ok = _composite_hash(b_vals, b_valids, b_rows)
    # invalid rows sort to the end under the max sentinel; a valid hash that
    # IS the sentinel steps below it, so that a bucket's valid rows stay one
    # run of sorted positions
    sort_key = jnp.where(b_ok, jnp.minimum(bh, _HASH_SENTINEL - np.uint64(1)),
                         _HASH_SENTINEL)
    order = jnp.argsort(sort_key).astype(jnp.int32)
    n = 1 << bits
    bucket = (bh >> jnp.uint64(64 - bits)).astype(jnp.int32)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[
        jnp.where(b_ok, bucket + 1, n + 1)].add(1, mode="drop")
    starts = jnp.cumsum(sizes)
    # a row a bucket: one gather of a two-word row costs a probe 4.8 ms at
    # 2^20 lanes on a v5e where two gathers of a word cost 16.8 (PERF.md)
    return PreparedBuild(tuple(b_vals), b_ok, order,
                         jnp.stack([starts[:-1], starts[1:]], axis=1))


@_jax.jit
def _join_probe_ranges(directory, p_vals, p_valids, p_rows):
    """The probe half as ONE compiled program: composite hash, then a lane's
    candidates are its bucket's run of the sorted build — one gather of a
    directory row where a binary search took 2 x log2(b_cap) gathers of
    emulated 64-bit hashes. A bucket holds the lane's equal-hash run and, at
    a sixteenth of a row on average, neighbours that _join_emit_pairs'
    equality pass drops like any hash collision."""
    bits = directory.shape[0].bit_length() - 1
    ph, p_ok = _composite_hash(p_vals, p_valids, p_rows)
    bucket = (ph >> jnp.uint64(64 - bits)).astype(jnp.int32)
    run = jnp.take(directory, bucket, axis=0)
    lo = run[:, 0]
    counts = jnp.where(p_ok, run[:, 1] - lo, 0)
    return counts, lo, p_ok, jnp.sum(counts)


@_functools.partial(_jax.jit, static_argnames=("out_cap",))
def _join_emit_pairs(counts, lo, order, b_ok, p_ok, b_vals, p_vals, total,
                     out_cap: int):
    """Stage B: expand candidate ranges into verified pairs (one program;
    out_cap is the bucketed static output shape). Also returns the verified
    pair count as a DEVICE scalar so it never needs its own blocking read —
    it either rides the joined batch's boundary device_get (deferred
    compaction) or fuses into the single eager sync below."""
    p_cap = counts.shape[0]
    b_cap = order.shape[0]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    j = jnp.arange(out_cap)
    pi = jnp.clip(jnp.searchsorted(ends, j, side="right"),
                  0, p_cap - 1).astype(jnp.int32)
    off = j - jnp.take(starts, pi)
    bi_sorted = jnp.take(lo, pi) + off
    bi = jnp.take(order, jnp.clip(bi_sorted, 0, b_cap - 1)).astype(jnp.int32)
    ok = (j < total) & jnp.take(b_ok, bi) & jnp.take(p_ok, pi)
    for bv, pv in zip(b_vals, p_vals):
        ok = ok & (jnp.take(bv, bi) == jnp.take(pv, pi))
    return pi, bi, ok, jnp.sum(ok)


def _device_equi_join(build_enc, build_rows: int, probe_enc, probe_rows: int):
    """Core matcher. Returns (pair_probe_idx, pair_build_idx, verified_mask,
    total_candidates, out_capacity). Index arrays have out_capacity entries."""
    b_cap = build_enc[0][0].shape[0]
    p_cap = probe_enc[0][0].shape[0]

    def split(enc, cap):
        vals = [v for v, _ in enc]
        valids = [vd if vd is not None else jnp.ones((cap,), jnp.bool_)
                  for _, vd in enc]
        return vals, valids

    b_vals, b_valids = split(build_enc, b_cap)
    p_vals, p_valids = split(probe_enc, p_cap)
    _, b_ok, order, directory = _join_prepare_build(
        b_vals, b_valids, jnp.int32(build_rows), bits=dir_bits(b_cap))
    counts, lo, p_ok, total_dev = _join_probe_ranges(
        directory, p_vals, p_valids, jnp.int32(probe_rows))
    from ..columnar.vector import audited_sync_int
    # host sync: candidate-pair count (it sizes the static output shape, so
    # it cannot defer); the VERIFIED count below stays a device scalar
    total = audited_sync_int(total_dev, "pairs")
    out_cap = bucket_capacity(max(total, 1))
    pi, bi, ok, n_ok = _join_emit_pairs(counts, lo, order, b_ok, p_ok,
                                        b_vals, p_vals, jnp.int32(total),
                                        out_cap=out_cap)
    return pi, bi, ok, n_ok, total, out_cap


@_jax.jit
def _compact_pairs_device(pi, bi, ok, n):
    out_cap = pi.shape[0]
    pos = jnp.cumsum(ok) - 1
    idx = jnp.full((out_cap,), out_cap, jnp.int32)
    idx = idx.at[jnp.where(ok, pos, out_cap)].set(
        jnp.arange(out_cap, dtype=jnp.int32), mode="drop")
    take = jnp.clip(idx, 0, out_cap - 1)
    slot_ok = jnp.arange(out_cap) < n
    return jnp.take(pi, take), jnp.take(bi, take), slot_ok


def _compact_pairs(pi, bi, ok, n_ok, deferred: bool):
    """Stable-compact verified pairs (one compiled program). The kept count
    `n_ok` arrives as a device scalar from the emit program: deferred mode
    keeps it on device (the joined batch carries it to the boundary);
    otherwise it syncs here — fused with the candidate-count read into the
    join's single per-batch scalar accounting, instead of the historical
    second `int(jnp.sum(ok))` round trip."""
    n = n_ok if deferred else _audited_pairs_int(n_ok)
    a, b, slot_ok = _compact_pairs_device(pi, bi, ok, jnp.int32(n))
    return a, b, slot_ok, n


def _audited_pairs_int(n_dev) -> int:
    from ..columnar.vector import audited_sync_int
    return audited_sync_int(n_dev, "pairs")


def index_counters(metrics):
    """A join's (or the absorbing segment's) node metrics as query counters:
    builds prepared (sorted, bucket directory), probe batches that read a
    directory, and the candidate pairs they handed the equality pass —
    candidate_pairs / rows_out is the false-candidate factor,
    probes_indexed / builds_indexed the reuse of a build."""
    return [("join.builds_indexed", metrics["buildsIndexed"]),
            ("join.probes_indexed", metrics["probesIndexed"]),
            ("join.candidate_pairs", metrics["numPairs"])]


def _all_null_cols(attrs_or_cols, num_rows: int, capacity: int):
    out = []
    for c in attrs_or_cols:
        dt = c.dtype
        out.append(TpuColumnVector.from_scalar(None, dt, num_rows, capacity))
    return out


class TpuShuffledHashJoinExec(TpuExec):
    """Equi-join with optional residual condition (reference
    GpuShuffledHashJoinExec; build side = right, Spark's BuildRight default)."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan, join_type: str,
                 left_keys: Sequence[Expression], right_keys: Sequence[Expression],
                 condition: Optional[Expression],
                 output: List[AttributeReference], per_partition: bool = False):
        super().__init__([left, right])
        self.join_type = join_type
        self.left_keys = bind_all(list(left_keys), left.output)
        self.right_keys = bind_all(list(right_keys), right.output)
        self.condition = (bind_references(condition, left.output + right.output)
                          if condition is not None else None)
        self._output = output
        # per_partition: both sides are co-partitioned by the join keys (hash
        # exchanges below us) so each partition joins independently
        self.per_partition = per_partition

    @property
    def output(self):
        return self._output

    def num_partitions(self) -> int:
        return self.children[0].num_partitions() if self.per_partition else 1

    def node_desc(self) -> str:
        return f"TpuShuffledHashJoin[{self.join_type}]"

    def additional_metrics(self):
        return {"buildTime": "MODERATE", "joinTime": "MODERATE",
                "numPairs": "DEBUG", "subPartitionedJoins": "DEBUG",
                "buildsIndexed": "DEBUG", "probesIndexed": "DEBUG"}

    def query_counters(self):
        rows = [n.metrics["numOutputRows"]
                for n in (self.children[0], self.children[1], self)]
        return [("join.rows_left", rows[0]), ("join.rows_right", rows[1]),
                ("join.rows_out", rows[2]),
                ("join.subpartitioned", self.metrics["subPartitionedJoins"])
                ] + index_counters(self.metrics)

    def mesh_counters(self):
        # the probe side a chip's task took in, beside the node's own
        return super().mesh_counters() + self.children[0].chip_rows_counters()

    def _collect_side(self, child: PhysicalPlan, ctx, idx: int) -> Optional[TpuColumnarBatch]:
        """Pull one input whole and concatenate it: phase `join.collect`."""
        with _obs.phase("join.collect"):
            batches = []
            if self.per_partition:
                batches.extend(child.execute_partition(idx, ctx))
            else:
                for p in range(child.num_partitions()):
                    batches.extend(child.execute_partition(p, ctx))
            return concat_batches(batches) if batches else None

    def _collect_sides(self, ctx, idx: int):
        """Collect both join inputs. The two sides are independent subtrees,
        so with shuffle pipelining enabled the build side materializes on a
        worker thread while the probe side materializes here — its shuffle
        reads, uploads and device dispatches overlap instead of running
        back-to-back (device concurrency stays bounded by the semaphore)."""
        from ..config import SHUFFLE_PIPELINE_ENABLED
        if ctx.conf.get(SHUFFLE_PIPELINE_ENABLED):
            import threading
            res: dict = {}
            # per-query tracing routes by thread: the side-collector thread
            # inherits this query's tracer via the captured handoff token,
            # so its shuffle reads/uploads/dispatches stay in THIS query's
            # record (no-op when untraced)
            obs_parent = _obs.current_span()
            # the query lifecycle binding rides the same handoff: a
            # cancel/deadline trips the build-side collection too
            from ..serving import query_context as _qlc
            qctx = _qlc.current()

            def collect_right():
                try:
                    with _obs.inherit(obs_parent), _qlc.bind(qctx):
                        res["right"] = self._collect_side(self.children[1],
                                                          ctx, idx)
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    res["err"] = e

            t = threading.Thread(target=collect_right, name="join-side")
            t.start()
            try:
                left = self._collect_side(self.children[0], ctx, idx)
            finally:
                t.join()
            if "err" in res:
                raise res["err"]
            return left, res["right"]
        return (self._collect_side(self.children[0], ctx, idx),
                self._collect_side(self.children[1], ctx, idx))

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        left, right = self._collect_sides(ctx, idx)
        jt = self.join_type
        names = [a.name for a in self._output]
        l_empty = left is None or left.num_rows == 0
        r_empty = right is None or right.num_rows == 0
        if l_empty or r_empty:
            out = self._join_pair(left if not l_empty else None,
                                  right if not r_empty else None, names, ctx)
            if out is not None and out.num_rows:
                yield out
            return
        from ..config import BATCH_SIZE_ROWS
        max_rows = ctx.conf.get(BATCH_SIZE_ROWS)
        if self.left_keys and max(left.num_rows, right.num_rows) > max_rows:
            # sub-partitioning: both sides split by the same key hash, each
            # pair joined independently — keys land in exactly one pair so
            # outer/semi/anti semantics compose (reference
            # GpuSubPartitionHashJoin.scala)
            from ..shuffle.partitioner import hash_split_parts
            k = max(2, -(-max(left.num_rows, right.num_rows) // max_rows))
            self.metrics["subPartitionedJoins"].add(1)
            # seed 100 (not the exchange's 42): upstream co-partitioning fixes
            # h42 % N, so re-bucketing with the same seed would collapse into
            # few sub-partitions (GpuSubPartitionHashJoin.scala hashSeed=100).
            # Each side's encode+split pair runs as one cached executable
            # when the keys trace (opjit.partition_split_plan).
            l_parts = hash_split_parts(left, self.left_keys, k, ctx, seed=100,
                                       metrics=self.metrics)
            r_parts = hash_split_parts(right, self.right_keys, k, ctx,
                                       seed=100, metrics=self.metrics)
            # phase `join.probe`: a pair joined outside a segment (build +
            # probe + gather), one lap a pair; never open across a yield, and
            # flushed however the consumer leaves the generator
            laps = _obs.PhaseLaps()
            try:
                with self.metrics["joinTime"].timed():
                    for lp, rp in zip(l_parts, r_parts):
                        with laps.lap("join.probe"):
                            out = self._join_pair(lp, rp, names, ctx)
                        if out is not None and out.num_rows:
                            yield out
            finally:
                laps.flush()
            return
        with self.metrics["joinTime"].timed(), _obs.phase("join.probe"):
            out = self._join(left, right, ctx)
        yield out

    def _join_pair(self, lp, rp, names, ctx):
        """One sub-partition pair with the empty-side fast paths preserved."""
        jt = self.join_type
        l_empty = lp is None or lp.num_rows == 0
        r_empty = rp is None or rp.num_rows == 0
        if l_empty and r_empty:
            return None
        if l_empty:
            if jt in ("rightouter", "right", "fullouter", "outer", "full"):
                nulls_l = _all_null_cols(self.children[0].output,
                                         rp.num_rows, rp.capacity)
                return TpuColumnarBatch(nulls_l + rp.columns, rp.num_rows,
                                        names)
            return None
        if r_empty:
            if jt in ("leftanti", "anti"):
                return lp.rename(names)
            if jt in ("leftouter", "left", "fullouter", "outer", "full"):
                # only left/full outer pad unmatched left rows; a right outer
                # join emits nothing for a partition with no right rows
                nulls_r = _all_null_cols(self.children[1].output,
                                         lp.num_rows, lp.capacity)
                return TpuColumnarBatch(lp.columns + nulls_r, lp.num_rows,
                                        names)
            return None
        return self._join(lp, rp, ctx)

    def _join(self, left: TpuColumnarBatch, right: TpuColumnarBatch,
              ctx: TaskContext) -> TpuColumnarBatch:
        jt = self.join_type
        names = [a.name for a in self._output]
        l_cap, r_cap = left.capacity, right.capacity
        # key eval + sortable-bit encode for BOTH sides as one cached
        # executable (execs/opjit.py); string/host keys keep the eager path
        from . import opjit
        enc = opjit.encode_join_sides(self.left_keys, self.right_keys,
                                      left, right, ctx.eval_ctx,
                                      self.metrics)
        if enc is not None:
            l_enc, r_enc = enc
        else:
            lk = [to_column(k.eval_tpu(left, ctx.eval_ctx), left, k.dtype)
                  for k in self.left_keys]
            rk = [to_column(k.eval_tpu(right, ctx.eval_ctx), right, k.dtype)
                  for k in self.right_keys]
            l_enc, r_enc = _encode_sides(lk, rk, left.num_rows,
                                         right.num_rows, l_cap, r_cap)
        # probe = left, build = right
        pi, bi, ok, n_ok, total, out_cap = _device_equi_join(
            r_enc, right.num_rows, l_enc, left.num_rows)
        self.metrics["numPairs"].add(total)
        self.metrics["buildsIndexed"].add(1)  # unfused: a build a pair
        self.metrics["probesIndexed"].add(1)
        from ..config import DEFERRED_COMPACTION
        deferred = bool(ctx.conf.get(DEFERRED_COMPACTION))
        cpi, cbi, slot_ok, n_pairs = _compact_pairs(pi, bi, ok, n_ok,
                                                    deferred)

        lg = gather(left, jnp.where(slot_ok, cpi, -1), n_pairs, out_cap)
        rg = gather(right, jnp.where(slot_ok, cbi, -1), n_pairs, out_cap)
        joined = TpuColumnarBatch(lg.columns + rg.columns, n_pairs)

        pair_keep = slot_ok
        if self.condition is not None:
            cond = to_column(self.condition.eval_tpu(joined, ctx.eval_ctx), joined)
            keep = cond.data.astype(jnp.bool_)
            if cond.validity is not None:
                keep = keep & cond.validity
            pair_keep = pair_keep & keep
            joined = compact(joined, keep, deferred=deferred)

        if jt in ("inner", "cross"):
            # deferred: the verified-pair count rides the joined batch as a
            # device scalar to the exchange/collect boundary
            return joined.rename(names)

        # bookkeeping over VERIFIED+residual-surviving pairs
        match_cnt = jnp.zeros((l_cap + 1,), jnp.int32).at[
            jnp.where(pair_keep, cpi, l_cap)].add(1, mode="drop")[:l_cap]
        build_matched = jnp.zeros((r_cap + 1,), jnp.bool_).at[
            jnp.where(pair_keep, cbi, r_cap)].max(True, mode="drop")[:r_cap]

        lmask = row_mask(left.num_rows, l_cap)
        if jt in ("leftsemi", "semi"):
            return compact(left, (match_cnt > 0) & lmask).rename(names)
        if jt in ("leftanti", "anti"):
            return compact(left, (match_cnt == 0) & lmask).rename(names)

        parts = [joined] if joined.num_rows else []
        if jt in ("leftouter", "left", "fullouter", "outer", "full"):
            unmatched_l = compact(left, (match_cnt == 0) & lmask)
            if unmatched_l.num_rows:
                nulls_r = _all_null_cols(right.columns, unmatched_l.num_rows,
                                         unmatched_l.capacity)
                parts.append(TpuColumnarBatch(unmatched_l.columns + nulls_r,
                                              unmatched_l.num_rows))
        if jt in ("rightouter", "right", "fullouter", "outer", "full"):
            rmask = row_mask(right.num_rows, r_cap)
            unmatched_r = compact(right, (~build_matched) & rmask)
            if unmatched_r.num_rows:
                nulls_l = _all_null_cols(left.columns, unmatched_r.num_rows,
                                         unmatched_r.capacity)
                parts.append(TpuColumnarBatch(nulls_l + unmatched_r.columns,
                                              unmatched_r.num_rows))
        if not parts:
            parts = [joined]
        return concat_batches(parts).rename(names)


class TpuBroadcastNestedLoopJoinExec(TpuExec):
    """Cross join / conditional non-equi join (reference
    GpuBroadcastNestedLoopJoinExec). Blockwise cartesian expansion + filter."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan, join_type: str,
                 condition: Optional[Expression],
                 output: List[AttributeReference]):
        super().__init__([left, right])
        self.join_type = join_type
        self.condition = (bind_references(condition, left.output + right.output)
                          if condition is not None else None)
        self._output = output

    @property
    def output(self):
        return self._output

    def num_partitions(self) -> int:
        return 1

    def node_desc(self) -> str:
        return f"TpuBroadcastNestedLoopJoin[{self.join_type}]"

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        def side(child):
            batches = []
            for p in range(child.num_partitions()):
                batches.extend(child.execute_partition(p, ctx))
            return concat_batches(batches) if batches else None

        left, right = side(self.children[0]), side(self.children[1])
        jt = self.join_type
        names = [a.name for a in self._output]
        l_empty = left is None or not left.num_rows
        r_empty = right is None or not right.num_rows
        if l_empty or r_empty:
            # empty-side semantics (reference GpuBroadcastNestedLoopJoinExec
            # computeBuildRowCount special cases)
            if not l_empty:
                if jt in ("leftsemi", "semi"):
                    return
                if jt in ("leftanti", "anti"):
                    yield left.rename(names)
                    return
                if jt in ("leftouter", "left", "fullouter", "outer", "full"):
                    nulls_r = _all_null_cols(self.children[1].output,
                                             left.num_rows, left.capacity)
                    yield TpuColumnarBatch(left.columns + nulls_r,
                                           left.num_rows, names)
                    return
            if not r_empty and jt in ("rightouter", "right", "fullouter",
                                      "outer", "full"):
                nulls_l = _all_null_cols(self.children[0].output,
                                         right.num_rows, right.capacity)
                yield TpuColumnarBatch(nulls_l + right.columns,
                                       right.num_rows, names)
            return
        n_l, n_r = left.num_rows, right.num_rows
        total = n_l * n_r
        out_cap = bucket_capacity(max(total, 1))
        j = jnp.arange(out_cap)
        li = jnp.where(j < total, j // n_r, -1).astype(jnp.int32)
        ri = jnp.where(j < total, j % n_r, -1).astype(jnp.int32)
        lg = gather(left, li, total, out_cap)
        rg = gather(right, ri, total, out_cap)
        joined = TpuColumnarBatch(lg.columns + rg.columns, total)
        keep = j < total
        if self.condition is not None:
            cond = to_column(self.condition.eval_tpu(joined, ctx.eval_ctx), joined)
            keep = keep & cond.data.astype(jnp.bool_)
            if cond.validity is not None:
                keep = keep & cond.validity
        if jt in ("inner", "cross"):
            if self.condition is None:
                yield joined.rename(names)  # keep == row mask: no copy needed
            else:
                yield compact(joined, keep).rename(names)
            return
        # per-side match flags (scatter-max over pair keep mask; padding pairs
        # route to the dropped slot n)
        safe_li = jnp.where(j < total, li, n_l)
        safe_ri = jnp.where(j < total, ri, n_r)
        l_matched = jnp.zeros((n_l,), jnp.bool_).at[safe_li].max(keep, mode="drop")
        r_matched = jnp.zeros((n_r,), jnp.bool_).at[safe_ri].max(keep, mode="drop")
        l_pad = jnp.zeros((left.capacity,), jnp.bool_).at[
            jnp.arange(n_l)].set(l_matched)
        r_pad = jnp.zeros((right.capacity,), jnp.bool_).at[
            jnp.arange(n_r)].set(r_matched)
        if jt in ("leftsemi", "semi"):
            yield compact(left, l_pad).rename(names)
            return
        if jt in ("leftanti", "anti"):
            mask = (~l_pad) & row_mask(left.num_rows, left.capacity)
            yield compact(left, mask).rename(names)
            return
        parts = [compact(joined, keep)]
        if jt in ("leftouter", "left", "fullouter", "outer", "full"):
            lo_mask = (~l_pad) & row_mask(left.num_rows, left.capacity)
            lo = compact(left, lo_mask)
            if lo.num_rows:
                nulls_r = _all_null_cols(self.children[1].output,
                                         lo.num_rows, lo.capacity)
                parts.append(TpuColumnarBatch(lo.columns + nulls_r, lo.num_rows))
        if jt in ("rightouter", "right", "fullouter", "outer", "full"):
            ro_mask = (~r_pad) & row_mask(right.num_rows, right.capacity)
            ro = compact(right, ro_mask)
            if ro.num_rows:
                nulls_l = _all_null_cols(self.children[0].output,
                                         ro.num_rows, ro.capacity)
                parts.append(TpuColumnarBatch(nulls_l + ro.columns, ro.num_rows))
        yield concat_batches(parts).rename(names)


# ---------------------------------------------------------------------------
# CPU oracle
# ---------------------------------------------------------------------------

_ARROW_JOIN_TYPE = {"inner": "inner", "leftouter": "left outer", "left": "left outer",
                    "rightouter": "right outer", "right": "right outer",
                    "fullouter": "full outer", "outer": "full outer",
                    "full": "full outer", "leftsemi": "left semi",
                    "semi": "left semi", "leftanti": "left anti",
                    "anti": "left anti"}


class CpuShuffledHashJoinExec(CpuExec):
    def __init__(self, left: PhysicalPlan, right: PhysicalPlan, join_type: str,
                 left_keys: Sequence[Expression], right_keys: Sequence[Expression],
                 condition: Optional[Expression],
                 output: List[AttributeReference], per_partition: bool = False):
        super().__init__([left, right])
        self.join_type = join_type
        self.left_keys = bind_all(list(left_keys), left.output)
        self.right_keys = bind_all(list(right_keys), right.output)
        self.condition = (bind_references(condition, left.output + right.output)
                          if condition is not None else None)
        self._output = output
        self.per_partition = per_partition

    @property
    def output(self):
        return self._output

    def num_partitions(self) -> int:
        return self.children[0].num_partitions() if self.per_partition else 1

    def node_desc(self) -> str:
        return f"CpuShuffledHashJoin[{self.join_type}]"

    def _side_table(self, child, ctx, prefix: str, idx: int = 0):
        """Collect one side with positionally-unique column names (both sides may
        share user-visible names; expressions bind by ordinal, not name)."""
        import pyarrow as pa
        from ..types import to_arrow
        tables = []
        if self.per_partition:
            tables.extend(child.execute_partition(idx, ctx))
        else:
            for p in range(child.num_partitions()):
                tables.extend(child.execute_partition(p, ctx))
        names = [f"{prefix}{i}" for i in range(len(child.output))]
        if tables:
            return pa.concat_tables(
                [t.rename_columns(names) for t in tables])
        return pa.schema([(n, to_arrow(a.dtype))
                          for n, a in zip(names, child.output)]).empty_table()

    def execute_partition(self, idx: int, ctx: TaskContext) -> Iterator:
        import pyarrow as pa
        import pyarrow.compute as pc
        lt = self._side_table(self.children[0], ctx, "l", idx)
        rt = self._side_table(self.children[1], ctx, "r", idx)
        jt = self.join_type
        n_l = len(self.children[0].output)
        n_r = len(self.children[1].output)
        lkeys, rkeys = [], []
        for i, (lk, rk) in enumerate(zip(self.left_keys, self.right_keys)):
            la = _norm_key(_as_arr(lk.eval_cpu(lt, ctx.eval_ctx)))
            ra = _norm_key(_as_arr(rk.eval_cpu(rt, ctx.eval_ctx)))
            la, ra = _align_key_pair(la, ra)
            lt = lt.append_column(f"__lk_{i}", la)
            lkeys.append(f"__lk_{i}")
            rt = rt.append_column(f"__rk_{i}", ra)
            rkeys.append(f"__rk_{i}")
        l_out = [f"l{i}" for i in range(n_l)]
        r_out = [f"r{i}" for i in range(n_r)]
        if jt in ("leftsemi", "semi", "leftanti", "anti"):
            sel = l_out
        else:
            sel = l_out + r_out
        out_names = [a.name for a in self._output]
        if self.condition is not None:
            yield self._conditional(lt, rt, lkeys, rkeys, l_out, r_out,
                                    sel, out_names, ctx)
            return
        res = lt.join(rt, keys=lkeys, right_keys=rkeys,
                      join_type=_ARROW_JOIN_TYPE[jt], coalesce_keys=False)
        yield res.select(sel).rename_columns(out_names)

    def _conditional(self, lt, rt, lkeys, rkeys, l_out, r_out, sel, out_names, ctx):
        """Residual condition joins: inner pairs + filter, then reconstruct
        unmatched rows via row ids."""
        import pyarrow as pa
        import pyarrow.compute as pc
        from ..types import to_arrow
        jt = self.join_type
        lt = lt.append_column("__lrow", pa.array(np.arange(lt.num_rows)))
        rt = rt.append_column("__rrow", pa.array(np.arange(rt.num_rows)))
        inner = lt.join(rt, keys=lkeys, right_keys=rkeys, join_type="inner",
                        coalesce_keys=False)
        mask = pc.fill_null(self.condition.eval_cpu(
            inner.select(l_out + r_out), ctx.eval_ctx), False)
        kept = inner.filter(mask)
        if jt in ("inner", "cross"):
            return kept.select(sel).rename_columns(out_names)

        # vectorized match flags: pc.is_in of the full row-id range against
        # the surviving pairs' row ids. The previous set(to_pylist()) +
        # per-row `i in set` python loop dominated parity-test time on wide
        # inputs (O(rows) python-level membership tests per side).
        def matched(table, row_col):
            ids = pa.array(np.arange(table.num_rows, dtype=np.int64))
            vals = kept.column(row_col).combine_chunks()
            return pc.is_in(ids, value_set=vals)

        if jt in ("leftsemi", "semi"):
            return lt.filter(matched(lt, "__lrow")).select(sel) \
                .rename_columns(out_names)
        if jt in ("leftanti", "anti"):
            keep = pc.invert(matched(lt, "__lrow"))
            return lt.filter(keep).select(sel).rename_columns(out_names)
        parts = [kept.select(sel)]
        r_attrs = self.children[1].output
        l_attrs = self.children[0].output
        if jt in ("leftouter", "left", "fullouter", "outer", "full"):
            lu = lt.filter(pc.invert(matched(lt, "__lrow"))).select(l_out)
            for name, a in zip(r_out, r_attrs):
                lu = lu.append_column(name, pa.nulls(lu.num_rows, to_arrow(a.dtype)))
            parts.append(lu.select(sel))
        if jt in ("rightouter", "right", "fullouter", "outer", "full"):
            ru = rt.filter(pc.invert(matched(rt, "__rrow"))).select(r_out)
            for name, a in reversed(list(zip(l_out, l_attrs))):
                ru = ru.add_column(0, name, pa.nulls(ru.num_rows, to_arrow(a.dtype)))
            parts.append(ru.select(sel))
        return pa.concat_tables(parts).rename_columns(out_names)


def _as_arr(x):
    import pyarrow as pa
    return x.combine_chunks() if isinstance(x, pa.ChunkedArray) else x


def _align_key_pair(la, ra):
    """Promote mismatched join-key types to a common comparable type
    (date32 vs int as day numbers — shared rule with the comparison
    predicates; int widths to the wider) — the device plane compares via
    width-normalized sortable bits, so the CPU oracle must accept the same
    pairs."""
    import pyarrow as pa
    from ..expressions.predicates import _align_date_int

    both_arr = all(isinstance(x, (pa.Array, pa.ChunkedArray))
                   for x in (la, ra))
    if both_arr and la.type != ra.type:
        la, ra = _align_date_int(pa, la, ra)
        if pa.types.is_date32(la.type) or pa.types.is_date32(ra.type):
            # date vs non-int (e.g. date32 vs int64-backed date): day numbers
            la = la.cast(pa.int32()) if pa.types.is_date32(la.type) else la
            ra = ra.cast(pa.int32()) if pa.types.is_date32(ra.type) else ra
        if pa.types.is_integer(la.type) and pa.types.is_integer(ra.type) \
                and la.type != ra.type:
            target = (la.type if la.type.bit_width >= ra.type.bit_width
                      else ra.type)
            la, ra = la.cast(target), ra.cast(target)
    return la, ra


def _norm_key(arr):
    """NaN/-0.0 normalization for join keys (Spark: NaN==NaN in joins)."""
    import pyarrow as pa
    import pyarrow.compute as pc
    if isinstance(arr, (pa.Array, pa.ChunkedArray)) and pa.types.is_floating(arr.type):
        zero = pa.scalar(0.0, arr.type)
        arr = pc.if_else(pc.equal(arr, zero), zero, arr)
    return arr


class CpuBroadcastNestedLoopJoinExec(CpuExec):
    def __init__(self, left: PhysicalPlan, right: PhysicalPlan, join_type: str,
                 condition: Optional[Expression],
                 output: List[AttributeReference]):
        super().__init__([left, right])
        self.join_type = join_type
        self.condition = (bind_references(condition, left.output + right.output)
                          if condition is not None else None)
        self._output = output

    @property
    def output(self):
        return self._output

    def num_partitions(self) -> int:
        return 1

    def node_desc(self) -> str:
        return f"CpuBroadcastNestedLoopJoin[{self.join_type}]"

    def execute_partition(self, idx: int, ctx: TaskContext) -> Iterator:
        import pyarrow as pa
        import pyarrow.compute as pc

        def side(child, prefix):
            tables = []
            for p in range(child.num_partitions()):
                tables.extend(child.execute_partition(p, ctx))
            names = [f"{prefix}{i}" for i in range(len(child.output))]
            if tables:
                return pa.concat_tables([t.rename_columns(names) for t in tables])
            from ..types import to_arrow
            return pa.schema([(n, to_arrow(a.dtype))
                              for n, a in zip(names, child.output)]).empty_table()

        lt, rt = side(self.children[0], "l"), side(self.children[1], "r")
        n_l, n_r = lt.num_rows, rt.num_rows
        jt = self.join_type
        names = [a.name for a in self._output]

        def with_nulls(keep_t, null_src, left_side: bool):
            kept = [keep_t.column(i) for i in range(keep_t.num_columns)]
            nulls = [pa.nulls(keep_t.num_rows, null_src.column(i).type)
                     for i in range(null_src.num_columns)]
            cols = kept + nulls if left_side else nulls + kept
            # from_arrays, not pa.table(dict(...)): output names may repeat
            return pa.Table.from_arrays(
                [c.combine_chunks() if isinstance(c, pa.ChunkedArray) else c
                 for c in cols], names=names)

        if n_l == 0 or n_r == 0:
            if n_l:
                if jt in ("leftanti", "anti"):
                    yield lt.rename_columns(names)
                elif jt in ("leftouter", "left", "fullouter", "outer", "full"):
                    yield with_nulls(lt, rt, True)
            elif n_r and jt in ("rightouter", "right", "fullouter", "outer",
                                "full"):
                yield with_nulls(rt, lt, False)
            return
        li = np.repeat(np.arange(n_l), n_r)
        ri = np.tile(np.arange(n_r), n_l)
        joined = lt.take(pa.array(li))
        for i, name in enumerate(rt.column_names):
            joined = joined.append_column(name, rt.column(i).take(pa.array(ri)))
        if self.condition is not None:
            mask = self.condition.eval_cpu(joined, ctx.eval_ctx)
            mask_np = np.asarray(pc.fill_null(
                pa.array(mask) if not isinstance(mask, (pa.Array, pa.ChunkedArray))
                else mask, False))
        else:
            mask_np = np.ones(n_l * n_r, bool)
        if jt in ("inner", "cross"):
            yield joined.filter(pa.array(mask_np)).rename_columns(names)
            return
        l_matched = np.zeros(n_l, bool)
        l_matched[li[mask_np]] = True
        r_matched = np.zeros(n_r, bool)
        r_matched[ri[mask_np]] = True
        if jt in ("leftsemi", "semi"):
            yield lt.filter(pa.array(l_matched)).rename_columns(names)
            return
        if jt in ("leftanti", "anti"):
            yield lt.filter(pa.array(~l_matched)).rename_columns(names)
            return
        parts = [joined.filter(pa.array(mask_np)).rename_columns(names)]
        if jt in ("leftouter", "left", "fullouter", "outer", "full"):
            lo = lt.filter(pa.array(~l_matched))
            if lo.num_rows:
                parts.append(with_nulls(lo, rt, True))
        if jt in ("rightouter", "right", "fullouter", "outer", "full"):
            ro = rt.filter(pa.array(~r_matched))
            if ro.num_rows:
                parts.append(with_nulls(ro, lt, False))
        yield pa.concat_tables(parts)





# ---------------------------------------------------------------------------
# symmetric shuffled hash join (reference GpuShuffledSymmetricHashJoinExec,
# 1225 LoC: the join that picks its build side by the size actually
# materialized per partition rather than trusting the planner's estimate)
# ---------------------------------------------------------------------------

_MIRROR_JOIN = {"inner": "inner", "cross": "cross",
                "leftouter": "rightouter", "left": "rightouter",
                "rightouter": "leftouter", "right": "leftouter",
                "fullouter": "fullouter", "outer": "fullouter",
                "full": "fullouter"}


class TpuShuffledSymmetricHashJoinExec(TpuShuffledHashJoinExec):
    """Size-adaptive build side: each partition builds on whichever side
    materialized smaller, flipping the join orientation (and mirroring the
    join type) when the left is the better build side. Semi/anti joins are
    direction-bound and keep the fixed orientation."""

    def __init__(self, left, right, join_type, left_keys, right_keys,
                 condition, output, per_partition: bool = False):
        super().__init__(left, right, join_type, left_keys, right_keys,
                         condition, output, per_partition)
        self._can_flip = join_type in _MIRROR_JOIN
        if self._can_flip:
            self._twin = TpuShuffledHashJoinExec(
                right, left, _MIRROR_JOIN[join_type], right_keys, left_keys,
                condition, list(right.output) + list(left.output),
                per_partition)
            self._n_left_cols = len(left.output)

    def node_desc(self) -> str:
        return f"TpuShuffledSymmetricHashJoin[{self.join_type}]"

    def additional_metrics(self):
        m = dict(super().additional_metrics())
        m["buildSideFlips"] = "DEBUG"
        return m

    def _join(self, left: TpuColumnarBatch, right: TpuColumnarBatch,
              ctx: TaskContext) -> TpuColumnarBatch:
        # the base implementation builds on the RIGHT; flip when the left is
        # smaller so the hash table always comes from the smaller side
        if self._can_flip and left.num_rows < right.num_rows:
            self.metrics["buildSideFlips"].add(1)
            self._twin.metrics = self.metrics  # shared sink
            out = self._twin._join(right, left, ctx)
            nl = self._n_left_cols
            cols = out.columns[len(out.columns) - nl:] + \
                out.columns[: len(out.columns) - nl]
            names = [a.name for a in self._output]
            return TpuColumnarBatch(cols, out.num_rows, names)
        return super()._join(left, right, ctx)


# ---------------------------------------------------------------------------
# cartesian product (reference org/apache/spark/sql/rapids/
# GpuCartesianProductExec.scala: dedicated pairwise-partition product for
# large×large inner joins where neither side broadcasts)
# ---------------------------------------------------------------------------

class CpuCartesianProductExec(CpuExec):
    """Host cartesian product: output partition k = left part (k // nr) ×
    right part (k % nr)."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 condition: Optional[Expression],
                 output: List[AttributeReference]):
        super().__init__([left, right])
        self.condition = (bind_references(condition, left.output + right.output)
                          if condition is not None else None)
        self._output = output

    @property
    def output(self):
        return self._output

    def num_partitions(self) -> int:
        return self.children[0].num_partitions() * \
            self.children[1].num_partitions()

    def node_desc(self) -> str:
        return "CpuCartesianProduct"

    def _pair_tables(self, idx: int, ctx: TaskContext):
        import pyarrow as pa
        from ..types import to_arrow
        nr = self.children[1].num_partitions()
        li, ri = idx // nr, idx % nr

        def side(child, p, prefix):
            tables = list(child.execute_partition(p, ctx))
            names = [f"{prefix}{i}" for i in range(len(child.output))]
            if tables:
                return pa.concat_tables([t.rename_columns(names)
                                         for t in tables])
            return pa.schema([(n, to_arrow(a.dtype))
                              for n, a in zip(names, child.output)]).empty_table()

        return side(self.children[0], li, "l"), side(self.children[1], ri, "r")

    def execute_partition(self, idx: int, ctx: TaskContext) -> Iterator:
        import numpy as np
        import pyarrow as pa
        lt, rt = self._pair_tables(idx, ctx)
        nl, nr_rows = lt.num_rows, rt.num_rows
        if nl == 0 or nr_rows == 0:
            return
        li = np.repeat(np.arange(nl), nr_rows)
        ri = np.tile(np.arange(nr_rows), nl)
        joined = pa.Table.from_arrays(
            [lt.column(i).take(pa.array(li)) for i in range(lt.num_columns)]
            + [rt.column(i).take(pa.array(ri)) for i in range(rt.num_columns)],
            names=list(lt.column_names) + list(rt.column_names))
        if self.condition is not None:
            import pyarrow.compute as pc
            keep = self.condition.eval_cpu(joined, ctx.eval_ctx)
            joined = joined.filter(pc.fill_null(keep, False))
        yield joined.rename_columns([a.name for a in self._output])


class TpuCartesianProductExec(TpuExec):
    """Device cartesian product: the repeat/tile expansion is two gathers over
    an index grid — the same kernel BNLJ uses, but scoped to one
    (left-partition, right-partition) pair per output partition so the
    expansion never exceeds a partition pair's footprint."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 condition: Optional[Expression],
                 output: List[AttributeReference]):
        super().__init__([left, right])
        self.condition = (bind_references(condition, left.output + right.output)
                          if condition is not None else None)
        self._output = output

    @property
    def output(self):
        return self._output

    def num_partitions(self) -> int:
        return self.children[0].num_partitions() * \
            self.children[1].num_partitions()

    def node_desc(self) -> str:
        return "TpuCartesianProduct"

    def additional_metrics(self):
        return {"joinTime": "MODERATE", "numPairs": "DEBUG"}

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        nr = self.children[1].num_partitions()
        li, ri = idx // nr, idx % nr

        def side(child, p):
            batches = list(child.execute_partition(p, ctx))
            return concat_batches(batches) if batches else None

        left, right = side(self.children[0], li), side(self.children[1], ri)
        if left is None or right is None or not left.num_rows \
                or not right.num_rows:
            return
        names = [a.name for a in self._output]
        n_l, n_r = left.num_rows, right.num_rows
        total = n_l * n_r
        self.metrics["numPairs"].add(total)
        with self.metrics["joinTime"].timed():
            out_cap = bucket_capacity(max(total, 1))
            j = jnp.arange(out_cap)
            gl = gather(left, jnp.where(j < total, j // n_r, -1).astype(jnp.int32),
                        total, out_cap)
            gr = gather(right, jnp.where(j < total, j % n_r, -1).astype(jnp.int32),
                        total, out_cap)
            joined = TpuColumnarBatch(gl.columns + gr.columns, total)
            if self.condition is not None:
                cond = to_column(self.condition.eval_tpu(joined, ctx.eval_ctx),
                                 joined)
                keep = (j < total) & cond.data.astype(jnp.bool_)
                if cond.validity is not None:
                    keep = keep & cond.validity
                joined = compact(joined, keep)
            if joined.num_rows:
                yield joined.rename(names)
