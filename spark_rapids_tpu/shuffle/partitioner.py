"""Partitioners: split device batches by hash/round-robin/range/single.

Reference: GpuPartitioning.scala:64-118 (murmur3 on device + contiguousSplit),
GpuHashPartitioningBase.scala (Spark pid = pmod(murmur3(keys, 42), n)),
GpuRangePartitioner.scala. Device strategy: compute pids, stable-sort rows by
pid, sync the n partition boundaries to host, slice — the static-shape analogue
of cuDF's contiguous split.
"""

from __future__ import annotations

import functools as _functools

from typing import List, Optional, Sequence

import jax as _jax
import jax.numpy as jnp
import numpy as np

from ..columnar.batch import TpuColumnarBatch, gather
from ..columnar.vector import TpuColumnVector, bucket_capacity, row_mask
from ..expressions.base import Expression, to_column
from ..expressions.hashexprs import murmur3_batch


def hash_partition_ids(batch: TpuColumnarBatch, key_exprs: Sequence[Expression],
                       n: int, ctx, seed: int = 42,
                       metrics=None) -> jnp.ndarray:
    """Spark HashPartitioning: pmod(murmur3(keys, seed=42), n). Sub-partition
    callers pass a distinct seed so their buckets are independent of the
    upstream exchange's (reference GpuSubPartitionHashJoin.scala hashSeed=100).

    The key-eval + murmur3 + pmod chain runs as ONE cached executable when
    the keys trace (execs/opjit.py); string/host keys stay eager."""
    from ..execs import opjit
    pid = opjit.partition_ids(batch, key_exprs, n, ctx.eval_ctx, seed,
                              metrics)
    if pid is not None:
        return pid
    cols = [to_column(k.eval_tpu(batch, ctx.eval_ctx), batch, k.dtype)
            for k in key_exprs]
    h = murmur3_batch(cols, batch.num_rows, batch.capacity, seed)
    pid = h % n
    return jnp.where(pid < 0, pid + n, pid).astype(jnp.int32)


def round_robin_partition_ids(batch: TpuColumnarBatch, n: int,
                              start: int = 0) -> jnp.ndarray:
    return ((jnp.arange(batch.capacity, dtype=jnp.int32) + start) % n)


@_functools.partial(_jax.jit, static_argnames=("n",))
def _split_plan(pids, num_rows, n: int):
    """Sort-by-pid + partition bounds as one program (the eager version paid
    ~4 dispatches per batch)."""
    cap = pids.shape[0]
    mask = jnp.arange(cap) < num_rows
    key = jnp.where(mask, pids, n)  # padding last
    order = jnp.argsort(key, stable=True)
    sorted_pid = jnp.take(key, order)
    return order, jnp.searchsorted(sorted_pid, jnp.arange(n + 1))


def split_by_partition(batch: TpuColumnarBatch, pids, n: int) -> List[Optional[TpuColumnarBatch]]:
    """Device split: stable sort by pid, one async boundary readback,
    gather slices.

    The n+1 partition bounds decide each output's row count, and the exec
    protocol carries counts as python ints — so ONE small D→H transfer per
    batch is inherent to eager host-driven slicing (the compiled stage in
    execs/compiled.py is the no-sync path). What this avoids is blocking
    the pipeline for the full round trip: the copy starts immediately
    after the searchsorted is enqueued, overlapping the transfer with
    dispatch of the sort/gather work already in flight."""
    # rows_arg: a deferred-compaction batch's pending device count feeds the
    # plan directly — the bounds readback below is then the chain's ONE sync
    order, bounds_dev = _split_plan(pids, batch.rows_arg, n=n)
    return split_with_plan(batch, order, bounds_dev, n)


def split_with_plan(batch: TpuColumnarBatch, order, bounds_dev,
                    n: int) -> List[Optional[TpuColumnarBatch]]:
    """Slice a batch along an already-computed (order, bounds) split plan
    (from _split_plan or the fused opjit.partition_split_plan program)."""
    bounds_dev.copy_to_host_async()
    from ..columnar.vector import audited_sync
    bounds = audited_sync(bounds_dev, "bounds")
    return _slice_split(batch, order, bounds, n)


def _slice_split(batch: TpuColumnarBatch, order, bounds,
                 n: int) -> List[Optional[TpuColumnarBatch]]:
    """Gather the n partition slices given HOST bounds (the readback already
    happened — per batch in split_with_plan, or ONE transfer for a whole
    partition group in hash_split_parts_grouped)."""
    cap = batch.capacity
    out: List[Optional[TpuColumnarBatch]] = []
    for p in range(n):
        lo, hi = int(bounds[p]), int(bounds[p + 1])
        cnt = hi - lo
        if cnt == 0:
            out.append(None)
            continue
        idx = jnp.take(order, jnp.clip(jnp.arange(bucket_capacity(cnt)) + lo,
                                       0, cap - 1))
        out.append(gather(batch, idx, cnt, bucket_capacity(cnt)))
    return out


def hash_split_parts(batch: TpuColumnarBatch, key_exprs: Sequence[Expression],
                     n: int, ctx, seed: int = 42,
                     metrics=None) -> List[Optional[TpuColumnarBatch]]:
    """Hash-partition a batch into n slices with the ENCODE+SPLIT pair fused
    into one cached executable when the keys trace (opjit.partition_split_plan
    — one dispatch instead of pids + split plan); eager two-program path
    otherwise, bit-identical either way."""
    from ..execs import opjit
    plan = opjit.partition_split_plan(batch, key_exprs, n, ctx.eval_ctx,
                                      seed, metrics)
    if plan is not None:
        return split_with_plan(batch, plan[0], plan[1], n)
    pids = hash_partition_ids(batch, key_exprs, n, ctx, seed=seed,
                              metrics=metrics)
    return split_by_partition(batch, pids, n)


def hash_split_parts_grouped(batches: Sequence[TpuColumnarBatch],
                             key_exprs: Sequence[Expression], n: int, ctx,
                             seed: int = 42, metrics=None
                             ) -> Optional[List[List[Optional[TpuColumnarBatch]]]]:
    """Batched multi-partition dispatch of the hash split: N map partitions'
    batches run their encode+split plans as ONE cached executable
    (opjit.partition_split_plan_grouped) and ALL lanes' partition bounds come
    back in ONE device→host transfer — per-lane slices are bit-identical to
    hash_split_parts. Returns one parts list per input batch, or None when
    the keys don't trace (callers fall back to the per-batch split)."""
    from ..execs import opjit
    plans = opjit.partition_split_plan_grouped(
        batches, [list(key_exprs)] * len(batches), n, ctx.eval_ctx, seed,
        metrics)
    if plans is None:
        return None
    orders, bounds_dev = plans
    for bd in bounds_dev:
        try:
            bd.copy_to_host_async()
        except AttributeError:
            pass
    from ..columnar.vector import audited_device_get
    host_bounds = audited_device_get(bounds_dev, "bounds")
    return [_slice_split(b, o, hb, n)
            for b, o, hb in zip(batches, orders, host_bounds)]


def np_hash_partition_ids(table, key_exprs, n: int, ctx) -> np.ndarray:
    """Host mirror for the CPU exchange path."""
    from ..expressions.hashexprs import _np_hash_col
    import pyarrow as pa
    seeds = np.full(table.num_rows, np.uint32(42), np.uint32)
    for k in key_exprs:
        arr = k.eval_cpu(table, ctx.eval_ctx)
        if not isinstance(arr, (pa.Array, pa.ChunkedArray)):
            arr = pa.array([arr] * table.num_rows)
        seeds = _np_hash_col(k.dtype, arr, seeds)
    h = seeds.view(np.int32).astype(np.int64)
    return ((h % n) + n) % n
