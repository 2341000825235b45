"""Distributed execution over a jax.sharding.Mesh — kernel-level layer.

The TPU re-design of the reference's distributed layer (SURVEY.md §2.7):
  * Spark executor data-parallelism       → mesh "data" axis, row-sharded batches
  * partial→shuffle→final aggregation     → per-shard partial agg + psum (tree
    aggregate over ICI — cheaper than materializing a shuffle for aggregates)
  * hash-partition exchange (UCX mode)    → murmur3 bucketing + lax.all_to_all
    over ICI ("ICI shuffle mode", config spark.rapids.shuffle.mode=ICI)
The reference's parallelism inventory (SURVEY.md §2.7 note) maps exactly: no
tensor/pipeline/expert axes exist in a SQL engine; the mesh is 1-D data-parallel
with collectives carrying exchange traffic.

This module holds the KERNEL-level pieces (the q1 sharded step and the raw
all-to-all used as collective smoke checks); the plan-driven sharded
executor that runs ARBITRARY session queries on the mesh data plane lives
in `parallel/sharded.py` + `parallel/mesh.py`, selected by the planner
(`plan/overrides.py`) whenever a mesh session is active.  `dryrun_multichip`
below validates both layers and emits the MULTICHIP bench summary
(benchmarks/multichip.py) as its LAST stdout line.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..columnar.vector import audited_sync
from ..kernels.q1 import Q1Inputs, Q1State, q1_final, q1_partial


def make_mesh(n_devices: Optional[int] = None, axis: str = "data") -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (axis,))


def shard_batch(mesh: Mesh, batch, axis: str = "data"):
    """Place a batch's arrays row-sharded across the mesh."""
    sharding = NamedSharding(mesh, P(axis))
    return jax.tree.map(lambda a: jax.device_put(a, sharding), batch)


def distributed_q1_step(mesh: Mesh, axis: str = "data"):
    """Build the jitted multi-chip query step: row-sharded scan → per-shard
    partial agg → psum over ICI → identical final results on every shard.
    This is the aggregate analogue of partial/final around an exchange
    (GpuShuffleExchangeExecBase between GpuHashAggregateExec modes)."""

    def step(batch: Q1Inputs, cutoff):
        state = q1_partial(batch, cutoff)
        merged = jax.tree.map(lambda x: jax.lax.psum(x, axis), state)
        return q1_final(Q1State(*merged))

    spec = P(axis)
    in_specs = (Q1Inputs(*([spec] * 8)), P())
    out_spec = P()  # replicated results
    sharded = jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                        out_specs=out_spec, check_vma=False)
    return jax.jit(sharded)


def ici_all_to_all_exchange(mesh: Mesh, axis: str = "data"):
    """Jitted hash-partition exchange over ICI: each shard buckets its rows by
    murmur3(key) % n_shards into fixed-size slots, then lax.all_to_all moves
    bucket i of every shard to shard i (the UCX-mode data plane,
    reference shuffle-plugin/ UCXShuffleTransport, re-expressed as an XLA
    collective so XLA schedules it on the interconnect).

    Returns fn(keys, values, slot_capacity) -> (recv_keys, recv_values,
    recv_valid) with shapes [n_shards * slot_capacity] per shard; overflowing
    rows are dropped into the valid mask (callers size slots via sub-partition
    retry, mirroring GpuSubPartitionHashJoin's approach to skew)."""
    n_shards = mesh.devices.size

    def exchange(keys, values, valid):
        from ..expressions.hashexprs import murmur3_int
        cap = keys.shape[0]
        slot_cap = cap // n_shards
        h = murmur3_int(keys.astype(jnp.int32).view(jnp.uint32),
                        jnp.uint32(42)).view(jnp.int32)
        dest = jnp.where(valid, jnp.abs(h) % n_shards, n_shards)  # invalid → drop
        # slot position within destination bucket
        one = jnp.ones((cap,), jnp.int32)
        # rank of each row within its destination (stable): sort by dest
        order = jnp.argsort(dest, stable=True)
        sorted_dest = jnp.take(dest, order)
        # position within run of equal dest
        idx = jnp.arange(cap, dtype=jnp.int32)
        run_start = jnp.zeros((n_shards + 2,), jnp.int32).at[sorted_dest + 1].add(one, mode="drop")
        starts = jnp.cumsum(run_start)[:-1]  # start offset of each dest bucket
        pos_in_bucket = idx - jnp.take(starts, sorted_dest)
        keep = pos_in_bucket < slot_cap
        # scatter into [n_shards, slot_cap] send buffers
        send_slot = jnp.where(keep, sorted_dest * slot_cap + pos_in_bucket,
                              n_shards * slot_cap)
        src_rows = order
        buf_k = jnp.zeros((n_shards * slot_cap,), keys.dtype).at[send_slot].set(
            jnp.take(keys, src_rows), mode="drop")
        buf_v = jnp.zeros((n_shards * slot_cap,), values.dtype).at[send_slot].set(
            jnp.take(values, src_rows), mode="drop")
        buf_ok = jnp.zeros((n_shards * slot_cap,), jnp.bool_).at[send_slot].set(
            (sorted_dest < n_shards) & keep, mode="drop")
        # all-to-all: axis-split into n_shards blocks, transpose across shards
        def a2a(x):
            x = x.reshape(n_shards, slot_cap)
            return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                      tiled=False).reshape(-1)
        return a2a(buf_k), a2a(buf_v), a2a(buf_ok)

    spec = P(axis)
    return jax.jit(jax.shard_map(exchange, mesh=mesh,
                             in_specs=(spec, spec, spec),
                             out_specs=(spec, spec, spec), check_vma=False))


def dryrun_multichip(n_devices: int) -> None:
    """Multi-chip validation + MULTICHIP bench over an n_devices mesh:
    (a) kernel layer: row-sharded partial agg + psum final, and the raw
        ICI all-to-all exchange — both collective shapes of the shuffle
        design compile and route correctly;
    (b) data plane: the plan-driven sharded executor runs TPC-H q1/q3/q18
        and a TPC-DS sample through session → planner → collective
        exchanges, bit-identical to the single-device baseline, with the
        O(exchanges) collective-launch assertion — and prints the compact
        parseable MULTICHIP summary as the LAST stdout line (per-chip
        rows/s, collective-time breakdown, scaling efficiency)."""
    import json
    import os
    import sys

    from ..kernels.q1 import make_example_batch
    mesh = make_mesh(n_devices)
    n = 128 * n_devices
    batch, cutoff = make_example_batch(n)
    batch = shard_batch(mesh, batch)
    step = distributed_q1_step(mesh)
    out = step(batch, jnp.int32(cutoff))
    jax.block_until_ready(out)
    assert int(audited_sync(out["count_order"], "fetch").sum()) > 0

    exchange = ici_all_to_all_exchange(mesh)
    keys = jnp.arange(n, dtype=jnp.int64)
    vals = jnp.ones((n,), jnp.float32)
    valid = jnp.ones((n,), jnp.bool_)
    sharding = NamedSharding(mesh, P("data"))
    keys, vals, valid = (jax.device_put(x, sharding) for x in (keys, vals, valid))
    rk, rv, rok = exchange(keys, vals, valid)
    jax.block_until_ready((rk, rv, rok))
    # every received-valid key must hash-route to its receiving shard
    from ..expressions.hashexprs import np_murmur3_int
    rk_np = audited_sync(rk, "fetch")
    rok_np = audited_sync(rok, "fetch")
    n_local = rk_np.shape[0] // n_devices
    dest = np.abs(np_murmur3_int(rk_np.astype(np.int32).view(np.uint32),
                                 np.uint32(42)).view(np.int32).astype(np.int64)) % n_devices
    owner = np.repeat(np.arange(n_devices), n_local)
    assert (dest[rok_np] == owner[rok_np]).all(), "exchange misrouted rows"

    # (b) the framework data plane: plan-driven sharded execution of real
    # queries (benchmarks/multichip.py). The summary prints LAST so the
    # driver's stdout tail is the parseable MULTICHIP record.
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    import benchmarks.multichip as mc
    rows = int(os.environ.get("MULTICHIP_ROWS", str(1 << 16)))
    summary = mc.run(n_devices, rows)
    records = summary.pop("records", [])
    print(json.dumps({"detail": records}), flush=True)
    assert not summary.get("errors"), \
        f"multichip query stages failed: {summary['errors']}"
    assert summary.get("bit_identical_all"), \
        "mesh execution diverged from single-device results"
    assert summary.get("collective_launches_O_exchanges"), \
        "collective launches not O(exchanges)"
    # coverage, not just scaling: the pruned q3/q18/tpcds_q3 shapes are
    # fully fixed-width, so EVERY one of their exchanges must have ridden
    # the fabric (q1's string-keyed aggregation exchange is per-map by
    # design and is exempt) — a silent eligibility regression fails here
    for qname in ("tpch_q3", "tpch_q18", "tpcds_q3"):
        q = summary["queries"].get(qname, {})
        assert q.get("collective_launches", 0) == q.get("exchanges", -1), \
            f"{qname}: only {q.get('collective_launches')} of " \
            f"{q.get('exchanges')} exchanges took the collective"
    print(json.dumps(summary, separators=(",", ":")), flush=True)
