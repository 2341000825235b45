"""Pallas TPU kernel for the Q1 partial aggregation — single-pass fusion.

Why: the XLA path (kernels/q1.py) is correct and MXU-friendly, but it
materializes the [n, 16] one-hot operand and the [n, 6] measure stack in HBM
(~1.4 GB of extra traffic at n=16.7M). This kernel streams each row tile
through VMEM once — measures and the one-hot tile live only in registers /
VMEM, and the [16, 6] group table accumulates across sequential grid steps —
so total HBM traffic collapses to the 8 input columns (~0.5 GB), the
bandwidth floor for this query.

Reference analogue: one fused cuDF kernel chain of GpuAggFirstPassIterator;
here it is literally one kernel.

An MXU-contraction variant (one [16, E] x [E, 8] matmul per tile) lived here
until PR 22: Mosaic refuses its [256, 128] -> [32768, 1] reshape for v5e
("infer-vector-layout: unsupported shape cast"), so it had only ever "run"
by giving way to this kernel, and it was deleted rather than offered.
CPU tests run this kernel under interpret=True.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .q1 import N_GROUPS, N_STATUS, Q1Inputs, Q1State

_LANES = 128
_TILE_ROWS = 256  # rows of 128 lanes → 32768 elements per grid step


def _q1_kernel(cutoff_ref, rf_ref, ls_ref, qty_ref, price_ref, disc_ref,
               tax_ref, ship_ref, valid_ref, out_ref):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:, :] = jnp.zeros_like(out_ref)

    keep = valid_ref[:, :] & (ship_ref[:, :] <= cutoff_ref[0, 0])
    w = keep.astype(jnp.float32)
    price_raw = price_ref[:, :]
    disc_raw = disc_ref[:, :]
    qty = qty_ref[:, :] * w
    price = price_raw * w
    disc_price = price_raw * (1.0 - disc_raw) * w
    charge = disc_price * (1.0 + tax_ref[:, :])
    disc = disc_raw * w

    group = rf_ref[:, :] * N_STATUS + ls_ref[:, :]          # [R, 128] int32
    # masked VPU reductions over the row axis only (Mosaic rejects scalar
    # VMEM stores and the transposed MXU contraction): one [16,R,128] mask
    # broadcast, six reductions, a single [16, 6*128] accumulate; the caller
    # finishes the tiny lane sum
    gidx = jax.lax.broadcasted_iota(jnp.int32, (N_GROUPS, 1, 1), 0)
    masks = (group[None, :, :] == gidx).astype(jnp.float32)  # [16, R, 128]
    measures = (qty, price, disc_price, charge, disc, w)
    per = [jnp.sum(masks * col[None, :, :], axis=1)          # [16, 128] each
           for col in measures]
    out_ref[:, :] += jnp.concatenate(per, axis=1)            # [16, 6*128]


def q1_partial_pallas(batch: Q1Inputs, cutoff_days,
                      interpret: bool = False) -> Q1State:
    """Pallas single-pass partial aggregation (shapes padded to tile size)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = batch.quantity.shape[0]
    per_tile = _TILE_ROWS * _LANES
    padded = -(-n // per_tile) * per_tile

    def shape2d(a, fill):
        if padded != n:
            a = jnp.pad(a, (0, padded - n), constant_values=fill)
        return a.reshape(-1, _LANES)

    rf = shape2d(batch.returnflag, 0)
    ls = shape2d(batch.linestatus, 0)
    qty = shape2d(batch.quantity, 0)
    price = shape2d(batch.extendedprice, 0)
    disc = shape2d(batch.discount, 0)
    tax = shape2d(batch.tax, 0)
    ship = shape2d(batch.shipdate, 0)
    valid = shape2d(batch.valid, False)

    grid = padded // per_tile
    col_spec = pl.BlockSpec((_TILE_ROWS, _LANES), lambda i: (i, 0))
    # Mosaic rejects the program under jax_enable_x64 (64-bit index types leak
    # into the lowering); every dtype in this kernel is explicitly 32-bit, so
    # tracing the call in a disable-x64 scope is semantics-preserving
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _q1_kernel,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),  # cutoff scalar
                col_spec, col_spec, col_spec, col_spec, col_spec, col_spec,
                col_spec, col_spec,
            ],
            out_specs=pl.BlockSpec((N_GROUPS, 6 * _LANES), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((N_GROUPS, 6 * _LANES),
                                           jnp.float32),
            interpret=interpret,
        )(jnp.asarray([[cutoff_days]], jnp.int32), rf, ls, qty, price, disc,
          tax, ship, valid)

    sums = out.reshape(N_GROUPS, 6, _LANES).sum(axis=2)  # finish lane sum
    return Q1State(
        sum_qty=sums[:, 0], sum_base_price=sums[:, 1],
        sum_disc_price=sums[:, 2], sum_charge=sums[:, 3],
        sum_disc=sums[:, 4],
        count=sums[:, 5].astype(jnp.int32),
    )


@partial(jax.jit, static_argnames=("interpret",))
def q1_step_pallas(batch: Q1Inputs, cutoff, interpret: bool = False):
    """Jitted full Q1 step over the Pallas partial. No fallback: where the
    backend's compiler refuses the kernel, the caller sees that error
    (tests/test_tpu_compile.py keeps it compiling for v5e at 2^24 rows;
    `interpret=True` is for CPU tests only)."""
    from .q1 import q1_final
    return q1_final(q1_partial_pallas(batch, cutoff, interpret=interpret))
