"""Device columnar batches + host↔device conversion.

TPU analogue of Spark's `ColumnarBatch` of `GpuColumnVector`s and the reference's
row↔columnar transitions (/root/reference/sql-plugin/.../GpuColumnarToRowExec.scala,
GpuRowToColumnarExec.scala, HostColumnarToGpu.scala). The host substrate is Arrow
(pyarrow.RecordBatch/Table) rather than Spark InternalRow.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence

import functools as _functools

import jax.numpy as jnp
import numpy as np

from jax import jit as _jax_jit

from ..types import DataType, StructField, StructType, from_arrow as arrow_to_type
from .vector import (TpuColumnVector, audited_device_get, audited_sync,
                     audited_sync_int, bucket_capacity, row_mask)


class TpuColumnarBatch:
    """A batch of device columns sharing num_rows/capacity.

    `num_rows` may be constructed from a DEVICE int scalar (deferred
    compaction, `compact(..., deferred=True)`): the count then rides along
    as a device value — `rows_lazy`/`rows_arg` expose it without blocking —
    and materializes to a host int on first `.num_rows` read, or for free
    inside `to_arrow`'s batched device_get. Rows in [num_rows, capacity)
    are padding with validity False either way, so device math over a
    deferred batch is identical to the materialized one."""

    __slots__ = ("columns", "names", "_num_rows", "_rows_dev")

    def __init__(self, columns: List[TpuColumnVector], num_rows,
                 names: Optional[List[str]] = None):
        self.columns = columns
        self.names = names
        if isinstance(num_rows, (int, np.integer)):
            self._num_rows: Optional[int] = int(num_rows)
            self._rows_dev = None
            for c in columns:
                assert not isinstance(c.num_rows, (int, np.integer)) \
                    or c.num_rows == self._num_rows, \
                    "column row counts must agree"
        else:  # device scalar: deferred row count
            self._num_rows = None
            self._rows_dev = num_rows

    @property
    def num_rows(self) -> int:
        """Logical row count; materializes a deferred count (ONE blocking
        scalar sync, recorded in the ledger) on first read."""
        if self._num_rows is None:
            self._set_rows(audited_sync_int(self._rows_dev, "rows"))
        return self._num_rows

    @property
    def has_pending_rows(self) -> bool:
        return self._num_rows is None

    @property
    def rows_lazy(self):
        """The row count WITHOUT forcing a sync: host int when known,
        device scalar otherwise (TpuMetric.add_lazy accepts either)."""
        return self._rows_dev if self._num_rows is None else self._num_rows

    @property
    def rows_arg(self):
        """Row count as a jitted-program argument: int or device scalar
        (jax specializes per argument signature; results are identical)."""
        return self.rows_lazy

    def _set_rows(self, n: int) -> None:
        self._num_rows = int(n)
        self._rows_dev = None
        # columns built under a deferred count carry the device scalar too;
        # patch them so direct column access sees the host int
        for c in self.columns:
            if not isinstance(c.num_rows, (int, np.integer)):
                c.num_rows = self._num_rows
                if c.children is not None:
                    for k in c.children:
                        if not isinstance(k.num_rows, (int, np.integer)):
                            k.num_rows = self._num_rows

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else bucket_capacity(self.num_rows)

    def schema(self) -> StructType:
        names = self.names or [f"c{i}" for i in range(self.num_columns)]
        return StructType([StructField(n, c.dtype) for n, c in zip(names, self.columns)])

    def column(self, i: int) -> TpuColumnVector:
        return self.columns[i]

    def device_memory_size(self) -> int:
        return sum(c.device_memory_size() for c in self.columns)

    def to_arrow(self):
        import pyarrow as pa
        names = self.names or [f"c{i}" for i in range(self.num_columns)]
        # ONE device_get for every device buffer in the batch: each
        # np.asarray on a jax.Array is a blocking round trip, which dominates
        # result materialization when there are many small buffers. A
        # deferred row count rides the SAME transfer — materializing at the
        # boundary costs zero extra syncs.
        leaves: List = []

        def collect(c):
            if c.host_data is not None:
                return
            for buf in (c.data, c.validity, c.offsets):
                if buf is not None and not isinstance(buf, np.ndarray):
                    leaves.append(buf)
            if c.child is not None:
                collect(c.child)
            if c.children is not None:
                for k in c.children:
                    collect(k)

        for c in self.columns:
            collect(c)
        pending = self.has_pending_rows
        if pending:
            leaves.append(self._rows_dev)
        if leaves:
            got = audited_device_get(leaves, "batch")
        else:
            got = []
        if pending:
            self._set_rows(int(got.pop()))
        fetched = iter(got)

        def localize(c):
            if c.host_data is not None:
                return c
            data, validity, offsets = c.data, c.validity, c.offsets
            if data is not None and not isinstance(data, np.ndarray):
                data = next(fetched)
            if validity is not None and not isinstance(validity, np.ndarray):
                validity = next(fetched)
            if offsets is not None and not isinstance(offsets, np.ndarray):
                offsets = next(fetched)
            child = localize(c.child) if c.child is not None else None
            kids = ([localize(k) for k in c.children]
                    if c.children is not None else None)
            return TpuColumnVector(c.dtype, data, validity, c.num_rows,
                                   offsets=offsets, child=child,
                                   host_data=c.host_data,
                                   host_capacity=c.host_capacity,
                                   children=kids)

        arrays = [localize(c).to_arrow() for c in self.columns]
        # from_arrays, not pa.table(dict(...)): names may repeat (e.g. join
        # output carrying the same key name from both sides)
        return (pa.Table.from_arrays(arrays, names=list(names))
                if arrays else pa.table({}))

    def to_pylist(self) -> List[dict]:
        return self.to_arrow().to_pylist()

    @staticmethod
    def from_arrow(table, to_device: bool = True) -> "TpuColumnarBatch":
        """Arrow table/record-batch → device batch (H→D; reference
        HostColumnarToGpu). All buffers ship in ONE device_put.
        `to_device=False` keeps numpy buffers (valid column payloads — jax
        ops upload them implicitly on first use): right for tiny result
        tables that are usually collected straight back to the host."""
        import pyarrow as pa

        from .vector import _keep_host
        if isinstance(table, pa.RecordBatch):
            table = pa.table(table)
        table = table.combine_chunks()
        _keep_host.active = True
        try:
            cols = [TpuColumnVector.from_arrow(table.column(i))
                    for i in range(table.num_columns)]
            # all columns in one batch must share a row capacity
            if cols:
                cap = max(c.capacity for c in cols)
                cols = [_repad(c, cap) for c in cols]
        finally:
            _keep_host.active = False
        if not to_device:
            return TpuColumnarBatch(cols, table.num_rows,
                                    list(table.column_names))

        # single upload of every numpy buffer across all columns
        return batch_to_device(
            TpuColumnarBatch(cols, table.num_rows, list(table.column_names)),
            None)

    @staticmethod
    def from_pydict(data: Dict[str, Sequence],
                    types: Optional[Dict[str, DataType]] = None) -> "TpuColumnarBatch":
        import pyarrow as pa
        from ..types import to_arrow as type_to_arrow
        arrays = {}
        for name, vals in data.items():
            at = type_to_arrow(types[name]) if types and name in types else None
            arrays[name] = pa.array(vals, type=at)
        return TpuColumnarBatch.from_arrow(pa.table(arrays))

    def select(self, indices: Sequence[int]) -> "TpuColumnarBatch":
        names = self.names
        return TpuColumnarBatch([self.columns[i] for i in indices],
                                self.rows_lazy,
                                [names[i] for i in indices] if names else None)

    def rename(self, names: List[str]) -> "TpuColumnarBatch":
        # rows_lazy: renaming a deferred batch must not force its count
        return TpuColumnarBatch(self.columns, self.rows_lazy, list(names))


def batch_to_device(batch: TpuColumnarBatch, device) -> TpuColumnarBatch:
    """`batch` with every buffer on `device`: ONE device_put of all its
    arrays — numpy buffers upload, arrays already there are passed through.
    A device (a mesh session's chip) commits them to it; None is the
    default device, uncommitted (`from_arrow`'s upload). A deferred row
    count moves with it; a dictionary encoding, a cache that need not
    follow, drops."""
    import dataclasses

    import jax
    leaves: List = []

    def collect(c: TpuColumnVector) -> None:
        for buf in (c.data, c.validity, c.offsets):
            if buf is not None:
                leaves.append(buf)
        if c.child is not None:
            collect(c.child)
        for k in c.children or ():
            collect(k)

    for c in batch.columns:
        collect(c)
    rows = batch.rows_lazy
    if not isinstance(rows, (int, np.integer)):
        leaves.append(rows)
    placed = iter(jax.device_put(leaves, device)) if leaves else iter(())

    def rebuild(c: TpuColumnVector) -> TpuColumnVector:
        data, validity, offsets = (
            None if buf is None else next(placed)
            for buf in (c.data, c.validity, c.offsets))
        return dataclasses.replace(
            c, data=data, validity=validity, offsets=offsets,
            child=None if c.child is None else rebuild(c.child),
            children=None if c.children is None
            else [rebuild(k) for k in c.children],
            dict_encoding=None)

    cols = [rebuild(c) for c in batch.columns]
    if not isinstance(rows, (int, np.integer)):
        rows = next(placed)
        for c in cols:
            if not isinstance(c.num_rows, (int, np.integer)):
                c.num_rows = rows
    return TpuColumnarBatch(cols, rows, batch.names)


def _repad(col: TpuColumnVector, capacity: int) -> TpuColumnVector:
    if col.capacity == capacity:
        return col
    if col.host_data is not None:
        return TpuColumnVector(col.dtype, col.data, col.validity, col.num_rows,
                               host_data=col.host_data, host_capacity=capacity)
    if col.capacity > capacity:
        raise ValueError("cannot shrink capacity")
    if col.children is not None:
        pad = capacity - col.capacity
        validity = col.validity
        if validity is not None:
            vxp = np if isinstance(validity, np.ndarray) else jnp
            validity = vxp.concatenate(
                [validity, vxp.zeros((pad,), vxp.bool_)])
        return TpuColumnVector(
            col.dtype, col.data, validity, col.num_rows,
            children=[_repad(c, capacity) for c in col.children])
    pad = capacity - col.capacity
    # stay in the numpy domain for host-built columns (deferred batch upload)
    xp = np if isinstance(col.data, np.ndarray) else jnp
    if col.offsets is not None:
        last = col.offsets[-1]
        oxp = np if isinstance(col.offsets, np.ndarray) else jnp
        offsets = oxp.concatenate(
            [col.offsets, oxp.full((pad,), last, oxp.int32)])
        data = col.data
    else:
        offsets = None
        data = xp.concatenate(
            [col.data, xp.zeros((pad,) + col.data.shape[1:], col.data.dtype)])
    validity = col.validity
    if validity is not None:
        vxp = np if isinstance(validity, np.ndarray) else jnp
        validity = vxp.concatenate([validity, vxp.zeros((pad,), vxp.bool_)])
    return TpuColumnVector(col.dtype, data, validity, col.num_rows, offsets=offsets,
                           child=col.child)


def gather(batch: TpuColumnarBatch, indices, out_rows,
           out_capacity: Optional[int] = None) -> TpuColumnarBatch:
    """Row gather across all columns (reference: cudf Table.gather / GatherMap).

    `indices` is a device int32 array of length >= out_capacity; entries beyond
    out_rows are ignored (padding). Out-of-range entries yield null rows, matching
    cuDF OutOfBoundsPolicy.NULLIFY.

    `out_rows` may be a DEVICE int scalar (deferred compaction): the gather
    runs entirely on device and the returned batch carries a pending row
    count (`out_capacity` is then required — a bucketed capacity cannot be
    derived without syncing).
    """
    deferred = not isinstance(out_rows, (int, np.integer))
    if deferred:
        assert out_capacity is not None, \
            "deferred gather requires an explicit out_capacity"
    cap = out_capacity if out_capacity is not None else bucket_capacity(out_rows)
    idx = jnp.asarray(indices)[:cap].astype(jnp.int32)
    # fixed-width columns gather in ONE compiled program (each eager op is its
    # own launch and, the first time, its own compile); strings/lists keep the
    # host-assisted per-column path
    fixed = [(i, c) for i, c in enumerate(batch.columns)
             if c.child is None and c.host_data is None
             and c.offsets is None and c.children is None]
    out_cols: list = [None] * len(batch.columns)
    if fixed:
        datas = [c.data for _, c in fixed]
        valids = [c.validity for _, c in fixed]
        g_datas, g_valids = _gather_fixed_cols(
            datas, valids, idx, jnp.int32(batch.rows_arg),
            jnp.int32(out_rows))
        for (i, c), d, v in zip(fixed, g_datas, g_valids):
            out_cols[i] = TpuColumnVector(c.dtype, d, v, out_rows)
    if len(fixed) != len(batch.columns):
        valid_idx = (idx >= 0) & (idx < batch.rows_arg)
        safe = jnp.where(valid_idx, idx, 0)
        pad_mask = row_mask(out_rows, cap)
        for i, col in enumerate(batch.columns):
            if out_cols[i] is None:
                out_cols[i] = _gather_column(col, safe,
                                             valid_idx & pad_mask,
                                             out_rows, cap)
    return TpuColumnarBatch(out_cols, out_rows, batch.names)


@_jax_jit
def _gather_fixed_cols(datas, valids, idx, in_rows, out_rows):
    cap = idx.shape[0]
    valid_idx = (idx >= 0) & (idx < in_rows)
    safe = jnp.where(valid_idx, idx, 0)
    mask = valid_idx & (jnp.arange(cap) < out_rows)
    out_d, out_v = [], []
    for d, v in zip(datas, valids):
        g = jnp.take(d, safe, axis=0)
        vv = mask if v is None else (jnp.take(v, safe, axis=0) & mask)
        vb = vv[:, None] if g.ndim == 2 else vv  # decimal128 limb pairs
        out_d.append(jnp.where(vb, g, jnp.zeros((), g.dtype)))
        out_v.append(vv)
    return out_d, out_v


def _gather_column(col: TpuColumnVector, safe_idx, valid, out_rows: int,
                   cap: int) -> TpuColumnVector:
    if col.children is not None:
        # struct gather = per-child gather under the struct validity
        # (cuDF gathers STRUCT columns child-wise the same way)
        v = valid
        if col.validity is not None:
            v = jnp.take(col.validity, safe_idx, axis=0) & valid
        kids = [_gather_column(c, safe_idx, valid, out_rows, cap)
                for c in col.children]
        return TpuColumnVector(col.dtype, col.data, v, out_rows,
                               children=kids)
    if col.child is not None or col.host_data is not None:
        return _gather_lists(col, safe_idx, valid, out_rows, cap)
    if col.offsets is not None:
        return _gather_strings(col, safe_idx, valid, out_rows, cap)
    data = jnp.take(col.data, safe_idx, axis=0)
    if col.validity is not None:
        v = jnp.take(col.validity, safe_idx, axis=0) & valid
    else:
        v = valid
    vb = v[:, None] if data.ndim == 2 else v  # decimal128 limb pairs
    data = jnp.where(vb, data, jnp.zeros((), data.dtype))
    return TpuColumnVector(col.dtype, data, v, out_rows)


@_jax_jit
def _gather_string_plan(offsets, safe_idx, valid):
    starts = jnp.take(offsets[:-1], safe_idx)
    ends = jnp.take(offsets[1:], safe_idx)
    lens = jnp.where(valid, ends - starts, 0)
    return starts, lens, jnp.sum(lens)


def _gather_strings(col: TpuColumnVector, safe_idx, valid, out_rows: int,
                    cap: int) -> TpuColumnVector:
    """String gather ON DEVICE via the shared ragged-gather plan
    (kernels/strings.py build_ranges): offsets math + byte movement are two
    compiled programs and ONE scalar D→H sync (the output byte capacity).
    The previous host byte-shuffle fetched the whole column per call — the
    q3 profile showed 188 s of the 361 s steady-state run inside it."""
    from ..kernels.strings import build_ranges
    starts, lens, total_dev = _gather_string_plan(col.offsets, safe_idx,
                                                  valid)
    # scalar sync: the output byte capacity is a static program shape
    out_cap = bucket_capacity(max(audited_sync_int(total_dev, "chars"), 1))
    data, new_offsets = build_ranges(col.data, starts, lens, out_cap)
    v = valid
    if col.validity is not None:
        v = jnp.take(col.validity, safe_idx) & valid
    out = TpuColumnVector(col.dtype, data, v, out_rows,
                          offsets=new_offsets)
    de = getattr(col, "dict_encoding", None)
    if de is not None:
        # the dictionary codes gather with the SAME indices (one extra
        # take), so compaction/filtering keeps the column's device
        # encoding alive for downstream group-key consumers
        codes, dcol = de
        g = jnp.where(v, jnp.take(codes, safe_idx), jnp.int32(0))
        out.dict_encoding = (g, dcol)
    return out


def decode_dictionary_column(dict_col: TpuColumnVector,
                             codes_col: TpuColumnVector, out_rows: int,
                             cap: int) -> TpuColumnVector:
    """Dictionary decode-on-read: int32 codes (null lanes zeroed) + a
    dictionary string column → the materialized string column, entirely on
    device via the shared ragged gather (ONE scalar sync for the char
    capacity). The codes ride along as the rebuilt column's
    ``dict_encoding`` so downstream group-key encoding never re-derives
    them — the reduce side of the dictionary-encoded collective exchange
    and any other consumer of (codes, dictionary) pairs decode through
    here."""
    idx = jnp.asarray(codes_col.data)[:cap].astype(jnp.int32)
    valid = row_mask(out_rows, cap)
    if codes_col.validity is not None:
        valid = codes_col.validity[:cap] & valid
    safe = jnp.clip(idx, 0, max(int(dict_col.num_rows) - 1, 0))
    out = _gather_strings(dict_col, safe, valid, out_rows, cap)
    out.dict_encoding = (jnp.where(valid, safe, jnp.int32(0)), dict_col)
    return out


def _gather_lists(col: TpuColumnVector, safe_idx, valid, out_rows: int,
                  cap: int) -> TpuColumnVector:
    """List-column gather: host-assisted via Arrow take (same status as the
    string path — offsets math is device-able, element movement awaits a Pallas
    ragged-gather kernel). Reference: cuDF gathers LIST columns natively."""
    import pyarrow as pa
    import pyarrow.compute as pc
    if not isinstance(out_rows, (int, np.integer)):
        out_rows = audited_sync_int(out_rows, "rows")  # host take needs it
    idx_np = audited_sync(safe_idx, "gather")[:cap].astype(np.int64)
    valid_np = audited_sync(valid, "gather")[:cap]
    take_idx = pa.array(np.where(valid_np, idx_np, 0)[:out_rows],
                        mask=~valid_np[:out_rows])
    taken = pc.take(col.to_arrow(), take_idx)
    out = TpuColumnVector.from_arrow(taken)
    return _repad(out, cap) if out.capacity < cap else out


@_jax_jit
def _compact_plan(mask, num_rows):
    """Stable cumsum-scatter compaction plan as ONE program (the eager chain
    paid ~4 dispatches per batch)."""
    cap = mask.shape[0]
    mask = mask & (jnp.arange(cap) < num_rows)
    positions = jnp.cumsum(mask) - 1  # output slot per kept row
    # gather indices: for each output slot, index of the kept input row
    idx = jnp.full((cap,), cap, dtype=jnp.int32)
    idx = idx.at[jnp.where(mask, positions, cap)].set(
        jnp.arange(cap, dtype=jnp.int32), mode="drop")
    return idx, jnp.sum(mask)


def deferrable(batch: TpuColumnarBatch) -> bool:
    """May this batch's compaction defer its row-count sync? Host-resident
    and nested columns need a host count to gather, so they stay eager."""
    return all(c.host_data is None and c.child is None and c.children is None
               for c in batch.columns)


def compact(batch: TpuColumnarBatch, keep_mask,
            deferred: bool = False) -> TpuColumnarBatch:
    """Filter: keep rows where mask is True, preserving order
    (reference GpuFilter: boolean mask + cudf apply_boolean_mask,
    basicPhysicalOperators.scala:638). Uses a stable cumsum-scatter.

    Default mode syncs the kept-row count to host (it becomes the new
    logical num_rows). With `deferred=True` (and a batch whose columns can
    gather under a device count — `deferrable`) the count stays a DEVICE
    scalar: the output keeps the input's bucketed padded capacity, rows
    beyond the kept count are padding with validity False, and the count
    materializes at the first consumer that needs a host int — for a
    filter→project→serialize chain that is the exchange/collect boundary,
    where it rides the batch device_get for free."""
    cap = batch.capacity
    idx, n_dev = _compact_plan(jnp.asarray(keep_mask), batch.rows_arg)
    if deferred and deferrable(batch):
        return gather(batch, idx, n_dev, out_capacity=cap)
    n_keep = audited_sync_int(n_dev, "rows")  # D→H sync: one scalar per batch
    return gather(batch, idx, n_keep, out_capacity=cap)


def slice_batch(batch: TpuColumnarBatch, start: int, length: int) -> TpuColumnarBatch:
    length = max(0, min(length, batch.num_rows - start))
    idx = jnp.arange(batch.capacity, dtype=jnp.int32) + start
    return gather(batch, idx, length, out_capacity=batch.capacity)


def materialize_row_counts(batches: List[TpuColumnarBatch]) -> None:
    """Force every pending deferred row count in the list with ONE blocking
    transfer (audited_device_get stacks the scalars into a single round
    trip). A coalesce window of N deferred batches costs one 'rows' sync,
    not N."""
    pending = [b for b in batches if b.has_pending_rows]
    if not pending:
        return
    got = audited_device_get([b._rows_dev for b in pending], "rows")
    for b, n in zip(pending, got):
        b._set_rows(int(n))


def concat_batches(batches: List[TpuColumnarBatch]) -> TpuColumnarBatch:
    """Concatenate batches (reference: cudf Table.concatenate, used by coalesce).
    Routed through Arrow host concat for ragged columns; fixed-width stays on device."""
    assert batches
    if len(batches) == 1:
        return batches[0]
    materialize_row_counts(batches)
    total = sum(b.num_rows for b in batches)
    names = batches[0].names
    out_cols: List[Optional[TpuColumnVector]] = [None] * batches[0].num_columns
    cap = bucket_capacity(total)
    offs = []
    pos = 0
    for b in batches:
        offs.append(pos)
        pos += b.num_rows
    fixed_ix = [ci for ci in range(batches[0].num_columns)
                if batches[0].columns[ci].offsets is None
                and batches[0].columns[ci].host_data is None
                and batches[0].columns[ci].child is None
                and batches[0].columns[ci].children is None]
    if fixed_ix:
        # all fixed-width columns of all batches concatenate in ONE compiled
        # scatter program; row offsets are traced so varying row counts hit
        # the same executable (each eager op is its own launch)
        col_datas = [[b.columns[ci].data for b in batches] for ci in fixed_ix]
        col_valids = [[b.columns[ci].validity for b in batches]
                      for ci in fixed_ix]
        ns = [jnp.int32(b.num_rows) for b in batches]
        offs_t = [jnp.int32(o) for o in offs]
        outs, outs_v = _concat_fixed_cols(col_datas, col_valids, ns, offs_t,
                                          jnp.int32(total), out_cap=cap)
        for ci, d, v in zip(fixed_ix, outs, outs_v):
            out_cols[ci] = TpuColumnVector(batches[0].columns[ci].dtype,
                                           d, v, total)
    for ci in range(batches[0].num_columns):
        if out_cols[ci] is None:
            import pyarrow as pa
            cols = [b.columns[ci] for b in batches]
            merged = pa.concat_arrays([c.to_arrow() for c in cols])
            out_cols[ci] = TpuColumnVector.from_arrow(merged)
    return TpuColumnarBatch(out_cols, total, names)


@_functools.partial(_jax_jit, static_argnames=("out_cap",))
def _concat_fixed_cols(col_datas, col_valids, ns, offs, total, out_cap: int):
    outs, outs_v = [], []
    mask_final = jnp.arange(out_cap) < total
    for datas, valids in zip(col_datas, col_valids):
        out = jnp.zeros((out_cap,) + datas[0].shape[1:], datas[0].dtype)
        ov = jnp.zeros((out_cap,), jnp.bool_)
        for d, v, n, off in zip(datas, valids, ns, offs):
            ar = jnp.arange(d.shape[0])
            idx = jnp.where(ar < n, ar + off, out_cap)  # OOB rows drop
            out = out.at[idx].set(d, mode="drop")
            vv = jnp.ones((d.shape[0],), jnp.bool_) if v is None else v
            ov = ov.at[idx].set(vv, mode="drop")
        outs.append(out)
        outs_v.append(ov & mask_final)
    return outs, outs_v
