"""Device-resident column vectors backed by jax.Array.

TPU analogue of the reference's `GpuColumnVector` (a Spark ColumnVector wrapping a
cuDF device column, /root/reference/sql-plugin/src/main/java/com/nvidia/spark/rapids/
GpuColumnVector.java:40). Differences driven by XLA's compilation model:

  * Static shapes: every column has a *physical capacity* (always padded to a
    power-of-two bucket, `bucket_capacity`) and a *logical* `num_rows` kept
    host-side. Rows in [num_rows, capacity) are padding and always invalid.
    cuDF kernels take dynamic sizes; XLA would recompile per size, so we bucket.
  * Validity is a dense bool array (Arrow uses bitmaps; a bool vector vectorizes
    better through XLA and converts to/from Arrow bitmaps at the host boundary).
  * Strings/binary are Arrow-style offset+data pairs (int32 offsets, uint8 bytes).
  * No refcounting: jax.Arrays are immutable and GC'd; the spill framework tracks
    byte accounting instead (see memory/).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..types import (ArrayType, BinaryType, BooleanType, DataType, DecimalType,
                     NullType, StringType, is_fixed_width)


def bucket_capacity(n: int, minimum: int = 16) -> int:
    """Round row counts up to power-of-two buckets to bound XLA recompilation."""
    cap = minimum
    while cap < n:
        cap <<= 1
    return cap


import threading as _threading


class _KeepHost(_threading.local):
    """When active, column constructors keep numpy buffers instead of
    uploading each one — the batch-level builder then ships ALL buffers in a
    single device_put (one transfer instead of one per buffer, which matters
    on high-latency links)."""
    active = False


_keep_host = _KeepHost()


def _np_to_jax(arr: np.ndarray):
    if _keep_host.active:
        return arr
    return jnp.asarray(arr)


def rebase_string_offsets(buffers, n: int, arrow_offset: int = 0,
                          copy: bool = True
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """Rebase one Arrow string/binary array's raw buffers to zero-based
    offsets + exactly the addressed bytes: `(offsets[n+1] int32 starting at
    0, chars uint8)`. A sliced Arrow array's offsets point into the PARENT
    buffer at an arbitrary base — every consumer of the raw buffers
    (device upload, vectorized hashing, decode staging) needs the same
    subtract-the-base / slice-the-bytes dance, so there is exactly one
    copy of it (`from_arrow`, `parallel/executors._string_hash_u32`).
    `buffers` is the `arr.buffers()` list ([validity, offsets, data]).
    `copy=False` returns views into the Arrow buffers (offsets still
    copied — they are rewritten in place) for transient readers that do
    not outlive the array (the hash path)."""
    offsets = np.frombuffer(buffers[1], dtype=np.int32, count=n + 1,
                            offset=arrow_offset * 4).copy()
    base = int(offsets[0])
    offsets -= base
    nbytes = int(offsets[-1])
    if not nbytes:
        return offsets, np.zeros(0, np.uint8)
    chars = np.frombuffer(buffers[2], dtype=np.uint8, count=nbytes,
                          offset=base)
    return offsets, (chars.copy() if copy else chars)


def device_layout_ok(dt: DataType) -> bool:
    """Whether a type has a device (jax.Array) layout. Structs are device-
    resident as child-column tuples (cuDF STRUCT ColumnView analogue);
    maps are offsets + a struct<key,value> child (cuDF LIST-of-STRUCT,
    exactly Spark's MapVector layout); decimal beyond precision 18 carries
    as two int64 limbs per row (kernels/decimal128.py, reference
    spark-rapids-jni DecimalUtils __int128)."""
    from ..types import MapType, StructType
    if isinstance(dt, MapType):
        return device_layout_ok(dt.key_type) \
            and device_layout_ok(dt.value_type)
    if isinstance(dt, StructType):
        return all(device_layout_ok(f.data_type) for f in dt.fields)
    if isinstance(dt, ArrayType):
        return device_layout_ok(dt.element_type)
    if isinstance(dt, DecimalType):
        return dt.precision <= DecimalType.MAX_PRECISION
    return True


@dataclass
class TpuColumnVector:
    """One device column. `data` layout by type:
       fixed-width: (capacity,) of the type's carrier dtype
       string/binary: `data` is uint8 (char_capacity,), `offsets` int32 (capacity+1,)
    Padding rows carry zeros and validity False."""

    dtype: DataType
    data: jax.Array
    validity: Optional[jax.Array]  # bool (capacity,); None == all-valid
    num_rows: int
    offsets: Optional[jax.Array] = None  # strings/binary/lists
    #: list columns only: the flattened element vector (child.num_rows == total
    #: element count == offsets[num_rows]). Mirrors cuDF's LIST column layout
    #: (a device offsets buffer + a child column) — the same offsets+data shape
    #: strings already use, generalized one level.
    child: Optional["TpuColumnVector"] = None
    #: map columns (no device layout yet): the column stays host-side as
    #: a pyarrow Array; device `data` is an empty placeholder. Host-assisted
    #: expressions consume it via to_arrow/to_pylist; gathers route through
    #: arrow take. The tagging layer prices these ops as host_assisted.
    host_data: Optional[Any] = None
    host_capacity: int = 0
    #: struct columns: one device column per field at the same capacity
    #: (cuDF STRUCT ColumnView: a validity mask over child columns). The
    #: struct's own `data` is an empty placeholder.
    children: Optional[List["TpuColumnVector"]] = None
    #: string/binary columns only: an OPTIONAL device dictionary encoding
    #: riding next to the materialized offsets+bytes — `(codes, dictionary)`
    #: where `codes` is an int32 array of this column's capacity (null and
    #: padding lanes zeroed) and `dictionary` is a plain string
    #: TpuColumnVector holding the DISTINCT values (codes preserve
    #: equality: row i == row j iff codes[i] == codes[j] under equal
    #: validity). Producers: the device parquet decoder (RLE_DICTIONARY
    #: pages — the parquet dictionary IS the encoding) and the
    #: dictionary-encoded collective exchange's decode-on-read. Consumers:
    #: group-key encoding (`execs/aggregates.encode_group_keys` and the
    #: opjit sort-plan program) use the codes directly so string-keyed
    #: aggregation needs no host dictionary pass. Best-effort cache: any
    #: transform that cannot cheaply carry it just drops it — correctness
    #: never depends on its presence.
    dict_encoding: Optional[Tuple[Any, "TpuColumnVector"]] = None

    @property
    def capacity(self) -> int:
        if self.host_data is not None:
            return self.host_capacity
        if self.offsets is not None:
            return int(self.offsets.shape[0]) - 1
        if self.children is not None:
            return self.children[0].capacity if self.children \
                else max(int(self.validity.shape[0])
                         if self.validity is not None else self.num_rows, 1)
        return int(self.data.shape[0])

    @property
    def has_nulls(self) -> bool:
        return self.validity is not None

    def validity_or_true(self) -> jax.Array:
        if self.validity is not None:
            return self.validity
        return row_mask(self.num_rows, self.capacity)

    def device_memory_size(self) -> int:
        n = self.data.size * self.data.dtype.itemsize
        if self.validity is not None:
            n += self.validity.size
        if self.offsets is not None:
            n += self.offsets.size * 4
        if self.dict_encoding is not None:
            # the codes buffer is owned per column and freed with it (a
            # spill drops the encoding); the DICTIONARY column is shared
            # across every column gathered from the same source and is
            # accounted where it is owned (e.g. the exchange's spillable
            # dictionary batch), so only the codes count here
            codes = self.dict_encoding[0]
            n += codes.size * codes.dtype.itemsize
        if self.child is not None:
            n += self.child.device_memory_size()
        if self.children is not None:
            n += sum(c.device_memory_size() for c in self.children)
        return int(n)

    # ---- host materialization (the D→H boundary) ----
    def _host_rows(self) -> int:
        """num_rows as a host int (columns inside a deferred-compaction
        batch carry a device scalar until the batch materializes)."""
        n = self.num_rows
        if not isinstance(n, (int, np.integer)):
            n = audited_sync_int(n, "rows")
            self.num_rows = n
        return int(n)

    def to_numpy(self) -> np.ndarray:
        """Logical values as a numpy array; nulls surfaced via to_arrow instead."""
        return audited_sync(self.data[: self._host_rows()], "fetch")

    def to_arrow(self):
        import pyarrow as pa
        from ..types import to_arrow as t2a
        n = self._host_rows()
        if self.host_data is not None:
            return self.host_data.slice(0, n) if len(self.host_data) > n \
                else self.host_data
        if self.validity is not None:
            valid = audited_sync(self.validity[:n], "fetch")
            mask = ~valid
        else:
            mask = None
        if self.children is not None:
            from ..types import StructType as _St
            fields = self.dtype.fields
            kids = [c.to_arrow() for c in self.children]
            kids = [k.combine_chunks() if isinstance(k, pa.ChunkedArray)
                    else k for k in kids]
            if mask is not None:
                bitmap = pa.py_buffer(np.packbits(
                    valid, bitorder="little").tobytes())
                nulls = int(mask.sum())
            else:
                bitmap, nulls = None, 0
            atype = pa.struct([(f.name, k.type)
                               for f, k in zip(fields, kids)])
            return pa.Array.from_buffers(atype, n, [bitmap],
                                         null_count=nulls, children=kids)
        from ..types import MapType as _Mt
        if isinstance(self.dtype, _Mt):
            offs = audited_sync(self.offsets[: n + 1],
                                "fetch").astype(np.int32)
            n_elems = int(offs[-1]) if n else 0
            keys = self.child.children[0].to_arrow()
            items = self.child.children[1].to_arrow()
            if len(keys) != n_elems:
                keys = keys.slice(0, n_elems)
            if len(items) != n_elems:
                items = items.slice(0, n_elems)
            if mask is not None:
                bitmap = pa.py_buffer(np.packbits(
                    valid, bitorder="little").tobytes())
                nulls = int(mask.sum())
            else:
                bitmap, nulls = None, 0
            atype = pa.map_(keys.type, items.type)
            entries = pa.StructArray.from_arrays(
                [keys, items],
                fields=[pa.field("key", keys.type, nullable=False),
                        pa.field("value", items.type, nullable=True)])
            return pa.Array.from_buffers(
                atype, n, [bitmap, pa.py_buffer(offs.tobytes())],
                null_count=nulls, children=[entries])
        if isinstance(self.dtype, ArrayType):
            offs = audited_sync(self.offsets[: n + 1],
                                "fetch").astype(np.int32)
            n_elems = int(offs[-1]) if n else 0
            elems = self.child.to_arrow() if self.child.num_rows == n_elems else \
                self.child.to_arrow().slice(0, n_elems)
            if mask is not None:
                bitmap = pa.py_buffer(np.packbits(valid, bitorder="little").tobytes())
                nulls = int(mask.sum())
            else:
                bitmap, nulls = None, 0
            atype = pa.list_(elems.type)
            return pa.Array.from_buffers(
                atype, n, [bitmap, pa.py_buffer(offs.tobytes())],
                null_count=nulls, children=[elems])
        if isinstance(self.dtype, (StringType, BinaryType)):
            offs = audited_sync(self.offsets[: n + 1],
                                "fetch").astype(np.int32)
            chars = audited_sync(self.data[: int(offs[-1])],
                                 "fetch").tobytes() if n else b""
            buf_offs = pa.py_buffer(offs.tobytes())
            buf_data = pa.py_buffer(chars)
            if mask is not None:
                bitmap = pa.py_buffer(np.packbits(valid, bitorder="little").tobytes())
                nulls = int(mask.sum())
            else:
                bitmap, nulls = None, 0
            atype = pa.string() if isinstance(self.dtype, StringType) else pa.binary()
            return pa.Array.from_buffers(atype, n, [bitmap, buf_offs, buf_data], null_count=nulls)
        vals = audited_sync(self.data[:n], "fetch")
        if isinstance(self.dtype, DecimalType):
            import decimal as _d
            scale = self.dtype.scale
            if vals.ndim == 2:  # two-limb decimal128 carrier
                from ..kernels.decimal128 import limbs_to_int, scaled_decimal
                py = [None if (mask is not None and mask[i]) else
                      scaled_decimal(limbs_to_int(vals[i, 0], vals[i, 1]),
                                     scale)
                      for i in range(n)]
                return pa.array(py, type=t2a(self.dtype))
            # int64-scaled carrier -> arrow decimal128
            py = [None if (mask is not None and mask[i]) else
                  _d.Decimal(int(vals[i])).scaleb(-scale) for i in range(n)]
            return pa.array(py, type=t2a(self.dtype))
        arrow_type = t2a(self.dtype)
        return pa.array(vals, type=arrow_type, mask=mask)

    def to_pylist(self):
        return self.to_arrow().to_pylist()

    # ---- constructors ----
    @staticmethod
    def from_numpy(dtype: DataType, values: np.ndarray,
                   validity: Optional[np.ndarray] = None,
                   capacity: Optional[int] = None) -> "TpuColumnVector":
        n = len(values)
        cap = capacity if capacity is not None else bucket_capacity(n)
        carrier = dtype.np_dtype
        buf = np.zeros(cap, dtype=carrier)
        buf[:n] = values.astype(carrier, copy=False)
        vmask = None
        if validity is not None and not validity.all():
            v = np.zeros(cap, dtype=bool)
            v[:n] = validity
            vmask = _np_to_jax(v)
        return TpuColumnVector(dtype, _np_to_jax(buf), vmask, n)

    @staticmethod
    def from_strings(dtype: DataType, offsets: np.ndarray, chars: np.ndarray,
                     validity: Optional[np.ndarray] = None,
                     capacity: Optional[int] = None,
                     char_capacity: Optional[int] = None) -> "TpuColumnVector":
        n = len(offsets) - 1
        cap = capacity if capacity is not None else bucket_capacity(n)
        ccap = char_capacity if char_capacity is not None else bucket_capacity(
            max(int(offsets[-1]), 1))
        obuf = np.full(cap + 1, offsets[-1], dtype=np.int32)
        obuf[: n + 1] = offsets
        cbuf = np.zeros(ccap, dtype=np.uint8)
        cbuf[: int(offsets[-1])] = chars[: int(offsets[-1])]
        vmask = None
        if validity is not None and not validity.all():
            v = np.zeros(cap, dtype=bool)
            v[:n] = validity
            vmask = _np_to_jax(v)
        return TpuColumnVector(dtype, _np_to_jax(cbuf), vmask, n, offsets=_np_to_jax(obuf))

    @staticmethod
    def from_arrow(arr) -> "TpuColumnVector":
        """Host Arrow array → device column (the H→D upload)."""
        import pyarrow as pa
        from ..types import from_arrow as a2t
        dtype = a2t(arr.type)
        arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
        n = len(arr)
        if not device_layout_ok(dtype):
            return TpuColumnVector(dtype, jnp.zeros((0,), jnp.int8), None, n,
                                   host_data=arr,
                                   host_capacity=bucket_capacity(n))
        if arr.null_count:
            validity = np.asarray(arr.is_valid())
        else:
            validity = None
        from ..types import StructType as _St
        if isinstance(dtype, _St):
            # struct = validity over per-field child columns (cuDF STRUCT)
            cap = bucket_capacity(n)
            kids = []
            for i in range(arr.type.num_fields):
                kid = TpuColumnVector.from_arrow(arr.field(i))
                if kid.capacity != cap:
                    from .batch import _repad
                    kid = _repad(kid, cap)
                kids.append(kid)
            vmask = None
            if validity is not None and not validity.all():
                v = np.zeros(cap, dtype=bool)
                v[:n] = validity
                vmask = _np_to_jax(v)
            return TpuColumnVector(dtype, jnp.zeros((0,), jnp.int8), vmask,
                                   n, children=kids)
        from ..types import MapType as _Mt, StructField as _Sf
        if isinstance(dtype, _Mt):
            # map = offsets + struct<key,value> child (cuDF LIST-of-STRUCT)
            bufs = arr.buffers()
            off0 = arr.offset
            offsets = np.frombuffer(bufs[1], dtype=np.int32,
                                    count=n + 1, offset=off0 * 4).copy()
            base = int(offsets[0])
            offsets -= base
            n_elems = int(offsets[-1])
            entry_t = _St([_Sf("key", dtype.key_type, False),
                           _Sf("value", dtype.value_type,
                               dtype.value_contains_null)])
            kcol = TpuColumnVector.from_arrow(
                arr.keys.slice(base, n_elems))
            vcol = TpuColumnVector.from_arrow(
                arr.items.slice(base, n_elems))
            ecap = max(kcol.capacity, vcol.capacity)
            from .batch import _repad
            if kcol.capacity != ecap:
                kcol = _repad(kcol, ecap)
            if vcol.capacity != ecap:
                vcol = _repad(vcol, ecap)
            child = TpuColumnVector(entry_t, jnp.zeros((0,), jnp.int8),
                                    None, n_elems, children=[kcol, vcol])
            cap = bucket_capacity(n)
            obuf = np.full(cap + 1, n_elems, dtype=np.int32)
            obuf[: n + 1] = offsets
            vmask = None
            if validity is not None and not validity.all():
                v = np.zeros(cap, dtype=bool)
                v[:n] = validity
                vmask = _np_to_jax(v)
            return TpuColumnVector(dtype, kcol.data, vmask, n,
                                   offsets=_np_to_jax(obuf), child=child)
        if isinstance(dtype, ArrayType):
            if pa.types.is_large_list(arr.type):
                arr = arr.cast(pa.list_(arr.type.value_type))
            bufs = arr.buffers()
            off0 = arr.offset
            offsets = np.frombuffer(bufs[1], dtype=np.int32,
                                    count=n + 1, offset=off0 * 4).copy()
            base = int(offsets[0])
            offsets -= base
            n_elems = int(offsets[-1])
            values = arr.values.slice(base, n_elems)
            child = TpuColumnVector.from_arrow(values)
            cap = bucket_capacity(n)
            obuf = np.full(cap + 1, n_elems, dtype=np.int32)
            obuf[: n + 1] = offsets
            vmask = None
            if validity is not None and not validity.all():
                v = np.zeros(cap, dtype=bool)
                v[:n] = validity
                vmask = _np_to_jax(v)
            return TpuColumnVector(dtype, child.data, vmask, n,
                                   offsets=_np_to_jax(obuf), child=child)
        if isinstance(dtype, (StringType, BinaryType)):
            if pa.types.is_large_string(arr.type) or pa.types.is_large_binary(arr.type):
                arr = arr.cast(pa.string() if isinstance(dtype, StringType) else pa.binary())
            offsets, chars = rebase_string_offsets(arr.buffers(), n,
                                                   arr.offset)
            if validity is not None:
                # zero out data regions of null rows? keep: gathers only read valid rows
                pass
            return TpuColumnVector.from_strings(dtype, offsets, chars,
                                                validity)
        if isinstance(dtype, NullType):
            buf = np.zeros(n, dtype=bool)
            return TpuColumnVector.from_numpy(dtype, buf, np.zeros(n, dtype=bool))
        if isinstance(dtype, DecimalType):
            if dtype.precision > DecimalType.MAX_DEVICE_PRECISION:
                # two-limb carrier: (capacity, 2) int64 [hi, lo]
                from ..kernels.decimal128 import pack, unscaled_int
                unscaled = [0 if v is None else unscaled_int(v, dtype.scale)
                            for v in arr.to_pylist()]
                limbs = pack(unscaled)
                cap = bucket_capacity(n)
                buf = np.zeros((cap, 2), np.int64)
                buf[:n] = limbs
                vmask = None
                if validity is not None and not validity.all():
                    v = np.zeros(cap, dtype=bool)
                    v[:n] = validity
                    vmask = _np_to_jax(v)
                return TpuColumnVector(dtype, _np_to_jax(buf), vmask, n)
            scaled = np.array(
                [0 if v is None else int(v.scaleb(dtype.scale)) for v in arr.to_pylist()],
                dtype=np.int64)
            return TpuColumnVector.from_numpy(dtype, scaled, validity)
        carrier = dtype.np_dtype
        if pa.types.is_boolean(arr.type):
            np_arr = np.asarray(arr.fill_null(False).to_numpy(zero_copy_only=False))
        else:
            # read the raw fixed-width values buffer: exact (to_numpy would route
            # nullable ints through float64, corrupting large int64 values)
            bufs = arr.buffers()
            phys = np.dtype(arr.type.to_pandas_dtype()) if not pa.types.is_timestamp(arr.type) \
                else np.dtype(np.int64)
            if pa.types.is_date32(arr.type):
                phys = np.dtype(np.int32)
            np_arr = np.frombuffer(bufs[1], dtype=phys, count=n,
                                   offset=arr.offset * phys.itemsize).copy()
            if validity is not None:
                np_arr[~validity] = 0
            np_arr = np_arr.astype(carrier, copy=False)
        return TpuColumnVector.from_numpy(dtype, np_arr, validity)

    @staticmethod
    def from_scalar(value: Any, dtype: DataType, num_rows: int,
                    capacity: Optional[int] = None) -> "TpuColumnVector":
        cap = capacity if capacity is not None else bucket_capacity(num_rows)
        if not device_layout_ok(dtype):
            import pyarrow as pa
            from ..types import to_arrow as t2a
            pa_arr = pa.array([value] * num_rows, type=t2a(dtype))
            return TpuColumnVector(dtype, jnp.zeros((0,), jnp.int8), None,
                                   num_rows, host_data=pa_arr, host_capacity=cap)
        from ..types import StructType as _St
        if isinstance(dtype, _St):
            import pyarrow as pa
            from ..types import to_arrow as t2a
            from .batch import _repad
            pa_arr = pa.array([value] * num_rows, type=t2a(dtype))
            col = TpuColumnVector.from_arrow(pa_arr)
            return _repad(col, cap) if col.capacity < cap else col
        if isinstance(dtype, ArrayType):
            import pyarrow as pa
            from ..types import to_arrow as t2a
            pa_arr = pa.array([value] * num_rows, type=t2a(dtype))
            col = TpuColumnVector.from_arrow(pa_arr)
            if col.capacity < cap:
                pad = cap - col.capacity
                offs = jnp.concatenate(
                    [col.offsets, jnp.full((pad,), col.offsets[-1], jnp.int32)])
                validity = col.validity
                if validity is not None:
                    validity = jnp.concatenate([validity, jnp.zeros((pad,), jnp.bool_)])
                col = TpuColumnVector(dtype, col.data, validity, num_rows,
                                      offsets=offs, child=col.child)
            return col
        if isinstance(dtype, (StringType, BinaryType)):
            if value is None:
                offs = np.zeros(num_rows + 1, dtype=np.int32)
                return TpuColumnVector.from_strings(
                    dtype, offs, np.zeros(0, np.uint8),
                    np.zeros(num_rows, dtype=bool), capacity=cap)
            raw = value.encode() if isinstance(value, str) else bytes(value)
            offs = (np.arange(num_rows + 1, dtype=np.int32) * len(raw))
            chars = np.tile(np.frombuffer(raw, dtype=np.uint8), max(num_rows, 1))
            return TpuColumnVector.from_strings(dtype, offs, chars, None, capacity=cap)
        dec128 = (isinstance(dtype, DecimalType)
                  and dtype.precision > DecimalType.MAX_DEVICE_PRECISION)
        if value is None:
            if dec128:
                buf = np.zeros((cap, 2), np.int64)
                v = np.zeros(cap, dtype=bool)
                return TpuColumnVector(dtype, _np_to_jax(buf), _np_to_jax(v),
                                       num_rows)
            buf = np.zeros(num_rows, dtype=dtype.np_dtype or np.bool_)
            return TpuColumnVector.from_numpy(dtype, buf,
                                              np.zeros(num_rows, dtype=bool), capacity=cap)
        if isinstance(dtype, DecimalType):
            from ..kernels.decimal128 import unscaled_int
            value = unscaled_int(value, dtype.scale)
            if dec128:
                from ..kernels.decimal128 import int_to_limbs
                buf = np.zeros((cap, 2), np.int64)
                buf[:num_rows] = int_to_limbs(value)
                return TpuColumnVector(dtype, _np_to_jax(buf), None, num_rows)
        buf = np.full(num_rows, value, dtype=dtype.np_dtype)
        return TpuColumnVector.from_numpy(dtype, buf, None, capacity=cap)


def row_mask(num_rows: int, capacity: int) -> jax.Array:
    """Mask that is True for logical rows, False for padding."""
    return jnp.arange(capacity) < num_rows


# ---------------------------------------------------------------------------
# the audited device→host sync gate (profiling sync ledger)
#
# Every BLOCKING device→host transfer in execs/ and shuffle/ must route
# through one of these three helpers: each records itself in the process-wide
# sync ledger (profiling.SyncLedger) under the active operator scope, so a
# per-batch sync regression is visible in metrics and bench output instead
# of only in wall time. tracelint rule TL011 statically flags raw
# np.asarray/.item()/jax.device_get on device values outside this gate.
# ---------------------------------------------------------------------------


def audited_sync(value, kind: str = "fetch") -> np.ndarray:
    """np.asarray of a (possibly device) array through the ledger. Free for
    values already on host."""
    if isinstance(value, np.ndarray):
        return value
    from ..profiling import record_sync
    record_sync(kind)
    return np.asarray(value)


def audited_sync_int(value, kind: str = "scalar") -> int:
    """int() of a device scalar through the ledger (the compaction/join
    count syncs)."""
    if isinstance(value, (int, np.integer)):
        return int(value)
    from ..profiling import record_sync
    record_sync(kind)
    return int(value)


def audited_device_get(leaves, kind: str = "batch"):
    """ONE jax.device_get for a list of device buffers through the ledger
    (batch materialization: the whole transfer is a single blocking round
    trip regardless of leaf count, so it records as ONE sync)."""
    from ..profiling import record_sync
    record_sync(kind)
    return jax.device_get(leaves)


@dataclass(frozen=True)
class TpuScalar:
    """Device scalar (reference: cudf Scalar). value is a python value; nulls allowed."""
    dtype: DataType
    value: Any  # None == null

    @property
    def is_null(self) -> bool:
        return self.value is None
