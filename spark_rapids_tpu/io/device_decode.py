"""Device-side Parquet decode: host stages raw page bytes, TPU decodes.

Reference: `GpuParquetScan.scala:1983,2506` — the plugin parses the footer
and walks page headers on HOST, acquires the GPU semaphore, then hands the
(decompressed) column-chunk bytes to cuDF's device page decoders in chunked
batches. This module is the TPU analogue for the flat fixed-width column
classes:

* the host does ONLY O(pages)+O(runs) work — footer/row-group metadata
  (via pyarrow), a minimal Thrift-compact page-header walk, snappy/zstd/gzip
  page decompression, and the RLE/bit-packed hybrid *run-header* walk
  (varint headers; a handful per page) — plus the per-page non-null counts
  needed to place runs in the dense value stream;
* every O(rows) transform (bit-unpacking, run expansion, dictionary gather,
  definition-level → validity, null compaction into the padded batch
  layout, PLAIN reinterpret) runs on device via kernels/parquet_decode.py,
  fused into **one cached program dispatch per row group** — programs are
  cached `opjit`-style, keyed by the per-column (encoding kind, physical
  type, bit layout) spec plus bucketed buffer shapes, and each dispatch is
  recorded under the ``parquet_decode`` kind in the process-wide dispatch
  accounting (`opjit.cache_stats()["calls_by_kind"]`);
* the walk also records what layout it saw, and the spec carries it as
  static structure: an index stream of bit-packed literal runs only is
  staged without its run headers and unpacked with static shifts, one with
  RLE runs among them as the same literal stream plus an O(runs) boundary
  table (`_mixed_segments`), PLAIN values go over as uint32 words,
  "dictionary pages, then PLAIN pages" is placed by position, and a
  REQUIRED string column whose dictionary entries all have one length
  takes its chars from the dictionary's matrix — the program searches per
  element only where the layout leaves no other way (definition levels,
  booleans, ragged strings, interleaved pages), and never because of a
  conf or a column name (`decode_stats()["dense_values"]` of `["values"]`
  says how often, `["general_<reason>"]` why not);
* BYTE_ARRAY string/binary columns decode into the engine's own
  offsets+bytes device layout (`columnar/vector.py`): PLAIN pages walk
  their 4-byte length prefixes host-side into per-value (start, length)
  tables (vectorized pointer-doubling — no per-value Python), dictionary
  pages ship the raw dictionary bytes plus the index stream, and the
  device program cumsums row lengths into the int32 offsets vector and
  byte-gathers the char buffer (`kernels/parquet_decode.string_offsets` /
  `gather_string_bytes`). RLE_DICTIONARY string columns additionally
  surface the parquet dictionary as a device `dict_encoding`
  (codes + dictionary column) so downstream group-by key encoding
  consumes the codes without a host dictionary pass;
* columns the device path cannot decode (nested, INT96,
  FIXED_LEN_BYTE_ARRAY, unsupported encodings/codecs, mid-chunk
  dictionary fallback) decode on host via pyarrow for just that column
  and zip into the same `TpuColumnarBatch` — the per-column fallback the
  meta/typecheck machinery already expresses for expressions, applied to
  scans (`spark.rapids.tpu.parquet.deviceDecode.enabled`, per-column
  auto-demotion).

Robustness: staged bytes route through the `FileCache` range reader (chaos site
``scan.read``); structural checks (thrift bounds, decompressed-size,
value-region-length, row-count) convert corrupt/truncated pages into
`DeviceDecodeError`, which the scan heals by re-reading the file on host —
never wrong data. Encrypted files (PARE footer magic, or an
``encryption_algorithm`` field in a plaintext footer) raise
`ParquetEncryptedException` with the reference's message semantics
(`GpuParquetScan.scala:590`).
"""

from __future__ import annotations

import struct
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..columnar.vector import TpuColumnVector, bucket_capacity
from ..obs import tracer as _obs
from ..types import (BinaryType, BooleanType, ByteType, DataType, DateType,
                     DoubleType, FloatType, IntegerType, LongType, ShortType,
                     StringType, TimestampType, from_arrow as arrow_to_type,
                     to_arrow as type_to_arrow)


class ParquetEncryptedException(RuntimeError):
    """Encrypted parquet input: the device decoder (like the reference GPU
    reader) does not support encryption — reference message semantics,
    GpuParquetScan.scala:590."""


class DeviceDecodeError(RuntimeError):
    """This file/column cannot (or should not) decode on device; the scan
    falls back to the host pyarrow path with identical results."""


# ---------------------------------------------------------------------------
# dispatch/fallback accounting (bench + tests assert O(row-groups) launches)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_STATS: Dict[str, int] = {
    "dispatches": 0,        # one per decoded row group (the launch count)
    "programs": 0,          # distinct compiled decode programs
    "row_groups": 0,
    "rows": 0,
    "values": 0,            # decoded values: rows x device columns
    "dense_values": 0,      # ... of those, decoded with no per-element search
                            # (dense or mixed index streams, PLAIN values of
                            # REQUIRED columns; a string column only where
                            # its chars took none either)
    "bytes_staged": 0,      # raw page bytes shipped to HBM
    "device_columns": 0,
    "fallback_columns": 0,     # per-column host demotions
    "fallback_row_groups": 0,  # per-row-group host re-reads (decode errors)
    "fallback_files": 0,       # whole-file host fallbacks
}
#: why a device column stayed on the general side (a search an element for
#: its levels, its index stream or its chars), one reason a column, the first
#: that applies; counted a decoded row group as `general_<reason>`
GENERAL_REASONS = (
    "nullable",                    # definition levels expand by run table
    "boolean",                     # bit-packed / RLE values by run table
    "interleaved_plain",           # dictionary and PLAIN pages interleaved
    "truncated_run",               # a literal run the page end cuts short
    "uneven_segments",             # literal payloads too scattered to pad
    "variable_length_dictionary",  # ragged chars: cumsum + byte search
    "plain_strings",               # PLAIN BYTE_ARRAY pages: ragged chars
)
_STATS.update({"general_" + r: 0 for r in GENERAL_REASONS})
_PROGRAMS: "OrderedDict[Tuple, Any]" = OrderedDict()
_PROGRAM_CACHE_MAX = 64


def decode_stats() -> Dict[str, int]:
    with _LOCK:
        return dict(_STATS)


def reset_for_tests() -> None:
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0
        _PROGRAMS.clear()


def _bump(key: str, n: int = 1) -> None:
    with _LOCK:
        _STATS[key] += n


# ---------------------------------------------------------------------------
# minimal Thrift compact-protocol reader (parquet page headers + footer).
# Bounds violations raise IndexError/struct.error — callers convert to
# DeviceDecodeError so a truncated/corrupt page heals via host fallback.
# ---------------------------------------------------------------------------


def _varint(buf, pos: int) -> Tuple[int, int]:
    out = sh = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << sh
        if not (b & 0x80):
            return out, pos
        sh += 7
        if sh > 63:
            raise ValueError("varint overflow")


def _zigzag(v: int) -> int:
    return (v >> 1) ^ -(v & 1)


def _read_value(buf, pos: int, ctype: int):
    if ctype == 1:
        return True, pos
    if ctype == 2:
        return False, pos
    if ctype == 3:
        return buf[pos], pos + 1
    if ctype in (4, 5, 6):  # i16/i32/i64
        v, pos = _varint(buf, pos)
        return _zigzag(v), pos
    if ctype == 7:  # double
        return struct.unpack_from("<d", buf, pos)[0], pos + 8
    if ctype == 8:  # binary
        n, pos = _varint(buf, pos)
        if n < 0 or pos + n > len(buf):
            raise ValueError("binary field out of bounds")
        return bytes(buf[pos:pos + n]), pos + n
    if ctype in (9, 10):  # list/set
        h = buf[pos]
        pos += 1
        n, et = h >> 4, h & 0x0F
        if n == 15:
            n, pos = _varint(buf, pos)
        out = []
        for _ in range(n):
            v, pos = _read_value(buf, pos, et)
            out.append(v)
        return out, pos
    if ctype == 11:  # map
        n, pos = _varint(buf, pos)
        if n == 0:
            return {}, pos
        h = buf[pos]
        pos += 1
        out = {}
        for _ in range(n):
            k, pos = _read_value(buf, pos, h >> 4)
            v, pos = _read_value(buf, pos, h & 0x0F)
            out[k] = v
        return out, pos
    if ctype == 12:
        return _read_struct(buf, pos)
    raise ValueError(f"thrift compact type {ctype}")


def _read_struct(buf, pos: int) -> Tuple[Dict[int, Any], int]:
    """Generic struct → {field id: value}; unknown fields parse and keep."""
    fields: Dict[int, Any] = {}
    fid = 0
    while True:
        h = buf[pos]
        pos += 1
        if h == 0:
            return fields, pos
        delta, ctype = h >> 4, h & 0x0F
        if delta:
            fid += delta
        else:
            v, pos = _varint(buf, pos)
            fid = _zigzag(v)
        val, pos = _read_value(buf, pos, ctype)
        fields[fid] = val


# ---------------------------------------------------------------------------
# encrypted-parquet detection (reference GpuParquetScan.scala:590)
# ---------------------------------------------------------------------------

_MAGIC_PLAIN = b"PAR1"
_MAGIC_ENCRYPTED = b"PARE"
#: parquet.thrift FileMetaData field 8 = encryption_algorithm (plaintext
#: footer mode: the footer parses but column chunks are encrypted)
_FMD_ENCRYPTION_ALGORITHM = 8


def detect_encryption(path: str) -> Optional[str]:
    """Return a human-readable reason when `path` is an encrypted parquet
    file (encrypted-footer PARE magic, or plaintext-footer crypto
    metadata), None for ordinary files. Unreadable/short files return None —
    later stages produce their own errors."""
    import os
    try:
        size = os.path.getsize(path)
        if size < 12:
            return None
        with open(path, "rb") as f:
            f.seek(size - 8)
            tail = f.read(8)
            if tail[4:] == _MAGIC_ENCRYPTED:
                return "encrypted footer (PARE magic)"
            if tail[4:] != _MAGIC_PLAIN:
                return None
            flen = struct.unpack("<I", tail[:4])[0]
            if flen <= 0 or flen > size - 8:
                return None
            f.seek(size - 8 - flen)
            footer = f.read(flen)
        fmd, _ = _read_struct(footer, 0)
        if _FMD_ENCRYPTION_ALGORITHM in fmd:
            return ("columns encrypted with plaintext footer "
                    "(encryption_algorithm set)")
    except Exception:  # noqa: BLE001 — detection must never mask real reads
        return None
    return None


def encrypted_message(path: str, reason: str) -> str:
    """Reference message semantics: name the file, the reason, and the CPU
    fallback (GpuParquetScan.scala:590 'The GPU does not support reading
    encrypted Parquet files')."""
    return (f"The TPU does not support reading encrypted Parquet files: "
            f"{path} is encrypted ({reason}). To read this file, fall back "
            f"to the CPU by setting spark.rapids.sql.enabled=false (or "
            f"spark.rapids.sql.format.parquet.enabled=false) and configure "
            f"decryption keys for the CPU reader.")


# ---------------------------------------------------------------------------
# RLE / bit-packed hybrid run-header walk (host: O(runs), tiny)
# ---------------------------------------------------------------------------

from ..kernels.parquet_decode import (BOUND_BASE, BOUND_POS, BOUND_ROWS,
                                      BOUND_STEP, BOUND_VALUE, RUN_BITOFF,
                                      RUN_COLS, RUN_LITERAL, RUN_PAD_START,
                                      RUN_START, RUN_VALUE, RUN_WIDTH)

#: host-only sixth field of a walked run: the slots (values, padding of the
#: last group included) its payload holds; 0 marks a literal run whose payload
#: the page end cuts short
RUN_SLOTS = RUN_COLS


def _walk_runs(data, start: int, end: int, bw: int, n: int,
               out_base: int, bit_base: int) -> List[List[int]]:
    """Walk hybrid run headers in data[start:end) covering `n` values.
    Returns run-table rows [out_start, abs_bitoff, value, literal, width]
    with output positions offset by `out_base` and literal bit offsets by
    `bit_base` (both in the staged, concatenated buffers), each followed by
    what the header said of the run's extent (RUN_SLOTS)."""
    runs: List[List[int]] = []
    out = 0
    vbytes = (bw + 7) // 8
    pos = start
    while out < n and pos < end:
        h, pos = _varint(data, pos)
        if h & 1:  # bit-packed literal groups of 8
            cnt = (h >> 1) * 8
            if cnt <= 0:
                raise ValueError("zero-length literal run")
            nbytes = (cnt * bw + 7) // 8
            runs.append([out_base + out, bit_base + (pos - start) * 8,
                         0, 1, bw, cnt if pos + nbytes <= end else 0])
            pos += nbytes
        else:
            cnt = h >> 1
            if cnt <= 0:
                raise ValueError("zero-length RLE run")
            if pos + vbytes > end:
                raise ValueError("RLE run value out of bounds")
            v = int.from_bytes(data[pos:pos + vbytes], "little") \
                if vbytes else 0
            pos += vbytes
            runs.append([out_base + out, 0, v, 0, 0, cnt])
        out += cnt
    if out < n:
        raise ValueError(f"runs cover {out} of {n} values")
    return runs


def _count_valid(data, start: int, end: int, n: int) -> int:
    """Non-null count for one page's definition levels (bit width 1: flat
    columns only) WITHOUT expanding: RLE runs count directly, literal runs
    popcount their bit-packed bytes — O(levels bytes) ~ rows/8."""
    total = 0
    out = 0
    pos = start
    while out < n and pos < end:
        h, pos = _varint(data, pos)
        if h & 1:
            cnt = (h >> 1) * 8
            take = min(cnt, n - out)
            nbytes = (cnt + 7) // 8
            bits = np.unpackbits(
                np.frombuffer(data, np.uint8, count=nbytes, offset=pos),
                bitorder="little")[:take]
            total += int(bits.sum())
            pos += nbytes
        else:
            cnt = h >> 1
            v = data[pos]
            pos += 1
            if v:
                total += min(cnt, n - out)
        out += cnt
    return total


def _byte_array_starts(region: np.ndarray,
                       n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Value start positions + byte lengths of `n` length-prefixed
    BYTE_ARRAY values in `region` (a PLAIN data-page value region or a
    dictionary page), without a per-value Python loop: the next-value map
    (pos → pos + 4 + le32(pos)) is built for every byte position
    vectorized, then the set of value starts doubles each pass (pointer
    jumping: after pass k the first 2^k starts are known — log2(n)
    vectorized gathers total). A chain that runs out of bounds (bogus
    length, truncated region) raises ValueError."""
    if n <= 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    m = len(region)
    if m < 4:
        raise ValueError("BYTE_ARRAY region too short")
    r = region.astype(np.int64)
    le = r[: m - 3] | (r[1: m - 2] << 8) | (r[2: m - 1] << 16) \
        | (r[3:] << 24)
    # positions past m-4 have no readable prefix: they map to the sentinel
    # m, where the jump table is a fixed point — a broken chain parks there
    nxt = np.minimum(np.arange(m - 3, dtype=np.int64) + 4 + le, m)
    nxt = np.concatenate([nxt, np.full(4, m, np.int64)])  # index m valid
    starts = np.zeros(1, np.int64)
    jump = nxt
    while len(starts) < n:
        take = min(len(starts), n - len(starts))
        if int(starts[:take].max(initial=0)) >= m:
            raise ValueError("BYTE_ARRAY values overrun the page")
        starts = np.concatenate([starts, jump[starts[:take]]])
        if len(starts) < n:
            jump = jump[jump]
    starts = starts[:n]
    if int(starts.max()) > m - 4:
        raise ValueError("BYTE_ARRAY values overrun the page")
    lengths = le[starts]
    if int((starts + 4 + lengths).max()) > m:
        raise ValueError("BYTE_ARRAY value out of bounds")
    return starts, lengths


def _accum_index_counts(data, start: int, end: int, bw: int, n: int,
                        counts: np.ndarray) -> None:
    """Histogram one page's dictionary indices (RLE / bit-packed hybrid
    region) into `counts` — O(region bytes) vectorized, no device round
    trip. The exact output char total (counts · dictionary lengths) sizes
    the staged string char buffer, so the one decode dispatch per row
    group keeps a static shape. An index outside the dictionary raises
    (the device expansion would gather garbage bytes)."""
    n_dict = len(counts)
    out = 0
    vbytes = (bw + 7) // 8
    pos = start
    while out < n and pos < end:
        h, pos = _varint(data, pos)
        if h & 1:
            cnt = (h >> 1) * 8
            take = min(cnt, n - out)
            nbytes = (cnt * bw + 7) // 8
            if bw:
                bits = np.unpackbits(
                    np.frombuffer(data, np.uint8, count=nbytes, offset=pos),
                    bitorder="little")
                vals = bits[: take * bw].reshape(take, bw).astype(np.int64) \
                    @ (np.int64(1) << np.arange(bw, dtype=np.int64))
                if take and int(vals.max()) >= n_dict:
                    raise ValueError("dictionary index out of range")
                np.add.at(counts, vals, 1)
            else:
                counts[0] += take
            pos += nbytes
        else:
            cnt = h >> 1
            v = int.from_bytes(data[pos: pos + vbytes], "little") \
                if vbytes else 0
            pos += vbytes
            take = min(cnt, n - out)
            if take:
                if v >= n_dict:
                    raise ValueError("dictionary index out of range")
                counts[v] += take
        out += cnt


# ---------------------------------------------------------------------------
# per-column decode plans (eligibility) and staged buffers
# ---------------------------------------------------------------------------

#: physical type → (itemsize, value kind) for PLAIN/dictionary values
_PHYS_FIXED = {"INT32": (4, "i"), "INT64": (8, "i"),
               "FLOAT": (4, "f"), "DOUBLE": (8, "f")}

_SUPPORTED_ENCODINGS = {"PLAIN", "RLE", "PLAIN_DICTIONARY", "RLE_DICTIONARY"}

_CODECS = {"UNCOMPRESSED": None, "SNAPPY": "snappy", "ZSTD": "zstd",
           "GZIP": "gzip", "BROTLI": "brotli", "LZ4": "lz4_raw",
           "LZ4_RAW": "lz4_raw"}

_INT_RANK = {ByteType: 0, ShortType: 1, IntegerType: 2, LongType: 3}

#: thrift page types / encodings
_PAGE_DATA_V1, _PAGE_INDEX, _PAGE_DICT, _PAGE_DATA_V2 = 0, 1, 2, 3
_ENC_PLAIN, _ENC_PLAIN_DICT, _ENC_RLE, _ENC_RLE_DICT = 0, 2, 3, 8


def _cast_ok(src: DataType, dst: DataType) -> bool:
    """Value-preserving device cast from the file's column type to the
    scan's output attribute type (mirrors the host path's .cast(schema))."""
    if type(src) is type(dst):
        return True
    sr, dr = _INT_RANK.get(type(src)), _INT_RANK.get(type(dst))
    if sr is not None and dr is not None:
        return dr >= sr
    return isinstance(src, FloatType) and isinstance(dst, DoubleType)


@dataclass
class _ColPlan:
    name: str
    leaf: int               # parquet leaf/column-chunk index
    phys: str               # physical type
    itemsize: int
    vkind: str              # "i"/"f" (ignored for BOOLEAN)
    out_dtype: DataType     # the scan attribute's engine type
    nullable: bool          # max_definition_level == 1


def _column_plan(attr, leaf_idx: int, sc, cc, field_type) -> _ColPlan:
    """Eligibility for one column of one row group; raises DeviceDecodeError
    naming the reason when the column must decode on host."""
    if sc.max_repetition_level > 0 or sc.max_definition_level > 1:
        raise DeviceDecodeError("nested column")
    phys = cc.physical_type
    if phys == "BOOLEAN":
        isz, vkind = 1, "b"
    elif phys in _PHYS_FIXED:
        isz, vkind = _PHYS_FIXED[phys]
    elif phys == "BYTE_ARRAY":
        isz, vkind = 0, "s"  # variable width: offsets+bytes device layout
    else:  # INT96, FIXED_LEN_BYTE_ARRAY
        raise DeviceDecodeError(f"physical type {phys}")
    unsupported = set(cc.encodings) - _SUPPORTED_ENCODINGS
    if unsupported:
        raise DeviceDecodeError(f"encoding {sorted(unsupported)}")
    codec = _CODECS.get(cc.compression)
    if cc.compression not in _CODECS:
        raise DeviceDecodeError(f"codec {cc.compression}")
    if codec is not None:
        import pyarrow as pa
        if not pa.Codec.is_available(codec):
            raise DeviceDecodeError(f"codec {cc.compression} unavailable")
    try:
        src = arrow_to_type(field_type)
    except Exception as e:  # noqa: BLE001 — unmapped arrow type
        raise DeviceDecodeError(f"arrow type {field_type}: {e}")
    import pyarrow as pa
    if pa.types.is_timestamp(field_type) and field_type.unit != "us":
        raise DeviceDecodeError(f"timestamp unit {field_type.unit}")
    if vkind == "s":
        # strings/binary: the value bytes are copied verbatim — only the
        # identity "cast" is value-preserving on device
        if not isinstance(src, (StringType, BinaryType)) \
                or type(src) is not type(attr.dtype):
            raise DeviceDecodeError(f"byte-array type {src} -> {attr.dtype}")
    elif not isinstance(src, (BooleanType, ByteType, ShortType, IntegerType,
                              LongType, FloatType, DoubleType, DateType,
                              TimestampType)):
        raise DeviceDecodeError(f"column type {src}")
    elif not _cast_ok(src, attr.dtype):
        raise DeviceDecodeError(f"cast {src} -> {attr.dtype}")
    return _ColPlan(attr.name, leaf_idx, phys, isz, vkind, attr.dtype,
                    sc.max_definition_level == 1)


# ---------------------------------------------------------------------------
# page walk → staged buffers for one column chunk
# ---------------------------------------------------------------------------


@dataclass
class _Staged:
    """One column's host-staged buffers + its program-spec fragment.
    String columns staged from dictionary pages additionally carry the
    parsed dictionary (zero-based offsets + contiguous chars) so the
    assembled column can surface a device `dict_encoding`."""
    spec: Tuple
    arrays: List[np.ndarray]
    dense_values: int = 0   # values decoded with no per-element search
    general: Optional[str] = None   # else why not: one of GENERAL_REASONS
    dict_offsets: Optional[np.ndarray] = None
    dict_chars: Optional[np.ndarray] = None


def _pad_bytes(parts: List[bytes], min_len: int = 0) -> np.ndarray:
    """Concatenate byte regions and zero-pad to a bucketed capacity (+8
    bytes of slack so unpack_bits' 5-byte window never reads OOB)."""
    total = sum(len(p) for p in parts)
    cap = bucket_capacity(max(total, min_len) + 8)
    out = np.zeros(cap, np.uint8)
    pos = 0
    for p in parts:
        out[pos:pos + len(p)] = np.frombuffer(p, np.uint8)
        pos += len(p)
    return out


def _pad_runs(rows: List[List[int]]) -> np.ndarray:
    cap = bucket_capacity(max(len(rows), 1))
    out = np.full((cap, RUN_COLS), 0, np.int64)
    out[:, RUN_START] = RUN_PAD_START  # searchsorted never lands on padding
    for i, r in enumerate(rows):
        out[i] = r[:RUN_COLS]
    return out


#: a dense index stream is staged as segments padded to one common size, so
#: that the program's key stays coarse; where that takes more than this many
#: slots per slot of the output bucket (bytes staged and uploaded, more than
#: device time), the run table is the better description
_DENSE_PAD_LIMIT = 4


def _literal_segments(runs: List[List[int]], parts: List[bytes], n: int,
                      out_cap: int):
    """An index stream of `n` values made of bit-packed literal runs only,
    as the header-free bit streams the device unpacks with static shifts
    (`unpack_dense_segments`). Returns what the program's key takes —
    ((width, segment bucket), ...) with widths ascending and the slot
    bucket all segments are padded to — and, as data, each segment slot's
    dense start and value count and the staged uint32 words. None where the
    layout is anything else (an RLE run, a run the page end cuts short,
    segments so uneven that padding them alike takes more than
    _DENSE_PAD_LIMIT x out_cap slots): the run table then drives the
    general expansion.

    A segment is a stretch of runs of one bit width, each starting at the
    slot where the one before ends, so its value i sits at bit i * width.
    A literal run holds a multiple of 8 slots: only a page that ends inside
    a group leaves padding slots, and the next page then starts a segment.
    The key holds no per-page count: the widths that occur, how many
    segments of each as a bucket, one slot bucket for all.
    O(runs) Python + one O(bytes) copy."""
    if not runs:
        return None
    table = np.array(runs, np.int64)
    width, start, slots = (table[:, c] for c in
                           (RUN_WIDTH, RUN_START, RUN_SLOTS))
    if not (table[:, RUN_LITERAL].all() and slots.all()
            and width.min() >= 1 and width.max() <= 32):
        return None
    first = np.ones(len(runs), bool)     # runs that open a segment
    first[1:] = (width[1:] != width[:-1]) \
        | (start[1:] != start[:-1] + slots[:-1])
    opens = np.flatnonzero(first)
    seg_slots = max(bucket_capacity(int(np.add.reduceat(slots, opens).max())),
                    32)
    widths, group, per_width = np.unique(
        width[opens], return_inverse=True, return_counts=True)
    groups = tuple((int(w), bucket_capacity(int(k), minimum=1))
                   for w, k in zip(widths, per_width))
    if sum(k for _, k in groups) * seg_slots > _DENSE_PAD_LIMIT * out_cap:
        return None
    # segments in the order they are staged: by width, then as they came
    order = np.argsort(group, kind="stable")
    first_slot = np.cumsum([0] + [k for _, k in groups])    # of each group
    first_seg = np.cumsum(np.append(0, per_width))
    slot = first_slot[group[order]] + np.arange(len(order)) \
        - first_seg[group[order]]
    starts = np.zeros(first_slot[-1], np.int32)
    counts = np.zeros(first_slot[-1], np.int32)
    starts[slot] = start[opens][order]
    counts[slot] = np.diff(np.append(start[opens], n))[order]
    # every run's payload goes from its part to its place in one copy
    payload, nbytes = _run_payloads(table, parts)
    used = np.add.reduceat(nbytes, opens).tolist()
    bounds = opens.tolist() + [len(runs)]
    zeros = memoryview(bytes(seg_slots * 4))
    pieces = []
    for g, (w, k) in enumerate(groups):
        seg_bytes = seg_slots // 8 * w
        for s in order[first_seg[g]:first_seg[g + 1]].tolist():
            pieces += payload[bounds[s]:bounds[s + 1]]
            pieces.append(zeros[:seg_bytes - used[s]])
        pieces += [zeros[:seg_bytes]] * (k - int(per_width[g]))
    words = np.frombuffer(b"".join(pieces), np.uint32)
    return (groups, seg_slots), starts, counts, words


def _run_payloads(table: np.ndarray, parts: List[bytes]):
    """The payload of every literal run of `table` (walked rows, RUN_SLOTS
    included) as a view into the staged part that holds it, and the
    payloads' byte lengths."""
    views = [memoryview(p) for p in parts]
    part_at = np.cumsum([0] + [len(p) for p in parts[:-1]])
    src = table[:, RUN_BITOFF] >> 3
    part = np.searchsorted(part_at, src, side="right") - 1
    lo = src - part_at[part]
    nbytes = table[:, RUN_SLOTS] * table[:, RUN_WIDTH] // 8
    return [views[p][a:b] for p, a, b in zip(
        part.tolist(), lo.tolist(), (lo + nbytes).tolist())], nbytes


def _mixed_segments(runs: List[List[int]], parts: List[bytes], out_cap: int):
    """An index stream with RLE runs among its bit-packed literal runs, for
    `kernels/parquet_decode.expand_mixed`: the literal payloads header-free
    and back to back, one dense stream a bit width (a literal run holds a
    multiple of 8 slots, so payloads join byte-aligned), and an O(runs)
    int32 boundary table. Output element i of a literal run reads the
    literal stream at `i + base` (base = the run's place in the stream less
    its dense start: it changes after an RLE run, whose count is no multiple
    of 8, after a page that ends inside a group of 8, and where the width
    changes), an element of an RLE run reads the run's value, kept in the
    table's own BOUND_VALUE row at slot `base`. A width-0 run (one-entry
    dictionary) reads as an RLE run of value 0. Runs that continue the rule
    of the one before add no boundary.

    Returns ((width, slot bucket), ...) with widths ascending — what the
    program's key takes beside the bucket of the boundary count — the
    boundary table and the staged uint32 words (none where no literal run
    holds a bit); or the reason (one of GENERAL_REASONS) the run table has
    to drive the general expansion.
    O(runs) Python + one O(bytes) copy."""
    table = np.array(runs, np.int64).reshape(-1, RUN_SLOTS + 1)
    start, slots = table[:, RUN_START], table[:, RUN_SLOTS]
    lit = (table[:, RUN_LITERAL] != 0) & (table[:, RUN_WIDTH] > 0)
    if not slots[lit].all():
        return "truncated_run"
    lt = table[lit]
    widths, group = np.unique(lt[:, RUN_WIDTH], return_inverse=True)
    per_group = np.bincount(group, minlength=len(widths))
    order = np.argsort(group, kind="stable")        # as staged: by width
    first_run = np.cumsum(np.append(0, per_group))
    staged = np.cumsum(lt[order, RUN_SLOTS])
    ends = staged[first_run[1:] - 1]        # slots staged, up to each group
    total = np.diff(ends, prepend=0)
    g_slots = [max(bucket_capacity(int(t)), 32) for t in total]
    if sum(g_slots) > _DENSE_PAD_LIMIT * out_cap:
        return "uneven_segments"
    g_base = np.cumsum([0] + g_slots)       # of each group's unpacked values
    # a literal run's place among the unpacked values of all groups
    g = group[order]
    place = np.empty(len(lt), np.int64)
    place[order] = staged - lt[order, RUN_SLOTS] - (ends - total)[g] \
        + g_base[g]
    base = np.zeros(len(runs), np.int64)
    base[lit] = place - start[lit]
    keep = np.ones(len(runs), bool)
    keep[1:] = ~(lit[1:] & lit[:-1] & (base[1:] == base[:-1]))
    kept = np.flatnonzero(keep)
    rle = ~lit[kept]
    base[kept[rle]] = g_base[-1] + np.flatnonzero(rle)
    bounds = np.zeros((BOUND_ROWS, bucket_capacity(len(kept))), np.int32)
    bounds[BOUND_POS] = out_cap             # padding: dropped by the scatter
    bounds[BOUND_POS, :len(kept)] = start[kept]
    bounds[BOUND_STEP, :len(kept)] = np.diff(lit[kept].astype(np.int32),
                                             prepend=0)
    bounds[BOUND_BASE, :len(kept)] = np.diff(base[kept], prepend=0)
    bounds[BOUND_VALUE, :len(kept)] = \
        table[kept, RUN_VALUE].astype(np.uint32).view(np.int32)
    payload, nbytes = _run_payloads(lt, parts)
    pieces = []
    for k, w in enumerate(widths.tolist()):
        mine = order[first_run[k]:first_run[k + 1]]
        pieces += [payload[r] for r in mine.tolist()]
        pieces.append(bytes(g_slots[k] // 8 * w - int(nbytes[mine].sum())))
    words = [np.frombuffer(b"".join(pieces), np.uint32)] if pieces else []
    return tuple(zip(widths.tolist(), g_slots)), bounds, words


def _stage_indices(runs: List[List[int]], parts: List[bytes], n: int,
                   out_cap: int, general: Optional[str] = None):
    """A dictionary-index stream of `n` values as the program decodes it,
    chosen from the runs the walk saw: literal runs only -> header-free
    segments (`_literal_segments`, unpacked with static shifts); RLE runs
    among them -> the literal stream plus a boundary table
    (`_mixed_segments`, one gather an element); else — or where `general`
    already names a reason the column is on the general side (a nullable
    column, interleaved PLAIN pages) — the run table and `expand_runs`.
    Returns (the spec's index part, the staged arrays, the reason the run
    table was taken or None)."""
    if general is None:
        lit = _literal_segments(runs, parts, n, out_cap)
        if lit is not None:
            (groups, seg_slots), starts, counts, words = lit
            return ("dense", groups, seg_slots, out_cap), \
                [starts, counts, words], None
        mixed = _mixed_segments(runs, parts, out_cap)
        if not isinstance(mixed, str):
            groups, bounds, words = mixed
            return ("mixed", groups, bounds.shape[1], out_cap), \
                [bounds] + words, None
        general = mixed
    vr = _pad_runs(runs)
    vb = _pad_bytes(parts)
    return ("runs", vr.shape[0], vb.shape[0], out_cap), [vr, vb], general


def _place_plain(parts: List[bytes], first: int, cap: int,
                 itemsize: int) -> np.ndarray:
    """PLAIN value regions copied to their dense positions (values from
    `first` on) of a zeroed `cap`-value buffer, handed over as the uint32
    words `plain_fixed_width` pairs up."""
    out = np.empty(cap * itemsize, np.uint8)
    pos = first * itemsize
    out[:pos] = 0
    for p in parts:
        out[pos:pos + len(p)] = np.frombuffer(p, np.uint8)
        pos += len(p)
    out[pos:] = 0
    return out.view(np.uint32)


def _decompress(codec: Optional[str], body, usize: int) -> bytes:
    if codec is None:
        data = bytes(body)
    else:
        import pyarrow as pa
        data = pa.Codec(codec).decompress(body, usize).to_pybytes()
    if len(data) != usize:
        raise ValueError(f"decompressed {len(data)} != header {usize}")
    return data


def _page_laps(codec: Optional[str], laps):
    """The page walk's steps as laps of `laps` (an `obs.PhaseLaps`):
    `scan.decompress` around each page's decompression, `scan.walk` around
    the level and index run walk (and, at the call sites, the page headers
    and the index histogram). Returns (decompress, walk, count_valid, lap)."""
    lap = laps.lap

    def decompress(body, usize: int) -> bytes:
        with lap("scan.decompress"):
            return _decompress(codec, body, usize)

    def walk(*args) -> List[List[int]]:
        with lap("scan.walk"):
            return _walk_runs(*args)

    def count_valid(*args) -> int:
        with lap("scan.walk"):
            return _count_valid(*args)

    return decompress, walk, count_valid, lap


_STRING_CHAR_LIMIT = 1 << 31  # int32 offsets: > 2^31 chars cannot address


def _stage_string_column(chunk: bytes, cc, plan: _ColPlan, num_rows: int,
                         cap: int, laps) -> _Staged:
    """BYTE_ARRAY staging: def-level runs exactly like the fixed path;
    value regions stage as either an index run table + raw dictionary
    bytes (RLE_DICTIONARY pages) or per-value (start, length) tables into
    the concatenated PLAIN regions (4-byte prefixes walked host-side by
    vectorized pointer doubling). The exact output char total is computed
    host-side (index histogram · dictionary lengths, or the sum of PLAIN
    lengths) so the one decode dispatch keeps a static char capacity."""
    codec = _CODECS[cc.compression]
    decompress, walk, count_valid, lap = _page_laps(codec, laps)
    obs_on = _obs._ACTIVE
    lv_runs: List[List[int]] = []
    lv_parts: List[bytes] = []
    lv_bits = 0
    val_runs: List[List[int]] = []      # dictionary-index runs
    val_parts: List[bytes] = []
    val_bits = 0
    idx_counts: Optional[np.ndarray] = None
    plain_srcs: List[np.ndarray] = []   # PLAIN per-value starts (chars)
    plain_lens: List[np.ndarray] = []
    plain_parts: List[bytes] = []
    plain_base = 0
    dict_srcs = dict_lens = None
    dict_bytes: Optional[bytes] = None
    n_dict = 0
    saw_dict = saw_plain = False
    rows_seen = 0
    dense_seen = 0
    try:
        pos = 0
        end = len(chunk)
        while pos < end and rows_seen < num_rows:
            with lap("scan.walk"):
                hdr, dpos = _read_struct(chunk, pos)
            ptype, usize, csize = hdr[1], hdr[2], hdr[3]
            if usize < 0 or csize < 0 or dpos + csize > end:
                raise ValueError("page body out of bounds")
            body = chunk[dpos:dpos + csize]
            pos = dpos + csize
            if obs_on:
                _obs.event("scan.page", cat="io", column=plan.name,
                           page_type=ptype, compressed=csize,
                           uncompressed=usize)
            if ptype == _PAGE_DICT:
                dph = hdr[7]
                if dph[2] not in (_ENC_PLAIN, _ENC_PLAIN_DICT):
                    raise ValueError(f"dictionary encoding {dph[2]}")
                data = decompress(body, usize)
                n_dict = dph[1]
                region = np.frombuffer(data, np.uint8)
                starts, lens = _byte_array_starts(region, n_dict)
                dict_srcs, dict_lens = starts + 4, lens
                dict_bytes = data
                idx_counts = np.zeros(max(n_dict, 1), np.int64)
                continue
            if ptype not in (_PAGE_DATA_V1, _PAGE_DATA_V2):
                continue  # index pages etc.: metadata only
            if ptype == _PAGE_DATA_V1:
                data = decompress(body, usize)
                dph = hdr[5]
                nv, enc, denc = dph[1], dph[2], dph[3]
                p = 0
                if plan.nullable:
                    if denc != _ENC_RLE:
                        raise ValueError(f"def-level encoding {denc}")
                    (dlen,) = struct.unpack_from("<i", data, 0)
                    p = 4 + dlen
                    if dlen < 0 or p > len(data):
                        raise ValueError("def levels out of bounds")
                    lv_runs += walk(data, 4, p, 1, nv,
                                    rows_seen, lv_bits)
                    lv_parts.append(data[4:p])
                    lv_bits += dlen * 8
                    nnn = count_valid(data, 4, p, nv)
                else:
                    nnn = nv
                region = data[p:]
            else:  # v2
                v2 = hdr[8]
                nv, nnulls, enc = v2[1], v2[2], v2[4]
                dl_len, rl_len = v2[5], v2[6]
                if rl_len:
                    raise ValueError("repetition levels on flat column")
                if dl_len + rl_len > csize:
                    raise ValueError("levels out of bounds")
                levels = bytes(body[:dl_len])
                region = body[dl_len:]
                if codec is not None and v2.get(7, True):
                    region = decompress(region, usize - dl_len)
                else:
                    region = bytes(region)
                if plan.nullable:
                    lv_runs += walk(levels, 0, dl_len, 1, nv,
                                    rows_seen, lv_bits)
                    lv_parts.append(levels)
                    lv_bits += dl_len * 8
                elif nnulls:
                    raise ValueError("nulls in a required column")
                nnn = nv - nnulls
            rows_seen += nv
            if nnn:
                if enc in (_ENC_PLAIN_DICT, _ENC_RLE_DICT):
                    saw_dict = True
                    if idx_counts is None:
                        raise ValueError("dictionary-encoded page before "
                                         "the dictionary page")
                    if not region:
                        raise ValueError("empty dictionary-indices page")
                    bw = region[0]
                    if bw > 32:
                        raise ValueError(f"index bit width {bw}")
                    val_runs += walk(region, 1, len(region), bw, nnn,
                                     dense_seen, val_bits)
                    val_parts.append(region[1:])
                    val_bits += (len(region) - 1) * 8
                    with lap("scan.walk"):
                        _accum_index_counts(region, 1, len(region), bw, nnn,
                                            idx_counts)
                elif enc == _ENC_PLAIN:
                    saw_plain = True
                    rb = np.frombuffer(bytes(region), np.uint8)
                    starts, lens = _byte_array_starts(rb, nnn)
                    plain_srcs.append(starts + 4 + plain_base)
                    plain_lens.append(lens)
                    plain_parts.append(bytes(region))
                    plain_base += len(region)
                else:
                    raise ValueError(f"value encoding {enc}")
            dense_seen += nnn
        if rows_seen != num_rows:
            raise ValueError(f"pages cover {rows_seen} of {num_rows} rows")
        if saw_dict and saw_plain:
            # mid-chunk dictionary fallback on a STRING column: merging two
            # ragged sources into one gather plan is not worth the program
            # complexity (rare writer-overflow shape) — demote, never wrong
            raise DeviceDecodeError(
                f"column {plan.name}: mixed dictionary+PLAIN string chunk")
        if saw_dict and dict_bytes is None:
            raise ValueError("dictionary-encoded pages without a "
                             "dictionary page")
    except DeviceDecodeError:
        raise
    except (KeyError, ValueError, IndexError, struct.error,
            OverflowError) as e:
        raise DeviceDecodeError(
            f"column {plan.name}: malformed page data ({e})")
    except Exception as e:  # noqa: BLE001 — codec errors etc.
        raise DeviceDecodeError(f"column {plan.name}: {e}")

    out_kind = "s" if isinstance(plan.out_dtype, StringType) else "b"
    arrays: List[np.ndarray] = []
    if plan.nullable:
        lvr = _pad_runs(lv_runs)
        lvb = _pad_bytes(lv_parts)
        arrays += [lvr, lvb]
        lv_shape = (lvr.shape[0], lvb.shape[0])
    else:
        lv_shape = None
    if saw_dict:
        total_chars = int(idx_counts @ dict_lens) if n_dict else 0
        if total_chars >= _STRING_CHAR_LIMIT:
            raise DeviceDecodeError(
                f"column {plan.name}: {total_chars} chars exceed the int32 "
                f"offsets range")
        char_cap = bucket_capacity(max(total_chars, 1))
        idx_spec, idx_arrays, general = _stage_indices(
            val_runs, val_parts, dense_seen, cap,
            "nullable" if plan.nullable else None)
        arrays += idx_arrays
        dict_cap = bucket_capacity(max(n_dict, 1))
        # every entry of one byte length in a REQUIRED column: nothing is
        # ragged, the dictionary goes over as a [entries, length] matrix
        fixed_len = int(dict_lens[0]) if not plan.nullable and n_dict \
            and int(dict_lens.min()) == int(dict_lens.max()) else None
        if fixed_len is None:
            general = general or "variable_length_dictionary"
            dsrc = np.zeros(dict_cap, np.int64)
            dsrc[:n_dict] = dict_srcs
            dln = np.zeros(dict_cap, np.int32)
            dln[:n_dict] = dict_lens
            db = _pad_bytes([dict_bytes])
            arrays += [dsrc, dln, db]
        # the parquet dictionary doubles as the column's device
        # dict_encoding — but codes only preserve equality when the
        # writer's dictionary is actually duplicate-free (true for every
        # real writer; cheap to prove, catastrophic to assume)
        region = np.frombuffer(dict_bytes, np.uint8)
        doffs = np.zeros(n_dict + 1, np.int64)
        np.cumsum(dict_lens, out=doffs[1:])
        if int(doffs[-1]):
            src_idx = np.repeat(dict_srcs, dict_lens) + (
                np.arange(int(doffs[-1]), dtype=np.int64)
                - np.repeat(doffs[:-1], dict_lens))
            dchars = region[src_idx]
        else:
            dchars = np.zeros(0, np.uint8)
        # vectorized duplicate-free proof (no per-entry Python): entries
        # are distinct iff their (length, zero-padded bytes) rows are —
        # the length column disambiguates a real trailing NUL from
        # padding. Oversized dictionaries skip the attach instead of
        # paying an O(n_dict × max_len) matrix (decode stays correct;
        # the encoding is only an optimization).
        max_len = int(dict_lens.max()) if n_dict else 0
        if n_dict and n_dict * max(max_len, 1) <= (1 << 26):
            mat = np.zeros((n_dict, max_len), np.uint8)
            if int(doffs[-1]):
                rows = np.repeat(np.arange(n_dict), dict_lens)
                cols = np.arange(int(doffs[-1]), dtype=np.int64) \
                    - np.repeat(doffs[:-1], dict_lens)
                mat[rows, cols] = dchars
            lenb = dict_lens.astype("<u4").view(np.uint8).reshape(n_dict, 4)
            keyed = np.concatenate([lenb, mat], axis=1)
            uniq = np.unique(keyed, axis=0).shape[0] == n_dict
        else:
            uniq = False
        emit_codes = bool(n_dict) and uniq
        if fixed_len is None:
            spec = ("str_dict", plan.nullable, out_kind, lv_shape, idx_spec,
                    dict_cap, db.shape[0], cap, char_cap, emit_codes)
        else:
            if fixed_len:
                dmat = np.zeros((dict_cap, fixed_len), np.uint8)
                dmat[:n_dict] = dchars.reshape(n_dict, fixed_len)
                arrays += [dmat]
            spec = ("str_fixed", False, out_kind, None, idx_spec, dict_cap,
                    fixed_len, cap, char_cap, emit_codes)
        return _Staged(spec, arrays,
                       0 if general else dense_seen, general,
                       dict_offsets=doffs.astype(np.int32)
                       if emit_codes else None,
                       dict_chars=dchars if emit_codes else None)
    # PLAIN (or an all-null chunk with no staged values)
    all_lens = np.concatenate(plain_lens) if plain_lens \
        else np.zeros(0, np.int64)
    total_chars = int(all_lens.sum())
    if total_chars >= _STRING_CHAR_LIMIT:
        raise DeviceDecodeError(
            f"column {plan.name}: {total_chars} chars exceed the int32 "
            f"offsets range")
    char_cap = bucket_capacity(max(total_chars, 1))
    dense_cap = bucket_capacity(max(dense_seen, 1))
    srcs = np.zeros(dense_cap, np.int64)
    lens = np.zeros(dense_cap, np.int32)
    if len(all_lens):
        srcs[:dense_seen] = np.concatenate(plain_srcs)
        lens[:dense_seen] = all_lens
    vb = _pad_bytes(plain_parts)
    arrays += [srcs, lens, vb]
    spec = ("str_plain", plan.nullable, out_kind, lv_shape, dense_cap,
            vb.shape[0], cap, char_cap)
    return _Staged(spec, arrays,
                   general="nullable" if plan.nullable else "plain_strings")


def _stage_column(chunk: bytes, cc, plan: _ColPlan, num_rows: int,
                  cap: int, laps) -> _Staged:
    """Walk one column chunk's pages: parse headers, decompress, walk run
    headers, and build the staged uint8/run-table buffers the device program
    consumes. Raises DeviceDecodeError on anything structurally off."""
    if plan.vkind == "s":
        return _stage_string_column(chunk, cc, plan, num_rows, cap, laps)
    codec = _CODECS[cc.compression]
    decompress, walk, count_valid, lap = _page_laps(codec, laps)
    obs_on = _obs._ACTIVE
    lv_runs: List[List[int]] = []
    lv_parts: List[bytes] = []
    lv_bits = 0          # staged level-bytes length (bits base for runs)
    val_runs: List[List[int]] = []       # dict indices or boolean values
    val_parts: List[bytes] = []
    val_bits = 0
    plain_parts: List[bytes] = []
    #: per-data-page dense-range segments [dense_start, plain_src, 0,
    #: is_plain, 0] — consumed only when the chunk mixes dictionary and
    #: PLAIN pages (mid-chunk dictionary fallback)
    segs: List[List[int]] = []
    plain_seen = 0       # dense PLAIN values staged so far
    dict_bytes: Optional[bytes] = None
    saw_dict_data = saw_plain_data = False
    rows_seen = 0
    dense_seen = 0
    try:
        pos = 0
        end = len(chunk)
        while pos < end and rows_seen < num_rows:
            with lap("scan.walk"):
                hdr, dpos = _read_struct(chunk, pos)
            ptype, usize, csize = hdr[1], hdr[2], hdr[3]
            if usize < 0 or csize < 0 or dpos + csize > end:
                raise ValueError("page body out of bounds")
            body = chunk[dpos:dpos + csize]
            pos = dpos + csize
            if obs_on:
                _obs.event("scan.page", cat="io", column=plan.name,
                           page_type=ptype, compressed=csize,
                           uncompressed=usize)
            if ptype == _PAGE_DICT:
                dph = hdr[7]
                if dph[2] not in (_ENC_PLAIN, _ENC_PLAIN_DICT):
                    raise ValueError(f"dictionary encoding {dph[2]}")
                data = decompress(body, usize)
                if len(data) < dph[1] * plan.itemsize:
                    raise ValueError("dictionary page too short")
                dict_bytes = data
                continue
            if ptype == _PAGE_DATA_V1:
                data = decompress(body, usize)
                dph = hdr[5]
                nv, enc, denc = dph[1], dph[2], dph[3]
                p = 0
                if plan.nullable:
                    if denc != _ENC_RLE:
                        raise ValueError(f"def-level encoding {denc}")
                    (dlen,) = struct.unpack_from("<i", data, 0)
                    p = 4 + dlen
                    if dlen < 0 or p > len(data):
                        raise ValueError("def levels out of bounds")
                    lv_runs += walk(data, 4, p, 1, nv,
                                    rows_seen, lv_bits)
                    lv_parts.append(data[4:p])
                    lv_bits += dlen * 8
                    nnn = count_valid(data, 4, p, nv)
                else:
                    nnn = nv
                rows_seen += nv
                region = data[p:]
                if enc in (_ENC_PLAIN_DICT, _ENC_RLE_DICT):
                    saw_dict_data = True
                    segs.append([dense_seen, 0, 0, 0, 0])
                    if not region:
                        raise ValueError("empty dictionary-indices page")
                    bw = region[0]
                    if bw > 32:
                        raise ValueError(f"index bit width {bw}")
                    val_runs += walk(region, 1, len(region), bw, nnn,
                                     dense_seen, val_bits)
                    val_parts.append(region[1:])
                    val_bits += (len(region) - 1) * 8
                elif enc == _ENC_PLAIN:
                    saw_plain_data = True
                    if plan.phys == "BOOLEAN":
                        if len(region) * 8 < nnn:
                            raise ValueError("boolean page too short")
                        val_runs.append([dense_seen, val_bits, 0, 1, 1])
                        val_parts.append(region)
                        val_bits += len(region) * 8
                    else:
                        segs.append([dense_seen, plain_seen, 0, 1, 0])
                        need = nnn * plan.itemsize
                        if len(region) < need:
                            raise ValueError("PLAIN values page too short")
                        plain_parts.append(region[:need])
                        plain_seen += nnn
                elif enc == _ENC_RLE and plan.phys == "BOOLEAN":
                    (blen,) = struct.unpack_from("<i", region, 0)
                    if blen < 0 or 4 + blen > len(region):
                        raise ValueError("RLE boolean region out of bounds")
                    val_runs += walk(region, 4, 4 + blen, 1, nnn,
                                     dense_seen, val_bits)
                    val_parts.append(region[4:4 + blen])
                    val_bits += blen * 8
                else:
                    raise ValueError(f"value encoding {enc}")
                dense_seen += nnn
                continue
            if ptype == _PAGE_DATA_V2:
                v2 = hdr[8]
                nv, nnulls, enc = v2[1], v2[2], v2[4]
                dl_len, rl_len = v2[5], v2[6]
                if rl_len:
                    raise ValueError("repetition levels on flat column")
                if dl_len + rl_len > csize:
                    raise ValueError("levels out of bounds")
                levels = bytes(body[:dl_len])
                vregion = body[dl_len:]
                if codec is not None and v2.get(7, True):
                    vregion = decompress(vregion, usize - dl_len)
                else:
                    vregion = bytes(vregion)
                if plan.nullable:
                    lv_runs += walk(levels, 0, dl_len, 1, nv,
                                    rows_seen, lv_bits)
                    lv_parts.append(levels)
                    lv_bits += dl_len * 8
                elif nnulls:
                    raise ValueError("nulls in a required column")
                rows_seen += nv
                nnn = nv - nnulls
                if enc in (_ENC_PLAIN_DICT, _ENC_RLE_DICT):
                    saw_dict_data = True
                    segs.append([dense_seen, 0, 0, 0, 0])
                    if not vregion:
                        raise ValueError("empty dictionary-indices page")
                    bw = vregion[0]
                    if bw > 32:
                        raise ValueError(f"index bit width {bw}")
                    val_runs += walk(vregion, 1, len(vregion), bw,
                                     nnn, dense_seen, val_bits)
                    val_parts.append(vregion[1:])
                    val_bits += (len(vregion) - 1) * 8
                elif enc == _ENC_PLAIN:
                    saw_plain_data = True
                    if plan.phys == "BOOLEAN":
                        if len(vregion) * 8 < nnn:
                            raise ValueError("boolean page too short")
                        val_runs.append([dense_seen, val_bits, 0, 1, 1])
                        val_parts.append(vregion)
                        val_bits += len(vregion) * 8
                    else:
                        segs.append([dense_seen, plain_seen, 0, 1, 0])
                        need = nnn * plan.itemsize
                        if len(vregion) < need:
                            raise ValueError("PLAIN values page too short")
                        plain_parts.append(vregion[:need])
                        plain_seen += nnn
                elif enc == _ENC_RLE and plan.phys == "BOOLEAN":
                    (blen,) = struct.unpack_from("<i", vregion, 0)
                    if blen < 0 or 4 + blen > len(vregion):
                        raise ValueError("RLE boolean region out of bounds")
                    val_runs += walk(vregion, 4, 4 + blen, 1, nnn,
                                     dense_seen, val_bits)
                    val_parts.append(vregion[4:4 + blen])
                    val_bits += blen * 8
                else:
                    raise ValueError(f"value encoding {enc}")
                dense_seen += nnn
                continue
            # index pages etc.: metadata only, skip
        if rows_seen != num_rows:
            raise ValueError(f"pages cover {rows_seen} of {num_rows} rows")
        if saw_dict_data and dict_bytes is None:
            raise ValueError("dictionary-encoded pages without a "
                             "dictionary page")
    except DeviceDecodeError:
        raise
    except (KeyError, ValueError, IndexError, struct.error,
            OverflowError) as e:
        raise DeviceDecodeError(
            f"column {plan.name}: malformed page data ({e})")
    except Exception as e:  # noqa: BLE001 — codec errors etc.
        raise DeviceDecodeError(f"column {plan.name}: {e}")

    out_np = str(np.dtype(plan.out_dtype.np_dtype))
    arrays: List[np.ndarray] = []
    dense_values = 0
    general = "nullable" if plan.nullable else None
    if plan.nullable:
        lvr = _pad_runs(lv_runs)
        lvb = _pad_bytes(lv_parts)
        arrays += [lvr, lvb]
        lv_shape = (lvr.shape[0], lvb.shape[0])
    else:
        lv_shape = None
    if plan.phys == "BOOLEAN":
        if saw_dict_data:
            # dict-encoded booleans (legal but exotic): the run table here
            # holds dictionary INDICES, which decode_bool_runs would read
            # as values — demote rather than risk wrong data
            raise DeviceDecodeError(
                f"column {plan.name}: dictionary-encoded boolean pages")
        vr = _pad_runs(val_runs)
        vb = _pad_bytes(val_parts)
        arrays += [vr, vb]
        general = general or "boolean"
        spec = ("bool", out_np, plan.nullable, lv_shape,
                (vr.shape[0], vb.shape[0]), cap)
    elif saw_dict_data:
        # "dictionary pages, then PLAIN pages" (one switch point, what a
        # writer's dictionary overflow produces) is a concatenation: only
        # the dictionary-coded prefix expands and gathers, in its own bucket
        flags = [sg[RUN_LITERAL] for sg in segs]
        tail = saw_plain_data and flags == sorted(flags)
        n_dict = dense_seen - plain_seen
        idx_cap = bucket_capacity(max(n_dict, 1)) if tail else cap
        # (interleaved dictionary and PLAIN pages keep the general path whole)
        idx_spec, idx_arrays, general = _stage_indices(
            val_runs, val_parts, n_dict, idx_cap,
            "nullable" if plan.nullable else
            "interleaved_plain" if saw_plain_data and not tail else None)
        arrays += idx_arrays
        db = _pad_bytes([dict_bytes], min_len=plan.itemsize).view(np.uint32)
        arrays += [db]
        if tail:
            # PLAIN values staged at their dense positions, n_dict a
            # run-time operand: the program selects by position
            arrays += [np.array(n_dict, np.int32),
                       _place_plain(plain_parts, n_dict, cap, plan.itemsize)]
            plain_spec = ("tail",)
        elif saw_plain_data:
            # interleaved dictionary and PLAIN pages: PLAIN values merge
            # back into the dense stream by segment table
            seg = _pad_runs(segs)
            pb = _pad_bytes(plain_parts, min_len=plan.itemsize) \
                .view(np.uint32)
            arrays += [seg, pb]
            plain_spec = ("segments", seg.shape[0], pb.shape[0])
        else:
            plain_spec = None
        dense_values = (n_dict if general is None else 0) \
            + (plain_seen if tail else 0)
        spec = ("dict", plan.itemsize, plan.vkind, out_np, plan.nullable,
                lv_shape, idx_spec, db.shape[0], plain_spec, cap)
    else:
        arrays += [_place_plain(plain_parts, 0, cap, plan.itemsize)]
        dense_values = dense_seen
        spec = ("plain", plan.itemsize, plan.vkind, out_np, plan.nullable,
                lv_shape, cap)
    # (definition levels are a per-element run lookup of their own)
    return _Staged(spec, arrays, 0 if plan.nullable else dense_values,
                   general)


# ---------------------------------------------------------------------------
# the cached per-row-group decode program: ONE dispatch decodes every staged
# column (O(row-groups) launches per scan, not O(pages) or O(columns))
# ---------------------------------------------------------------------------


def _build_program(specs: Tuple[Tuple, ...]):
    import jax
    import jax.numpy as jnp

    from ..kernels import parquet_decode as K

    def levels(it, nullable, cap, num_rows):
        with jax.named_scope("levels"):
            if nullable:
                lv_runs = next(it)
                lv_bytes = next(it)
                defs = K.expand_runs(lv_runs, lv_bytes, cap)
                return K.validity_from_defs(defs, 1, num_rows)
            return jnp.arange(cap, dtype=jnp.int64) < num_rows

    def indices(it, idx_spec):
        """The dictionary indices of one column, as `_stage_indices` staged
        them."""
        with jax.named_scope("rle_expand"):
            if idx_spec[0] == "dense":
                starts, counts, words = next(it), next(it), next(it)
                return K.unpack_dense_segments(
                    words, idx_spec[1], idx_spec[2], starts, counts,
                    idx_spec[3])
            if idx_spec[0] == "mixed":
                bounds = next(it)
                words = next(it) if idx_spec[1] else None
                return K.expand_mixed(words, idx_spec[1], bounds,
                                      idx_spec[3])
            vr, vb = next(it), next(it)
            return K.expand_runs(vr, vb, idx_spec[3])

    # the steps carry stable names into the device trace (jax.named_scope:
    # metadata only); the program itself reads jit_parquet_decode there
    def parquet_decode(num_rows, *bufs):
        it = iter(bufs)
        outs = []
        for spec in specs:
            kind = spec[0]
            if kind == "str_fixed":
                # one entry length, REQUIRED: offsets are an iota, chars a
                # take from the dictionary's matrix, codes the indices
                length, char_cap = spec[6], spec[8]
                idx = indices(it, spec[4])
                with jax.named_scope("string_gather"):
                    dmat = next(it) if length \
                        else jnp.zeros((1, 0), jnp.uint8)
                    offs, chars, codes = K.fixed_length_strings(
                        dmat, idx, num_rows, char_cap)
                outs += [offs, chars] + ([codes] if spec[9] else [])
                continue
            if kind in ("str_plain", "str_dict"):
                # BYTE_ARRAY → offsets+bytes device layout: row lengths
                # cumsum into int32 offsets, one searchsorted byte gather
                # materializes the chars (kernels/parquet_decode.py)
                nullable = spec[1]
                cap = spec[7] if kind == "str_dict" else spec[6]
                char_cap = spec[8] if kind == "str_dict" else spec[7]
                valid = levels(it, nullable, cap, num_rows)
                if kind == "str_dict":
                    idx = indices(it, spec[4])
                    dsrc, dlen, db = next(it), next(it), next(it)
                    with jax.named_scope("dict_gather"):
                        src_dense = K.dictionary_gather(dsrc, idx)
                        len_dense = K.dictionary_gather(dlen, idx)
                else:
                    src_dense, len_dense = next(it), next(it)
                    db = next(it)
                with jax.named_scope("string_gather"):
                    row_len = K.expand_dense(len_dense, valid)
                    row_src = K.expand_dense(src_dense, valid)
                    offs = K.string_offsets(row_len)
                    chars = K.gather_string_bytes(db, row_src, offs,
                                                  char_cap)
                outs.append(offs)
                outs.append(chars)
                outs.append(valid if nullable else None)
                if kind == "str_dict" and spec[9]:
                    # the parquet dictionary codes ride along as the
                    # column's device dict_encoding (null lanes zeroed)
                    with jax.named_scope("levels"):
                        outs.append(K.expand_dense(idx, valid)
                                    .astype(jnp.int32))
                continue
            cap = spec[-1]
            nullable = spec[4] if kind != "bool" else spec[2]
            out_np = spec[3] if kind != "bool" else spec[1]
            valid = levels(it, nullable, cap, num_rows)
            if kind == "bool":
                vr, vb = next(it), next(it)
                with jax.named_scope("rle_expand"):
                    dense = K.decode_bool_runs(vr, vb, cap)
            elif kind == "dict":
                isz, vkind = spec[1], spec[2]
                idx_spec, plain_spec = spec[6], spec[8]
                idx = indices(it, idx_spec)
                db = next(it)
                with jax.named_scope("dict_gather"):
                    dvals = K.plain_fixed_width(db, isz, vkind)
                    dense = K.dictionary_gather(dvals, idx)
                if plain_spec is not None:  # mid-chunk dictionary fallback
                    with jax.named_scope("plain"):
                        if plain_spec[0] == "tail":
                            n_dict, pb = next(it), next(it)
                            dense = K.place_plain_tail(
                                dense, K.plain_fixed_width(pb, isz, vkind),
                                n_dict)
                        else:
                            seg, pb = next(it), next(it)
                            dense = K.merge_plain_segments(
                                seg, K.plain_fixed_width(pb, isz, vkind),
                                dense, cap)
            else:  # plain
                isz, vkind = spec[1], spec[2]
                vb = next(it)
                with jax.named_scope("plain"):
                    dense = K.plain_fixed_width(vb, isz, vkind)
            with jax.named_scope("levels"):
                if nullable:
                    data = K.expand_dense(dense, valid)
                else:
                    data = jnp.where(valid, dense,
                                     jnp.zeros((), dense.dtype))
                data = data.astype(jnp.dtype(out_np))
            outs.append(data)
            outs.append(valid if nullable else None)
        return tuple(o for o in outs if o is not None)

    return jax.jit(parquet_decode)


def _program(specs: Tuple[Tuple, ...]):
    with _LOCK:
        fn = _PROGRAMS.get(specs)
        if fn is not None:
            _PROGRAMS.move_to_end(specs)
            return fn
    fn = _build_program(specs)
    with _LOCK:
        _PROGRAMS[specs] = fn
        _STATS["programs"] += 1
        while len(_PROGRAMS) > _PROGRAM_CACHE_MAX:
            _PROGRAMS.popitem(last=False)
    return fn


# ---------------------------------------------------------------------------
# row-group decode: read ranges → stage → one dispatch → TpuColumnarBatch
# ---------------------------------------------------------------------------


def _chunk_range(cc) -> Tuple[int, int]:
    start = cc.data_page_offset
    # truthy check: a 0 offset means "absent" (the file magic occupies
    # bytes 0-3, so no real page can start at 0)
    if cc.has_dictionary_page and cc.dictionary_page_offset:
        start = min(start, cc.dictionary_page_offset)
    return start, cc.total_compressed_size


def _host_columns(pf, rgi: int, names: List[str], attrs_by_name: Dict,
                  cap: int):
    """Host pyarrow decode for the fallback columns of one row group,
    normalized exactly like the host scan path (ns→us timestamps, cast to
    the attribute type)."""
    import pyarrow as pa

    from ..columnar.batch import _repad
    t = pf.read_row_groups([rgi], columns=names)
    out: Dict[str, TpuColumnVector] = {}
    for name in names:
        arr = t.column(name)
        at = arr.type
        if pa.types.is_timestamp(at) and at.unit == "ns":
            arr = arr.cast(pa.timestamp("us", tz=at.tz), safe=False)
        want = type_to_arrow(attrs_by_name[name].dtype)
        if arr.type != want:
            arr = arr.cast(want)
        col = TpuColumnVector.from_arrow(
            arr.combine_chunks() if isinstance(arr, pa.ChunkedArray)
            else arr)
        if col.capacity < cap:
            col = _repad(col, cap)
        out[name] = col
    return out


def _verify_against_host(pf, rgi: int, batch, device_names: List[str],
                         attrs_by_name: Dict) -> None:
    """Paranoid cross-check (spark.rapids.tpu.parquet.deviceDecode.verify):
    the device-decoded columns must be bit-identical to pyarrow's decode of
    the same row group. A mismatch means corrupted staged bytes slipped past
    the structural checks — DeviceDecodeError re-reads the file on host."""
    import pyarrow as pa
    ref = pf.read_row_groups([rgi], columns=device_names)
    got = batch.to_arrow()
    for name in device_names:
        want = ref.column(name)
        wt = type_to_arrow(attrs_by_name[name].dtype)
        if want.type != wt:
            want = want.cast(wt)
        have = got.column(name)
        if isinstance(want, pa.ChunkedArray):
            want = want.combine_chunks()
        if isinstance(have, pa.ChunkedArray):
            have = have.combine_chunks()
        if not want.equals(have):
            raise DeviceDecodeError(
                f"verify: device decode of column {name} in row group "
                f"{rgi} differs from the host decode")


class DeviceFileDecoder:
    """Device decode of one parquet file, row group at a time.

    Construction validates the FILE (encryption → `ParquetEncryptedException`
    with the reference's message semantics; unreadable footer / legacy
    rebase / no row groups → `DeviceDecodeError`, the caller re-reads the
    whole file on host). `decode_row_group` may raise `DeviceDecodeError`
    per row group (corrupt/truncated pages, all columns demoted) — the
    caller then host-reads just that row group, so a mid-file failure never
    duplicates or loses rows. Individual ineligible columns demote to host
    pyarrow decode and zip into the same batch.
    """

    def __init__(self, path: str, attrs: Sequence, conf):
        import pyarrow as pa
        import pyarrow.parquet as pq

        from ..config import (PARQUET_DEVICE_DECODE_VERIFY,
                              PARQUET_REBASE_MODE_READ)
        from ..filecache import FileCache
        from .rebase import needs_rebase

        self.path = path
        self.attrs = list(attrs)
        self.conf = conf
        reason = detect_encryption(path)
        if reason is not None:
            raise ParquetEncryptedException(encrypted_message(path, reason))
        try:
            self.pf = pq.ParquetFile(path)
            self.md = self.pf.metadata
        except Exception as e:  # noqa: BLE001 — unreadable footer
            raise DeviceDecodeError(f"{path}: cannot read footer ({e})")
        try:
            if self.md.num_row_groups == 0:
                raise DeviceDecodeError(f"{path}: no row groups")
            self.arrow_schema = self.pf.schema_arrow
            has_datetime = any(
                pa.types.is_date32(f.type) or pa.types.is_timestamp(f.type)
                for f in self.arrow_schema)
            if has_datetime and needs_rebase(
                    self.md.metadata, conf.get(PARQUET_REBASE_MODE_READ)):
                raise DeviceDecodeError(
                    f"{path}: legacy calendar rebase required")
            # leaf (column-chunk) index by name, flat columns only
            self.leaf_by_name: Dict[str, int] = {}
            rg0 = self.md.row_group(0)
            for j in range(rg0.num_columns):
                p = rg0.column(j).path_in_schema
                if "." not in p:
                    self.leaf_by_name[p] = j
            for a in self.attrs:
                if a.name not in self.leaf_by_name:
                    raise DeviceDecodeError(
                        f"{path}: column {a.name} not in file")
            self.attrs_by_name = {a.name: a for a in self.attrs}
            self.verify = bool(conf.get(PARQUET_DEVICE_DECODE_VERIFY))
            # ONE resolved handle for all chunk-range reads of this file
            # (a wide scan reads columns × row-groups ranges)
            self.reader = FileCache.get(conf).range_reader(path, conf)
            # the scan.* phases of every row group of this file, summed
            # and emitted once by close() (per-batch rule)
            self._laps = _obs.PhaseLaps()
        except BaseException:
            # validation raised after pf opened: the caller gets no
            # decoder object to close, so the footer fd must not ride
            # until GC — one leaked fd per host-fallback file otherwise
            try:
                self.pf.close()
            except AttributeError:
                pass
            raise

    def close(self) -> None:
        """Release the byte-range handle (and the footer reader): one open
        fd per scanned file must not ride until GC (TL020 — the scan loop
        closes each decoder in a finally)."""
        self._laps.flush()
        self.reader.close()
        try:
            self.pf.close()
        except AttributeError:  # older pyarrow: no ParquetFile.close
            pass

    def __enter__(self) -> "DeviceFileDecoder":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def row_groups(self, row_filter=None) -> List[int]:
        """Non-empty row groups surviving footer-statistics pruning (the
        same predicate as the host chunked reader)."""
        from .base_scan import rg_excluded
        out = []
        for rgi in range(self.md.num_row_groups):
            rg = self.md.row_group(rgi)
            if rg.num_rows == 0:
                continue
            if row_filter and rg_excluded(rg, row_filter):
                continue
            out.append(rgi)
        return out

    def decode_row_group(self, rgi: int, metrics: Optional[Dict] = None,
                         ctx=None):
        """Stage + decode one row group as ONE device dispatch; returns a
        `TpuColumnarBatch` with columns in attrs order. The TPU semaphore
        (when a task context is given) is acquired only around the device
        staging upload + dispatch — host page walking/decompression
        overlaps other tasks' device work, like the reference's
        host-staging-then-semaphore pattern."""
        import contextlib

        import jax

        from ..columnar.batch import TpuColumnarBatch
        from ..execs import opjit

        def timed(name):
            return metrics[name].timed() if metrics is not None \
                else contextlib.nullcontext()

        rg = self.md.row_group(rgi)
        num_rows = rg.num_rows
        cap = bucket_capacity(num_rows)
        path = self.path

        plans: List[_ColPlan] = []
        host_names: List[str] = []

        def demote(name: str, err) -> None:
            host_names.append(name)
            _bump("fallback_columns")
            if _obs._ACTIVE:
                _obs.event("scan.fallback", cat="io", column=name,
                           reason=str(err)[:120])

        for a in self.attrs:
            leaf = self.leaf_by_name[a.name]
            try:
                plans.append(_column_plan(
                    a, leaf, self.pf.schema.column(leaf), rg.column(leaf),
                    self.arrow_schema.field(a.name).type))
            except DeviceDecodeError as e:
                demote(a.name, e)
        if not plans:
            raise DeviceDecodeError(
                f"{path}: no device-decodable columns in row group {rgi}")

        laps = self._laps
        with _obs.span("scan.decode", cat="io", file=path, row_group=rgi,
                       device=True, rows=num_rows, device_cols=len(plans),
                       host_cols=len(host_names)):
            staged: List[_Staged] = []
            kept: List[_ColPlan] = []
            with timed("decodeTime"):
                with laps.lap("scan.page_walk"):
                    for plan in plans:
                        cc = rg.column(plan.leaf)
                        start, length = _chunk_range(cc)
                        try:
                            chunk = self.reader.read(start, length)
                            staged.append(_stage_column(chunk, cc, plan,
                                                        num_rows, cap, laps))
                            kept.append(plan)
                        except (DeviceDecodeError, OSError) as e:
                            # per-column demotion (bad bytes, failed range
                            # read): host decodes just this column
                            demote(plan.name, e)
                    if not kept:
                        raise DeviceDecodeError(
                            f"{path}: all columns demoted to host in row "
                            f"group {rgi}")

                # admission control only now: host page walking above
                # overlapped other tasks' device work (reference: stage on
                # host, THEN semaphore, then device decode)
                if ctx is not None:
                    from ..memory.semaphore import TpuSemaphore
                    with laps.lap("scan.admit", cat="wait"):
                        TpuSemaphore.get(self.conf).acquire_if_necessary(
                            ctx)

                # stage → HBM: ONE device_put for every buffer of every
                # column
                leaves: List[np.ndarray] = []
                for st in staged:
                    leaves.extend(st.arrays)
                _bump("bytes_staged", sum(a.nbytes for a in leaves))
                with timed("uploadTime"), laps.lap("scan.upload"):
                    uploaded = jax.device_put(leaves)

                with laps.lap("scan.launch"):
                    specs = tuple(st.spec for st in staged)
                    fn = _program(specs)
                    _bump("dispatches")
                    _bump("row_groups")
                    _bump("rows", num_rows)
                    _bump("values", num_rows * len(kept))
                    _bump("dense_values",
                          sum(st.dense_values for st in staged))
                    _bump("device_columns", len(kept))
                    general = [st.general for st in staged
                               if st.general is not None]
                    for reason in general:
                        _bump("general_" + reason)
                    opjit.record_external_dispatch("parquet_decode")
                    outs = fn(np.int64(num_rows), *uploaded)

                    # assemble columns in attrs order (device + host zipped)
                    out_it = iter(outs)
                    dev_cols: Dict[str, TpuColumnVector] = {}
                    for st, plan in zip(staged, kept):
                        kind = st.spec[0]
                        if kind in ("str_plain", "str_dict", "str_fixed"):
                            offs = next(out_it)
                            chars = next(out_it)
                            valid = next(out_it) if st.spec[1] else None
                            col = TpuColumnVector(plan.out_dtype, chars, valid,
                                                  num_rows, offsets=offs)
                            if kind != "str_plain" and st.spec[9]:
                                codes = next(out_it)
                                col.dict_encoding = (
                                    codes,
                                    TpuColumnVector.from_strings(
                                        plan.out_dtype, st.dict_offsets,
                                        st.dict_chars))
                            dev_cols[plan.name] = col
                            continue
                        data = next(out_it)
                        nullable = st.spec[4] if kind != "bool" \
                            else st.spec[2]
                        valid = next(out_it) if nullable else None
                        dev_cols[plan.name] = TpuColumnVector(
                            plan.out_dtype, data, valid, num_rows)
            if host_names:
                # per-column fallback decodes are HOST pyarrow work: they
                # count under hostDecodeTime, not decodeTime, so the bench
                # breakdown cannot hide a fallback-heavy scan
                with timed("hostDecodeTime"):
                    host_cols = _host_columns(self.pf, rgi, host_names,
                                              self.attrs_by_name, cap)
            else:
                host_cols = {}
            cols = []
            for a in self.attrs:
                col = dev_cols.get(a.name) or host_cols.get(a.name)
                assert col is not None, a.name
                cols.append(col)
            batch = TpuColumnarBatch(cols, num_rows,
                                     [a.name for a in self.attrs])
            if self.verify and dev_cols:
                _verify_against_host(self.pf, rgi, batch, list(dev_cols),
                                     self.attrs_by_name)
            if metrics is not None:
                # the scan node's own counts of what _STATS counted above:
                # its query's `scan.*` counters (a row group that raised
                # before here is re-read on the host and counts as fallback)
                metrics["decodeDispatches"].add(1)
                metrics["decodeFallbackColumns"].add(len(host_names))
                metrics["rowsDecoded"].add(num_rows)
                metrics["columnsDecoded"].add(len(kept))
                metrics["columnsGeneral"].add(len(general))
            return batch
