"""Device-side Parquet page-decode kernels (XLA, jit-composable).

Reference: the plugin decodes parquet bytes ON DEVICE after host staging —
`GpuParquetScan.scala:1983,2506` acquires the semaphore and hands the raw
(decompressed) column-chunk bytes to cuDF's page decoders. The TPU analogue
lives here: every O(rows) transform of the Parquet physical encodings is a
pure jnp function over device uint8 / uint32 buffers, composed per row group
into ONE cached program by io/device_decode.py. The host touches only O(pages) +
O(runs) metadata (footer, page headers, RLE run headers) and the
decompression pass; the unpack/expand/gather/scatter work below runs on
device.

Encodings covered (the flat fixed-width column classes and BYTE_ARRAY).
What costs time on a TPU is a per-element search or a gather from a large
table (8.6 ns an element from a 4 K-entry int32 table, 14-21 ns for
float64, 64-112 ns from the emulated-int64 run table, on a v5e: PERF.md
PR 25), so the program searches per element only where the page layout
leaves no other way; the host's page walk says which
(io/device_decode.py). The sides after PR 28, from dense to general:

* **dense bit-unpacking** (`unpack_dense`, `unpack_dense_segments`) — a
  dictionary-index stream made of bit-packed literal runs only, staged
  without its run headers: value k of every group of 32 is a static shift
  over one or two uint32 word lanes. No lookup at all; a stream whose bit
  width grows with the dictionary, or whose pages end inside a group of 8,
  is several such segments, unpacked together per width;
* **mixed streams by boundary table** (`expand_mixed`) — RLE runs among
  the literal runs (low-cardinality columns). The literal payloads unpack
  as above, one dense stream a width; an O(runs) int32 table says where
  the rule `index = step * i + base` changes (after an RLE run, whose
  count is no multiple of 8; after a page that ends inside a group of 8;
  where the width changes), the device scatters it, takes one two-row
  prefix sum and moves each value once: one 32-bit gather an element, no
  `searchsorted`, no byte gathers;
* **fixed-length dictionary strings** (`fixed_length_strings`) — a
  REQUIRED BYTE_ARRAY column whose dictionary entries all have one byte
  length: offsets are an iota, chars a take from the dictionary's
  [entries, length] matrix, codes the indices;
* **general bit-unpacking** (`unpack_bits`) — 1..32-bit packed little-endian
  values at arbitrary per-element bit offsets (PLAIN booleans, literal runs
  addressed through the run table);
* **RLE / bit-packed hybrid run expansion** (`expand_runs`) — definition
  levels (so every nullable column), booleans, and the index streams the
  two layouts above turn down (a literal run the page end cuts short,
  payloads too scattered to pad). The host walks the varint run headers
  into a run table (one row per run: output start, absolute bit offset,
  repeated value, literal flag, bit width); the kernel positions every
  output element in its run with one `searchsorted` and either bit-unpacks
  (literal run) or broadcasts the run value (RLE run) — about twenty
  gathers an element;
* **dictionary gather** (`dictionary_gather`) — expanded indices into the
  PLAIN-decoded dictionary values: the one gather an element that stays;
* **definition levels → validity** (`validity_from_defs`) and **null
  compaction** (`expand_dense`) — Parquet stores only non-null values
  densely; the scatter re-expands them into the padded-batch layout
  `columnar/batch.py` uses (rows in [num_rows, capacity) stay zero/invalid);
* **PLAIN fixed-width reinterpret** (`plain_fixed_width`) — the staged
  little-endian value bytes, viewed as uint32 words on the host, to
  int32/int64/float32/float64 carriers: a bitcast, or the even and odd
  words paired (no host round trip, no per-byte arithmetic);
* **dictionary → PLAIN fallback** — one switch point is a concatenation
  (`place_plain_tail`: the host stages the PLAIN values at their dense
  positions, one select merges); interleaved pages keep the segment-table
  merge (`merge_plain_segments`);
* **ragged BYTE_ARRAY strings** (`string_offsets`, `gather_string_bytes`)
  — what stays general among strings, because the row lengths differ or
  rows are null: variable-length dictionaries, nullable columns, PLAIN
  pages. They decode into the engine's own Arrow-style offsets+bytes
  layout (`columnar/vector.py`): per-row byte lengths (from the 4-byte
  PLAIN length prefixes, or gathered from the dictionary's entry lengths)
  cumsum into the int32 offsets vector, and one searchsorted byte gather
  materializes the char buffer — the same ragged shape
  `kernels/strings.py` computes over, so a decoded string column is
  immediately a first-class device string column. (A dictionary column's
  index stream is dense or mixed all the same when it is REQUIRED.)

All functions are shape-polymorphic jnp (no data-dependent host syncs), so
tracelint's kernel scan classifies them device-clean and io/device_decode.py
can fuse any per-row-group combination into a single dispatch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: run-table column indices (int64 [n_runs, 5] built by io/device_decode.py;
#: padding runs carry start = RUN_PAD_START so searchsorted never lands on
#: them)
RUN_START, RUN_BITOFF, RUN_VALUE, RUN_LITERAL, RUN_WIDTH = range(5)
RUN_COLS = 5
RUN_PAD_START = 1 << 62


def unpack_bits(data_u8, bit_offsets, widths):
    """Unpack little-endian bit-packed values of per-element `widths` (1..32
    bits) starting at absolute `bit_offsets` into uint64 values.

    `data_u8` must carry >= 8 bytes of zero padding past the last addressed
    bit (io/device_decode.py pads every staged buffer); out-of-range offsets
    clip into the padding and decode to garbage the caller masks off.
    """
    pos = bit_offsets.astype(jnp.int64)
    byte = pos >> 3
    shift = (pos & 7).astype(jnp.uint64)
    word = jnp.zeros(pos.shape, jnp.uint64)
    for k in range(5):  # 5 bytes cover any 32-bit value at any bit shift
        word = word | (jnp.take(data_u8, byte + k, mode="clip")
                       .astype(jnp.uint64) << jnp.uint64(8 * k))
    mask = (jnp.uint64(1) << widths.astype(jnp.uint64)) - jnp.uint64(1)
    return (word >> shift) & mask


def expand_runs(run_table, data_u8, out_len: int):
    """Expand an RLE / bit-packed hybrid run table into `out_len` int64
    values (dictionary indices or definition levels).

    Each output element finds its run by binary search over the run starts,
    then either broadcasts the run's repeated value (RLE run) or bit-unpacks
    its element from the staged page bytes (bit-packed literal run).
    Elements past the last real run read padding and are masked downstream.
    """
    idx = jnp.arange(out_len, dtype=jnp.int64)
    starts = run_table[:, RUN_START]
    r = jnp.searchsorted(starts, idx, side="right") - 1
    r = jnp.clip(r, 0, run_table.shape[0] - 1)
    local = idx - jnp.take(starts, r, mode="clip")
    width = jnp.take(run_table[:, RUN_WIDTH], r, mode="clip")
    bitoff = jnp.take(run_table[:, RUN_BITOFF], r, mode="clip") \
        + local * width
    unpacked = unpack_bits(data_u8, bitoff, width).astype(jnp.int64)
    literal = jnp.take(run_table[:, RUN_LITERAL], r, mode="clip") != 0
    value = jnp.take(run_table[:, RUN_VALUE], r, mode="clip")
    return jnp.where(literal, unpacked, value)


def validity_from_defs(def_levels, max_def, num_rows):
    """Definition levels → dense validity mask over the padded capacity.
    Rows in [num_rows, capacity) are padding and always invalid."""
    n = def_levels.shape[0]
    in_range = jnp.arange(n, dtype=jnp.int64) < num_rows
    return (def_levels == max_def) & in_range


def expand_dense(dense, validity):
    """Null compaction inverse: scatter the densely-stored non-null values
    into their row slots (Parquet data pages store only rows whose
    definition level is max_def). Null/padding rows read zero."""
    pos = jnp.cumsum(validity.astype(jnp.int64)) - 1
    safe = jnp.clip(pos, 0, dense.shape[0] - 1)
    g = jnp.take(dense, safe, axis=0, mode="clip")
    return jnp.where(validity, g, jnp.zeros((), dense.dtype))


def dictionary_gather(dict_values, indices):
    """Gather decoded dictionary values by expanded indices (clipped: padding
    indices land on dictionary slot 0 and are masked by validity)."""
    return jnp.take(dict_values, indices.astype(jnp.int32), axis=0,
                    mode="clip")


def unpack_dense(words_u32, width: int, out_len: int):
    """Gather-free unpack of a header-free stream of `width`-bit values
    (1..32, static): value i sits at bit i * width of the little-endian
    uint32 words. 32 values fill exactly `width` words, so value k of every
    group is the same static shift over the same one or two word lanes and
    nothing is looked up per element. The lanes are the rows of the word
    array transposed once, the output one transpose of the 32 value rows;
    all arithmetic is uint32. `words_u32` holds out_len / 32 * width words
    (`out_len` a multiple of 32, at least 32); slots past the staged values
    read the zero padding."""
    groups = out_len // 32
    lanes = words_u32[:groups * width].reshape(groups, width).T
    vals = []
    for k in range(32):
        w, s = divmod(k * width, 32)
        v = lanes[w] >> jnp.uint32(s)
        if s + width > 32:  # the value straddles two words
            v = v | (lanes[w + 1] << jnp.uint32(32 - s))
        if width < 32:
            v = v & jnp.uint32((1 << width) - 1)
        vals.append(v)
    return jnp.stack(vals, axis=0).T.reshape(out_len)


def unpack_dense_segments(words_u32, groups_py, slots: int, starts, counts,
                          out_len: int):
    """A dictionary-index stream of bit-packed literal runs only, staged as
    header-free segments (io/device_decode.py::_literal_segments), each
    padded to the common `slots` (static, a multiple of 32). `groups_py` is
    the static ((width, segments), ...), widths ascending: a group's
    segments lie back to back, so they unpack as ONE dense stream of
    segments * slots values, and the groups' words follow one another.
    `starts` and `counts` are data, one entry per segment slot in the same
    order: the dense position of the segment's first value and how many
    values it really holds (0 for a slot no segment fills). Each segment
    writes its own `counts` values only, so the order of placement is free
    and padding slots touch nothing."""
    if len(groups_py) == 1 and groups_py[0][1] == 1 and slots >= out_len:
        return unpack_dense(words_u32, groups_py[0][0], slots)[:out_len]
    out = jnp.zeros((out_len + slots,), jnp.uint32)
    lane = jnp.arange(slots, dtype=jnp.int32)
    word_at = seg_at = 0
    for width, n_seg in groups_py:
        n_words = n_seg * (slots // 32) * width
        vals = unpack_dense(
            jax.lax.slice(words_u32, (word_at,), (word_at + n_words,)),
            width, n_seg * slots).reshape(n_seg, slots)

        def place(k, out, vals=vals, seg_at=seg_at):
            at = jax.lax.dynamic_index_in_dim(starts, seg_at + k, 0, False)
            n = jax.lax.dynamic_index_in_dim(counts, seg_at + k, 0, False)
            seg = jax.lax.dynamic_index_in_dim(vals, k, 0, False)
            kept = jax.lax.dynamic_slice(out, (at,), (slots,))
            return jax.lax.dynamic_update_slice(
                out, jnp.where(lane < n, seg, kept), (at,))
        if n_seg <= 8:  # flat: the TPU compiler keeps even a loop of one
            for k in range(n_seg):
                out = place(k, out)
        else:
            out = jax.lax.fori_loop(0, n_seg, place, out)
        word_at += n_words
        seg_at += n_seg
    return out[:out_len]


#: rows of the boundary table of a mixed index stream (int32 [4, n],
#: io/device_decode.py::_mixed_segments): the dense position where a stretch
#: begins, the change there of `step` and of `base`, and the repeated value
#: of an RLE run that begins there
BOUND_POS, BOUND_STEP, BOUND_BASE, BOUND_VALUE = range(4)
BOUND_ROWS = 4


def expand_mixed(words_u32, groups_py, bounds, out_len: int):
    """A dictionary-index stream with RLE runs among its bit-packed literal
    runs, with no search an element. The literal payloads are staged
    header-free, back to back — `groups_py` is the static ((width, slots),
    ...), one dense stream a bit width, each padded to its `slots` (a
    multiple of 32) — and unpack with static shifts (`unpack_dense`); the RLE
    runs' values, one per boundary, follow them in one table. Element i then
    reads `table[step[i] * i + base[i]]`: inside a stretch of literal runs
    `step` is 1 and `base` the stretch's place in the literal stream less
    its dense start, inside an RLE run `step` is 0 and `base` the run's slot.
    Both change only where `bounds` says, so they are two prefix sums over a
    scatter of O(runs) entries; padding entries carry a position of
    `out_len` or more and are dropped. One 32-bit gather an element stays.
    Elements past the last run repeat its rule and are masked downstream."""
    lit = []
    word_at = 0
    for width, slots in groups_py:
        n_words = slots // 32 * width
        lit.append(unpack_dense(
            jax.lax.slice(words_u32, (word_at,), (word_at + n_words,)),
            width, slots))
        word_at += n_words
    table = jnp.concatenate(lit + [jax.lax.bitcast_convert_type(
        bounds[BOUND_VALUE], jnp.uint32)])
    rule = jnp.zeros((2, out_len), jnp.int32).at[:, bounds[BOUND_POS]].add(
        bounds[BOUND_STEP:BOUND_BASE + 1], mode="drop")
    step, base = jnp.cumsum(rule, axis=1, dtype=jnp.int32)
    i = jnp.arange(out_len, dtype=jnp.int32)
    return jnp.take(table, step * i + base, mode="clip")


def fixed_length_strings(dict_rows_u8, indices, num_rows, char_cap: int):
    """Strings of a REQUIRED column whose dictionary entries all have one
    byte length L (`dict_rows_u8` is the dictionary as a [n_dict, L]
    matrix): nothing is ragged, so the offsets are `L * min(row, num_rows)`,
    the chars the matrix's rows taken by index (a gather from a small
    table) and the codes the indices themselves — no prefix sum, no search.
    Returns (offsets int32 [capacity + 1], chars uint8 [char_cap], codes
    int32 [capacity]); padding rows read 0 and add no chars."""
    cap = indices.shape[0]
    length = dict_rows_u8.shape[1]
    n = num_rows.astype(jnp.int32)
    valid = jnp.arange(cap, dtype=jnp.int32) < n
    codes = jnp.where(valid, indices.astype(jnp.int32), 0)
    offs = jnp.minimum(jnp.arange(cap + 1, dtype=jnp.int32), n) * length
    if length == 0:
        return offs, jnp.zeros((char_cap,), jnp.uint8), codes
    rows = jnp.take(dict_rows_u8, codes, axis=0, mode="clip")
    chars = jnp.where(valid[:, None], rows, jnp.uint8(0)).reshape(cap * length)
    if cap * length < char_cap:
        chars = jnp.concatenate(
            [chars, jnp.zeros((char_cap - cap * length,), jnp.uint8)])
    return offs, chars[:char_cap], codes


def plain_fixed_width(words_u32, itemsize: int, kind: str):
    """PLAIN fixed-width reinterpret: the staged little-endian value bytes,
    handed over as uint32 words (a free host view), to carrier values.
    4-byte types are one bitcast; 8-byte types pair the even (low) and odd
    (high) words — strided slices, no per-byte arithmetic.

    kind: "i" signed int, "f" float; itemsize 4/8 (INT32/INT64/FLOAT/DOUBLE).
    """
    if itemsize == 4:
        return jax.lax.bitcast_convert_type(
            words_u32, jnp.float32 if kind == "f" else jnp.int32)
    n = words_u32.shape[0]
    lo = jax.lax.slice(words_u32, (0,), (n - 1,), (2,)).astype(jnp.uint64)
    hi = jax.lax.slice(words_u32, (1,), (n,), (2,)).astype(jnp.uint64)
    return jax.lax.bitcast_convert_type(
        lo | (hi << jnp.uint64(32)),
        jnp.float64 if kind == "f" else jnp.int64)


def place_plain_tail(dict_prefix, plain_values, n_dict):
    """Dictionary fallback with one switch point — dictionary pages, then
    PLAIN pages, what every parquet-cpp / parquet-mr writer produces: the
    dense stream is a concatenation. The host stages the PLAIN values at
    their dense positions (from `n_dict` on), `dict_prefix` holds the
    dictionary-gathered values of the first `n_dict` (a run-time operand)
    in a bucket of its own: one select by position merges them."""
    pad = plain_values.shape[0] - dict_prefix.shape[0]
    if pad:
        dict_prefix = jnp.concatenate(
            [dict_prefix, jnp.zeros((pad,), dict_prefix.dtype)])
    idx = jnp.arange(plain_values.shape[0], dtype=jnp.int32)
    return jnp.where(idx < n_dict, dict_prefix, plain_values)


def merge_plain_segments(seg_table, plain_values, base, out_len: int):
    """Mid-chunk dictionary fallback with interleaved segments (the general
    form; the one-switch layout takes `place_plain_tail`): once a writer's
    dictionary overflows, later data pages store PLAIN values while earlier
    pages stay dictionary-indexed (parquet's standard fallback; cuDF decodes
    such chunks natively). `seg_table` marks each data page's dense range
    ([dense_start, plain_src_start, 0, is_plain, 0] rows): elements inside
    a PLAIN page's range read `plain_values[src_start + (i - dense_start)]`,
    everything else keeps `base` (the dictionary-gathered stream)."""
    idx = jnp.arange(out_len, dtype=jnp.int64)
    starts = seg_table[:, RUN_START]
    r = jnp.searchsorted(starts, idx, side="right") - 1
    r = jnp.clip(r, 0, seg_table.shape[0] - 1)
    src = jnp.take(seg_table[:, RUN_BITOFF], r, mode="clip") \
        + idx - jnp.take(starts, r, mode="clip")
    is_plain = jnp.take(seg_table[:, RUN_LITERAL], r, mode="clip") != 0
    vals = jnp.take(plain_values,
                    jnp.clip(src, 0, plain_values.shape[0] - 1), axis=0)
    return jnp.where(is_plain, vals, base)


def string_offsets(row_lengths):
    """Per-row byte lengths → the Arrow-style int32 offsets vector
    (length capacity+1, offsets[0] == 0). Null and padding rows carry
    length 0, so their offsets repeat the running total — exactly the
    layout `TpuColumnVector.from_strings` builds host-side."""
    return jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(row_lengths.astype(jnp.int32), dtype=jnp.int32)])


def gather_string_bytes(data_u8, row_starts, offsets, out_len: int):
    """Materialize the output char buffer: output byte j belongs to row
    r = searchsorted(offsets, j) and reads
    `data_u8[row_starts[r] + (j - offsets[r])]` (the dictionary bytes or
    the staged PLAIN value region). Bytes past the total length
    (offsets[-1]) are zero padding."""
    j = jnp.arange(out_len, dtype=jnp.int32)
    r = jnp.searchsorted(offsets[1:], j, side="right").astype(jnp.int32)
    r = jnp.clip(r, 0, row_starts.shape[0] - 1)
    src = jnp.take(row_starts, r).astype(jnp.int64) \
        + (j - jnp.take(offsets, r)).astype(jnp.int64)
    in_range = j < offsets[offsets.shape[0] - 1]
    got = jnp.take(data_u8, jnp.clip(src, 0, data_u8.shape[0] - 1),
                   mode="clip")
    return jnp.where(in_range, got, jnp.uint8(0))


def decode_bool_runs(run_table, data_u8, out_len: int):
    """Boolean values from the run machinery: PLAIN bit-packed pages stage
    as one literal run each (width 1), RLE-encoded pages (data page v2) as
    ordinary runs — either way the expansion is `expand_runs` != 0."""
    return expand_runs(run_table, data_u8, out_len) != 0
