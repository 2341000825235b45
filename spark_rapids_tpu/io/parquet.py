"""File-source scan execs: parquet/ORC/CSV/JSON with multi-file read strategies.

Reference: GpuParquetScan.scala (2897 — host footer parse + row-group pruning,
then device decode), GpuMultiFileReader.scala (PERFILE / COALESCING /
MULTITHREADED strategies with AUTO selection, RapidsConf.scala:1067-1088),
GpuOrcScan/GpuCSVScan/text reader.

TPU mapping (SURVEY §2.4): there is no device decoder for parquet on TPU, so
decode happens on host via pyarrow (the reference also does footer/row-group
assembly on host) and the decoded Arrow columns upload to HBM. The COALESCING
strategy stitches many small files into one upload; MULTITHREADED overlaps
host IO+decode with device compute via a prefetching thread pool.
Predicate pushdown prunes row groups by footer statistics before decode.
"""

from __future__ import annotations

import concurrent.futures as _fut
import os
from typing import Iterator, List, Optional, Sequence

from ..columnar.batch import TpuColumnarBatch
from ..config import (MULTITHREAD_READ_NUM_THREADS, PARQUET_READER_TYPE)
from ..expressions.base import AttributeReference, Expression
from .base_scan import arrow_filter_from_condition
from ..execs.base import CpuExec, PhysicalPlan, TaskContext, TpuExec


def _partition_value(raw, dtype):
    """Raw hive partition-directory value → python value at the column
    type (one rule for the host table attach AND the device column
    attach — extending the coercion in one place keeps both scan paths
    returning identical partition values)."""
    import pyarrow as pa

    from ..types import to_arrow
    if raw is None or raw == "__HIVE_DEFAULT_PARTITION__":
        return None
    return int(raw) if to_arrow(dtype) == pa.int64() else raw


def _split_files(paths: List[str], n: int) -> List[List[str]]:
    out: List[List[str]] = [[] for _ in range(n)]
    for i, p in enumerate(paths):
        out[i % n].append(p)
    return out


def _resolve_cache_path(path: str, options: dict) -> str:
    """Route remote inputs through the local file cache (reference: the
    spark-rapids-private FileCache hooks in GpuExec/Plugin)."""
    conf = (options or {}).get("__conf__")
    if conf is not None:
        from ..filecache import FileCache
        from ..config import FILECACHE_ENABLED
        if conf.get(FILECACHE_ENABLED):
            return FileCache.get(conf).resolve(
                path, conf,
                force=str((options or {}).get("filecache.force",
                                              "false")).lower() == "true")
    return path


def _read_one(path: str, fmt: str, columns: Optional[List[str]],
              arrow_filter, options: dict):
    import pyarrow as pa
    # deletion vectors / stats are keyed by the ORIGINAL path; look them up
    # before the file cache rewrites it to a local copy
    dv_rows = (options or {}).get("__dv_rows__", {}).get(path)
    path = _resolve_cache_path(path, options)
    if fmt == "parquet":
        import pyarrow.parquet as pq
        fid_map = (options or {}).get("__iceberg_field_ids__")
        if fid_map is not None:
            from .iceberg import read_iceberg_parquet
            return read_iceberg_parquet(path, columns, fid_map,
                                        dv_rows=dv_rows)
        if dv_rows is not None:
            # deletion vector: positions are file-absolute, so read without
            # row-group filters, then drop deleted rows (delta DV read path)
            import numpy as np
            t = _read_parquet_table(path, columns=columns)
            keep = np.ones(t.num_rows, dtype=bool)
            keep[dv_rows.astype(np.int64)] = False
            return _postprocess_parquet(t.filter(pa.array(keep)), path,
                                        options)
        t = _read_parquet_table(path, columns=columns, filters=arrow_filter)
        return _postprocess_parquet(t, path, options)
    if fmt == "orc":
        import pyarrow.orc as paorc
        # ORC predicate pushdown: scan filters thread into the dataset read
        # (stripe/row-group statistics pruning, the ORC analogue of the
        # parquet `filters=` path above); the exact Filter exec above the
        # scan keeps results identical either way
        from .base_scan import dataset_filter_expr
        expr = dataset_filter_expr(arrow_filter) if arrow_filter else None
        if expr is not None:
            try:
                import pyarrow.dataset as pads
                t = pads.dataset(path, format="orc").to_table(
                    columns=columns, filter=expr)
                return t
            except Exception:  # noqa: BLE001 — dataset/orc pushdown
                pass  # unavailable: plain read below is always correct
        t = paorc.read_table(path, columns=columns)
    elif fmt == "csv":
        import pyarrow.csv as pacsv
        header = str(options.get("header", "false")).lower() == "true"
        sep = options.get("sep", options.get("delimiter", ","))
        popts = pacsv.ParseOptions(delimiter=sep)
        copts = None
        ddl = options.get("__user_schema__")
        if ddl is not None:
            # user schema: read named columns at the declared types (reference
            # GpuCSVScan type-cast post-pass)
            from ..types import to_arrow as type_to_arrow
            names = [f.name for f in ddl.fields]
            ropts = pacsv.ReadOptions(column_names=names,
                                      skip_rows=1 if header else 0)
            copts = pacsv.ConvertOptions(column_types={
                f.name: type_to_arrow(f.data_type) for f in ddl.fields})
        else:
            ropts = pacsv.ReadOptions(autogenerate_column_names=not header)
        try:
            t = pacsv.read_csv(path, read_options=ropts, parse_options=popts,
                               convert_options=copts)
        except pa.lib.ArrowInvalid:
            if ddl is None:
                raise
            # PERMISSIVE column-count mismatch: extra file columns dropped,
            # missing schema columns null (Spark CSV default mode)
            ropts2 = pacsv.ReadOptions(autogenerate_column_names=not header)
            raw = pacsv.read_csv(path, read_options=ropts2, parse_options=popts)
            out = {}
            for i, f in enumerate(ddl.fields):
                at = type_to_arrow(f.data_type)
                if header and f.name in raw.column_names:
                    src = raw.column(f.name)
                elif not header and i < raw.num_columns:
                    src = raw.column(i)
                else:
                    src = None
                out[f.name] = pa.nulls(raw.num_rows, at) if src is None \
                    else src.cast(at)
            t = pa.table(out)
        if columns:
            t = t.select([c for c in columns if c in t.column_names])
    elif fmt == "json":
        import pyarrow.json as pajson
        t = pajson.read_json(path)
        if columns:
            t = t.select([c for c in columns if c in t.column_names])
    elif fmt == "avro":
        from .avro import read_avro
        t = read_avro(path, columns=columns)
    elif fmt == "hivetext":
        from .hive_text import read_hive_text
        t = read_hive_text(path, options)
        if columns:
            t = t.select([c for c in columns if c in t.column_names])
    else:
        raise ValueError(f"unknown scan format {fmt}")
    return t


def _read_parquet_table(path: str, columns=None, filters=None):
    """pq.read_table with encrypted-file detection: pyarrow's error on an
    encrypted input is cryptic ('Parquet magic bytes not found'), so the
    host path raises the same clean message as the device decoder
    (reference GpuParquetScan.scala:590)."""
    import pyarrow.parquet as pq
    try:
        return pq.read_table(path, columns=columns, filters=filters)
    except Exception:
        from .device_decode import (ParquetEncryptedException,
                                    detect_encryption, encrypted_message)
        reason = detect_encryption(path)
        if reason is not None:
            raise ParquetEncryptedException(
                encrypted_message(path, reason)) from None
        raise


def _postprocess_parquet(t, path: str, options: dict, kv_metadata=None):
    """Per-file parquet parity passes (reference GpuParquetScan.scala:446):
      * INT96 timestamps decode as timestamp[ns] — normalize to micros;
      * legacy hybrid-calendar files (footer marker, or forced LEGACY read
        mode) get their date/timestamp values rebased to proleptic."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from .rebase import needs_rebase, rebase_scope, rebase_table
    # INT96 decodes as timestamp[ns]; the engine works in micros (Spark's
    # internal unit) — normalize the unit, keep the UTC zone convention
    ns_cols = [i for i, f in enumerate(t.schema)
               if pa.types.is_timestamp(f.type) and f.type.unit == "ns"]
    for i in ns_cols:
        f = t.schema.field(i)
        # safe=False: Spark TRUNCATES sub-microsecond precision to micros
        t = t.set_column(i, f.name, t.column(i).cast(
            pa.timestamp("us", tz=f.type.tz), safe=False))
    mode = "CORRECTED"
    conf = (options or {}).get("__conf__")
    if conf is not None:
        from ..config import PARQUET_REBASE_MODE_READ
        mode = conf.get(PARQUET_REBASE_MODE_READ)
    has_datetime = any(pa.types.is_date32(f.type)
                       or pa.types.is_timestamp(f.type) for f in t.schema)
    if has_datetime:
        kv = kv_metadata
        if kv is None:
            try:
                kv = pq.ParquetFile(path).metadata.metadata
            except Exception:  # noqa: BLE001 — no footer: assume modern
                kv = None
        if needs_rebase(kv, mode):
            # physical types from the footer: each legacy marker only
            # rebases its own encoding's columns (legacyINT96 → INT96,
            # legacyDateTime → dates + INT64 timestamps). Only opened on
            # the rebase path — the common CORRECTED case never re-reads
            # the footer.
            int96 = None
            try:
                int96 = {c.name for c in pq.ParquetFile(path).schema
                         if c.physical_type == "INT96"}
            except Exception:  # noqa: BLE001
                pass
            ts_names = [f.name for f in t.schema
                        if pa.types.is_timestamp(f.type)]
            dates, tss = rebase_scope(kv, mode, int96_cols=int96,
                                      ts_cols=ts_names)
            t = rebase_table(t, rebase_dates=dates, rebase_timestamps=tss)
    return t


def _read_parquet_chunks(path: str, columns, arrow_filter, options: dict,
                         chunk_bytes: int):
    """Bounded-memory parquet decode: row groups stream out in chunks whose
    compressed footprint stays under `chunk_bytes`, so a huge file feeds the
    retry framework chunk-by-chunk instead of OOMing the host in one decode
    (reference chunked reader, GpuParquetScan.scala + RapidsConf chunked
    reader limit)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from .base_scan import rg_excluded
    pf = pq.ParquetFile(path)
    md = pf.metadata
    n_rg = md.num_row_groups

    group, group_bytes = [], 0
    for i in range(n_rg):
        rg = md.row_group(i)
        if rg_excluded(rg, arrow_filter):
            continue
        group.append(i)
        group_bytes += rg.total_byte_size
        if group_bytes >= chunk_bytes:
            yield _postprocess_parquet(
                pf.read_row_groups(group, columns=columns), path, options,
                kv_metadata=md.metadata)
            group, group_bytes = [], 0
    if group:
        yield _postprocess_parquet(
            pf.read_row_groups(group, columns=columns), path, options,
            kv_metadata=md.metadata)
    elif n_rg == 0:
        yield _postprocess_parquet(pf.read(columns=columns), path, options,
                                   kv_metadata=md.metadata)


def _stats_may_match(stats: Optional[dict], arrow_filter) -> bool:
    """Conservative per-file pruning: False only when a pushed min/max leaf
    provably excludes every row of the file."""
    if not stats:
        return True
    mins = stats.get("minValues") or {}
    maxs = stats.get("maxValues") or {}
    num = stats.get("numRecords")
    nullc = stats.get("nullCount") or {}
    for col, op, val in arrow_filter:
        mn, mx = mins.get(col), maxs.get(col)
        if op == "in":
            if mn is None or mx is None:
                continue
            try:
                if all(v < mn or v > mx for v in val):
                    return False
            except TypeError:
                continue
            continue
        if mn is None or mx is None:
            continue
        try:
            if op == "==" and (val < mn or val > mx):
                return False
            if op == "<" and mn >= val:
                return False
            if op == "<=" and mn > val:
                return False
            if op == ">" and mx <= val:
                return False
            if op == ">=" and mx < val:
                return False
        except TypeError:
            continue  # incomparable stat (e.g. isoformat string vs date)
    # all-null file vs any comparison leaf: no row can match
    if num is not None and arrow_filter:
        for col, op, val in arrow_filter:
            if nullc.get(col) == num:
                return False
    return True


class FileScanBase:
    def _init_scan(self, paths: List[str], fmt: str,
                   output: List[AttributeReference],
                   pushed_filters: Sequence[Expression], options: dict,
                   num_partitions: Optional[int]):
        self.paths = list(paths)
        self.fmt = fmt
        self._output_attrs = output
        self.pushed_filters = list(pushed_filters)
        self.options = dict(options or {})
        self._n_parts = num_partitions or max(1, min(len(self.paths), 8))
        self._arrow_filter = arrow_filter_from_condition(self.pushed_filters)

    @property
    def output(self):
        return self._output_attrs

    def num_partitions(self) -> int:
        return self._n_parts

    def node_desc(self) -> str:
        pf = f", pushed={len(self.pushed_filters)}" if self.pushed_filters else ""
        return f"{type(self).__name__}[{self.fmt}, {len(self.paths)} files{pf}]"

    def _partition_columns(self):
        return self.options.get("__partition_cols__", ())

    def _attach_partition_cols(self, table, f: str):
        """Append the file's hive-partition values as constant columns
        (reference GpuFileSourceScanExec partitionColumns append)."""
        pcols = self._partition_columns()
        if not pcols:
            return table
        import pyarrow as pa
        from ..types import to_arrow
        vals = self.options.get("__partition_values__", {}).get(f, {})
        for name, dtype in pcols:
            py = _partition_value(vals.get(name), dtype)
            col = pa.array([py] * table.num_rows, type=to_arrow(dtype))
            table = table.append_column(name, col)
        return table

    def _prune_by_partition_values(self, files, conf=None):
        """Static + dynamic partition pruning: drop files whose partition
        values cannot satisfy the pushed filters, or that a runtime subquery
        broadcast (DPP) rules out — all before any IO (reference: partition
        filters + DynamicPruningExpression evaluated by the file index)."""
        pcols = dict(self._partition_columns())
        dpp = self.options.get("__dpp_filters__", ())
        if not pcols or not (self._arrow_filter or dpp):
            return files
        import pyarrow as pa
        from ..types import to_arrow
        pvals = self.options.get("__partition_values__", {})
        if dpp and conf is not None:
            for name, subq in dpp:
                if name not in pcols:
                    continue
                allowed = subq.values(conf)
                kept = []
                for f in files:
                    raw = pvals.get(f, {}).get(name)
                    if raw is None or raw == "__HIVE_DEFAULT_PARTITION__":
                        continue
                    v = int(raw) if to_arrow(pcols[name]) == pa.int64() else raw
                    if v in allowed:
                        kept.append(f)
                files = kept
        if not self._arrow_filter:
            return files

        def file_ok(f):
            vals = pvals.get(f, {})
            for name, op, lit in self._arrow_filter:
                if name not in pcols:
                    continue
                raw = vals.get(name)
                if raw is None or raw == "__HIVE_DEFAULT_PARTITION__":
                    return False  # null partition never matches a comparison
                v = int(raw) if to_arrow(pcols[name]) == pa.int64() else raw
                if op == "==" and not v == lit:
                    return False
                if op == "<" and not v < lit:
                    return False
                if op == "<=" and not v <= lit:
                    return False
                if op == ">" and not v > lit:
                    return False
                if op == ">=" and not v >= lit:
                    return False
                if op == "in" and v not in lit:
                    return False
            return True

        return [f for f in files if file_ok(f)]

    def _prune_by_bucket(self, files, conf):
        """Bucket pruning (reference GpuFileSourceScanExec bucketing): an
        equality filter on the single bucket column keeps only the files of
        pmod(murmur3(value), numBuckets) — file names carry the bucket id
        as part-NNNNN_BBBBB."""
        import re as _re

        import numpy as np
        spec = (self.options or {}).get("__bucket_spec__")
        if not spec or not self._arrow_filter:
            return files
        from ..config import BUCKETING_READ_PRUNE_ENABLED
        if conf is not None and not conf.get(BUCKETING_READ_PRUNE_ENABLED):
            return files
        cols = spec.get("bucketColumns") or []
        n = int(spec.get("numBuckets") or 0)
        if len(cols) != 1 or n <= 0:
            return files
        value = None
        for leaf in self._arrow_filter:
            try:
                name, op, val = leaf
            except Exception:  # noqa: BLE001 — nested filter shape
                continue
            if name == cols[0] and op in ("=", "=="):
                value = val
                break
        if value is None:
            return files
        import pyarrow as pa

        from ..expressions.hashexprs import _np_hash_col
        from ..types import to_arrow as t2a
        # hash with the COLUMN's declared type: murmur3 of int32 and int64
        # differ, and the writer hashed with the column type
        attr = next((a for a in self._output_attrs if a.name == cols[0]),
                    None)
        if attr is None:
            return files
        arr = pa.array([value], type=t2a(attr.dtype))
        seeds = np.full(1, np.uint32(42), np.uint32)
        h = _np_hash_col(attr.dtype, arr, seeds).view(np.int32).astype(
            np.int64)[0]
        bucket = int(((h % n) + n) % n)
        pat = _re.compile(rf"part-[^/]*_{bucket:05d}\.")
        kept = [f for f in files if pat.search(os.path.basename(f))]
        # unbucketed files (no _BBBBB suffix) must always be read
        plain = [f for f in files
                 if not _re.search(r"part-[^/]*_\d{5}\.",
                                   os.path.basename(f))]
        return kept + plain

    def _partition_files(self, idx: int, ctx: TaskContext):
        """File selection for one partition: split + every before-IO pruning
        pass (delta stats, partition values, buckets). Returns
        (files, data column names, data-column pushed-filter leaves) —
        shared by the host-decode strategies and the device decode path."""
        self.options["__conf__"] = ctx.conf  # file-cache resolution
        files = _split_files(self.paths, self._n_parts)[idx]
        file_stats = self.options.get("__file_stats__")
        if file_stats and self._arrow_filter:
            # data skipping on delta per-file stats (the delta analogue of the
            # reference's row-group pruning by footer statistics)
            files = [f for f in files
                     if _stats_may_match(file_stats.get(f), self._arrow_filter)]
        files = self._prune_by_partition_values(files, ctx.conf)
        files = self._prune_by_bucket(files, ctx.conf)
        part_names = {n for n, _ in self._partition_columns()}
        cols = [a.name for a in self._output_attrs if a.name not in part_names]
        # partition-column filters were applied above; only data-column
        # leaves push down into the file reads
        row_filter = None
        if self._arrow_filter:
            row_filter = [leaf for leaf in self._arrow_filter
                          if leaf[0] not in part_names] or None
        return files, cols, row_filter

    def _set_input_file(self, ctx: TaskContext, f: str) -> None:
        """Expose the current scan file to input_file_name()/block exprs
        through the task's eval context (reference InputFileUtils)."""
        import os as _os
        ec = ctx.eval_ctx
        ec.input_file = f
        ec.input_block_start = 0
        try:
            ec.input_block_length = _os.path.getsize(f)
        except OSError:
            ec.input_block_length = -1

    def _partition_tables(self, idx: int, ctx: TaskContext) -> Iterator:
        """Host-side reads for one partition under the selected strategy."""
        import pyarrow as pa
        files, cols, row_filter = self._partition_files(idx, ctx)
        if not files:
            return

        def read(f):
            return self._attach_partition_cols(
                _read_one(f, self.fmt, cols, row_filter, self.options), f)

        def set_input_file(f):
            self._set_input_file(ctx, f)

        strategy = str(ctx.conf.get(PARQUET_READER_TYPE)).upper()
        if strategy == "AUTO":
            strategy = "COALESCING" if len(files) > 1 else "PERFILE"
        if strategy == "MULTITHREADED":
            n_threads = ctx.conf.get(MULTITHREAD_READ_NUM_THREADS)
            with _fut.ThreadPoolExecutor(max_workers=n_threads) as pool:
                futs = [(f, pool.submit(read, f)) for f in files]
                for f, fut in futs:
                    t = fut.result()
                    if t.num_rows:
                        set_input_file(f)
                        yield t
        elif strategy == "COALESCING":
            tables = [read(f) for f in files]
            tables = [t for t in tables if t.num_rows] or tables[:1]
            # coalesced batches span files; expose the first (the reference's
            # coalescing reader tracks per-block, a planned refinement)
            set_input_file(files[0])
            yield pa.concat_tables(tables, promote_options="permissive")
        else:  # PERFILE
            from ..config import PARQUET_CHUNK_BYTES
            chunk_bytes = (ctx.conf.get(PARQUET_CHUNK_BYTES)
                           if self.fmt == "parquet" else 0)
            for f in files:
                chunkable = (chunk_bytes > 0 and self.fmt == "parquet"
                             and (self.options or {}).get(
                                 "__iceberg_field_ids__") is None
                             and f not in (self.options or {}).get(
                                 "__dv_rows__", {}))
                if chunkable:
                    rp = _resolve_cache_path(f, self.options)
                    for t in _read_parquet_chunks(rp, cols, row_filter,
                                                  self.options, chunk_bytes):
                        t = self._attach_partition_cols(t, f)
                        if t.num_rows:
                            set_input_file(f)
                            yield t
                    continue
                t = read(f)
                if t.num_rows:
                    set_input_file(f)
                    yield t


class CpuFileScanExec(FileScanBase, CpuExec):
    def __init__(self, paths, fmt, output, pushed_filters=(), options=None,
                 num_partitions=None):
        CpuExec.__init__(self, [])
        self._init_scan(paths, fmt, output, pushed_filters, options,
                        num_partitions)

    def execute_partition(self, idx: int, ctx: TaskContext) -> Iterator:
        from ..types import to_arrow
        import pyarrow as pa
        schema = pa.schema([(a.name, to_arrow(a.dtype))
                            for a in self._output_attrs])
        for t in self._partition_tables(idx, ctx):
            yield t.select([a.name for a in self._output_attrs]).cast(schema)


class TpuFileScanExec(FileScanBase, TpuExec):
    """Host decode → device upload (reference GpuParquetPartitionReaderFactory:
    semaphore acquire happens just before upload, GpuParquetScan.scala:1983)."""

    def __init__(self, paths, fmt, output, pushed_filters=(), options=None,
                 num_partitions=None):
        TpuExec.__init__(self, [])
        self._init_scan(paths, fmt, output, pushed_filters, options,
                        num_partitions)

    def additional_metrics(self):
        return {"scanTime": "ESSENTIAL", "uploadTime": "MODERATE",
                "filesRead": "DEBUG", "decodeTime": "MODERATE",
                "hostDecodeTime": "MODERATE", "decodeDispatches": "DEBUG",
                "decodeFallbackColumns": "DEBUG",
                "rowsDecoded": "DEBUG", "columnsDecoded": "DEBUG",
                "columnsGeneral": "DEBUG"}

    def query_counters(self):
        # what the device decoder read for this query; a file, row group or
        # column that fell back to the host counts under decode_stats()'
        # fallback_* and not here
        return [("scan.files", self.metrics["filesRead"]),
                ("scan.row_groups", self.metrics["decodeDispatches"]),
                ("scan.rows", self.metrics["rowsDecoded"]),
                ("scan.columns_decoded", self.metrics["columnsDecoded"]),
                ("scan.columns_general", self.metrics["columnsGeneral"])]

    def _device_decode_applies(self, ctx: TaskContext) -> bool:
        """Whole-scan eligibility for the device parquet decode path;
        per-file and per-column demotion happens inside it."""
        if self.fmt != "parquet":
            return False
        from ..config import PARQUET_DEVICE_DECODE_ENABLED
        if not ctx.conf.get(PARQUET_DEVICE_DECODE_ENABLED):
            return False
        # iceberg field-id remapping keeps its dedicated reader
        return (self.options or {}).get("__iceberg_field_ids__") is None

    def internal_do_execute_columnar(self, idx: int, ctx: TaskContext) -> Iterator:
        from ..types import to_arrow
        import pyarrow as pa
        from ..memory.semaphore import TpuSemaphore
        from ..obs import span as _obs_span
        schema = pa.schema([(a.name, to_arrow(a.dtype))
                            for a in self._output_attrs])
        names = [a.name for a in self._output_attrs]
        if self._device_decode_applies(ctx):
            yield from self._execute_device(idx, ctx, schema, names)
            return
        it = self._partition_tables(idx, ctx)
        while True:
            # host pyarrow decode happens inside the generator pull: time it
            # so the bench's host-vs-device decode breakdown is honest
            with self.metrics["hostDecodeTime"].timed(), \
                    _obs_span("scan.decode", cat="io", device=False):
                t = next(it, None)
            if t is None:
                return
            with self.metrics["scanTime"].timed():
                t = t.select(names).cast(schema)
            self.metrics["filesRead"].add(1)
            # admission control before taking HBM (reference semaphore pattern)
            TpuSemaphore.get(ctx.conf).acquire_if_necessary(ctx)
            with self.metrics["uploadTime"].timed():
                yield TpuColumnarBatch.from_arrow(t).rename(names)

    def _execute_device(self, idx: int, ctx: TaskContext, schema,
                        names) -> Iterator:
        """Device parquet decode (reference GpuParquetScan.scala:1983,2506:
        host footer/page-header walk + decompression, then device decode
        under the semaphore): one batched decode dispatch per row group,
        per-column host fallback zipped into the same batch, per-file and
        per-row-group host fallback on decode errors — results are
        bit-identical to the host path either way."""
        import pyarrow as pa

        from ..memory.semaphore import TpuSemaphore
        from .device_decode import DeviceDecodeError, DeviceFileDecoder
        files, cols, row_filter = self._partition_files(idx, ctx)
        part_names = {n for n, _ in self._partition_columns()}
        attrs = [a for a in self._output_attrs if a.name not in part_names]
        dv_map = (self.options or {}).get("__dv_rows__", {})

        def host_file(f):
            """Whole-file host fallback (also the deletion-vector path)."""
            with self.metrics["hostDecodeTime"].timed():
                t = self._attach_partition_cols(
                    _read_one(f, self.fmt, cols, row_filter, self.options),
                    f)
            if not t.num_rows:
                return
            with self.metrics["scanTime"].timed():
                t = t.select(names).cast(schema)
            self._set_input_file(ctx, f)
            TpuSemaphore.get(ctx.conf).acquire_if_necessary(ctx)
            with self.metrics["uploadTime"].timed():
                yield TpuColumnarBatch.from_arrow(t).rename(names)

        def host_row_group(f, dec, rgi):
            """One row group on host: decode-error healing re-reads exactly
            the failed row group, never duplicating already-yielded ones."""
            with self.metrics["hostDecodeTime"].timed():
                t = dec.pf.read_row_groups([rgi], columns=cols)
                t = _postprocess_parquet(t, f, self.options,
                                         kv_metadata=dec.md.metadata)
            t = self._attach_partition_cols(t, f)
            with self.metrics["scanTime"].timed():
                t = t.select(names).cast(schema)
            TpuSemaphore.get(ctx.conf).acquire_if_necessary(ctx)
            with self.metrics["uploadTime"].timed():
                return TpuColumnarBatch.from_arrow(t).rename(names)

        for f in files:
            if f in dv_map:
                yield from host_file(f)
                continue
            rp = _resolve_cache_path(f, self.options)
            try:
                with self.metrics["decodeTime"].timed():
                    dec = DeviceFileDecoder(rp, attrs, ctx.conf)
            except DeviceDecodeError:
                from .device_decode import _bump
                _bump("fallback_files")
                yield from host_file(f)
                continue
            self.metrics["filesRead"].add(1)
            try:
                for rgi in dec.row_groups(row_filter):
                    try:
                        # the decoder acquires the semaphore only for its
                        # device staging+dispatch; host page walking
                        # overlaps other tasks' device work.
                        # decodeTime/hostDecodeTime split inside
                        # decode_row_group.
                        batch = dec.decode_row_group(rgi, self.metrics,
                                                     ctx=ctx)
                        batch = self._attach_partition_vectors(batch, f,
                                                               names)
                    except DeviceDecodeError:
                        from .device_decode import _bump
                        _bump("fallback_row_groups")
                        # host_row_group carries the full output schema
                        batch = host_row_group(f, dec, rgi)
                    self._set_input_file(ctx, f)
                    yield batch
            finally:
                # one open range-reader fd per file: released even when a
                # downstream operator abandons the scan mid-file (TL020)
                dec.close()

    def _attach_partition_vectors(self, batch: TpuColumnarBatch, f: str,
                                  names) -> TpuColumnarBatch:
        """Append the file's hive-partition values as constant device
        columns and order per the scan output (the device-path analogue of
        `_attach_partition_cols`)."""
        pcols = self._partition_columns()
        if not pcols:
            return batch
        from ..columnar.vector import TpuColumnVector
        vals = self.options.get("__partition_values__", {}).get(f, {})
        cap = batch.capacity
        n = batch.num_rows  # host int from file metadata: no device sync
        by = {nm: c for nm, c in zip(batch.names, batch.columns)}
        for name, dtype in pcols:
            py = _partition_value(vals.get(name), dtype)
            by[name] = TpuColumnVector.from_scalar(py, dtype, n,
                                                   capacity=cap)
        return TpuColumnarBatch([by[nm] for nm in names], n, list(names))
