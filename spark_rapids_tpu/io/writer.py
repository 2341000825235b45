"""DataFrameWriter: columnar file writers.

Reference: ColumnarOutputWriter.scala (251, retry-aware base) +
GpuParquetFileFormat.scala / GpuOrcFileFormat.scala / GpuFileFormatDataWriter
(dynamic partitioning). Host pyarrow writers consume the executed plan's
partition streams — one output file per partition (part-NNNNN), Spark layout,
with dynamic partitionBy subdirectories."""

from __future__ import annotations

import os
import shutil
from typing import List, Optional


class DataFrameWriter:
    def __init__(self, df):
        self._df = df
        self._mode = "errorifexists"
        self._options = {}
        self._partition_by: List[str] = []
        self._bucket_by: List[str] = []
        self._num_buckets = 0

    def mode(self, m: str) -> "DataFrameWriter":
        self._mode = m.lower()
        return self

    def option(self, key, value) -> "DataFrameWriter":
        self._options[str(key)] = value
        return self

    def partitionBy(self, *cols: str) -> "DataFrameWriter":
        self._partition_by = list(cols)
        return self

    def bucketBy(self, num_buckets: int, *cols: str) -> "DataFrameWriter":
        """Hash-bucketed output (reference GpuFileFormatWriter bucketing):
        rows split into `num_buckets` files per task by
        pmod(murmur3(cols), n), with a _bucket_spec.json sidecar the scan
        uses for bucket pruning."""
        self._bucket_by = list(cols)
        self._num_buckets = int(num_buckets)
        return self

    def format(self, fmt: str) -> "DataFrameWriter":
        self._options["__format__"] = str(fmt).lower()
        return self

    def save(self, path: str) -> None:
        fmt = self._options.pop("__format__", "parquet")
        if fmt == "delta":
            return self.delta(path)
        writers = {"parquet": self.parquet, "orc": self.orc, "csv": self.csv,
                   "json": self.json, "avro": self.avro,
                   "hivetext": self.hive_text}
        if fmt not in writers:
            raise ValueError(f"unknown write format {fmt}")
        return writers[fmt](path)

    def delta(self, path: str) -> None:
        """Transactional delta write (reference delta-lake/ write side)."""
        from .delta import write_delta
        mode = {"errorifexists": "errorifexists", "error": "errorifexists"}.get(
            self._mode, self._mode)
        write_delta(self._df, path, mode, self._partition_by,
                    options={k: v for k, v in self._options.items()
                             if k.startswith("delta.")})

    def _prepare_dir(self, path: str) -> None:
        if os.path.exists(path):
            if self._mode == "overwrite":
                shutil.rmtree(path)
            elif self._mode in ("ignore",):
                return
            elif self._mode != "append":
                raise FileExistsError(f"path {path} exists (mode={self._mode})")
        os.makedirs(path, exist_ok=True)

    def _execute_partitions(self):
        """Yield (partition_index, arrow table) from the physical plan
        (non-file consumers: delta/iceberg transaction logs)."""
        from ..execs.base import TaskContext
        from ..plan.overrides import plan_query
        session = self._df.session
        conf = session._rapids_conf()
        final, _, _ = plan_query(self._df._plan, conf)
        names = [a.name for a in final.output]
        import pyarrow as pa
        for p in range(final.num_partitions()):
            ctx = TaskContext(p, conf)
            try:
                tables = [t.rename_columns(names)
                          for t in final.execute_partition(p, ctx) if t.num_rows]
            finally:
                ctx.complete()
            if tables:
                yield p, pa.concat_tables(tables)

    def _write(self, path: str, ext: str, write_fn, fmt: str = None) -> None:
        """File-format writes run as a DataWritingCommandExec at the plan
        root, so the override engine tags/converts/meters the write
        (reference GpuDataWritingCommandExec) instead of the driver
        hand-executing partitions."""
        import pyarrow as pa
        from ..execs.base import TaskContext
        from ..execs.write import CpuDataWritingCommandExec, WriteSpec
        from ..plan.overrides import TpuOverrides, plan_cpu
        self._prepare_dir(path)
        session = self._df.session
        conf = session._rapids_conf()
        child, _, _ = plan_cpu(self._df._plan, conf)
        bucket_by, num_buckets = self._bucket_by, self._num_buckets
        if num_buckets:
            from ..config import BUCKETING_WRITE_ENABLED
            if not conf.get(BUCKETING_WRITE_ENABLED):
                bucket_by, num_buckets = [], 0
            else:
                import json as _json
                spec_path = os.path.join(path, "_bucket_spec.json")
                if self._mode == "append" and os.path.exists(spec_path):
                    # appending with a different bucket spec would leave
                    # files hashed under two moduli behind one sidecar —
                    # read-side pruning would silently drop rows (Spark
                    # rejects the same mismatch at the catalog layer)
                    with open(spec_path) as f:
                        old = _json.load(f)
                    if (old.get("numBuckets") != num_buckets
                            or old.get("bucketColumns") != bucket_by):
                        raise ValueError(
                            f"append to {path} with bucket spec "
                            f"({num_buckets}, {bucket_by}) conflicts with "
                            f"existing ({old.get('numBuckets')}, "
                            f"{old.get('bucketColumns')})")
                with open(spec_path, "w") as f:
                    _json.dump({"numBuckets": num_buckets,
                                "bucketColumns": bucket_by}, f)
        spec = WriteSpec(fmt or ext, path, ext, write_fn,
                         list(self._partition_by), dict(self._options),
                         bucket_by=bucket_by, num_buckets=num_buckets)
        cmd = CpuDataWritingCommandExec(child, spec)
        final = TpuOverrides.apply(cmd, conf)
        wrote_files = False
        for p in range(final.num_partitions()):
            ctx = TaskContext(p, conf)
            try:
                for _ in final.execute_partition(p, ctx):
                    pass
            finally:
                ctx.complete()
        wrote_files = any(
            os.path.isfile(os.path.join(root, f))
            for root, _, files in os.walk(path) for f in files)
        if not wrote_files:
            # empty result: still record the schema (parquet only)
            from ..types import to_arrow
            schema = pa.schema([(a.name, to_arrow(a.dtype))
                                for a in self._df._plan.output])
            write_fn(schema.empty_table(),
                     os.path.join(path, f"part-00000.{ext}"))

    def parquet(self, path: str) -> None:
        import pyarrow.parquet as pq
        compression = self._options.get("compression", "snappy")
        self._write(path, "parquet",
                    lambda t, p: pq.write_table(t, p, compression=compression))

    def orc(self, path: str) -> None:
        import pyarrow.orc as paorc
        self._write(path, "orc", lambda t, p: paorc.write_table(t, p))

    def csv(self, path: str) -> None:
        import pyarrow.csv as pacsv
        header = str(self._options.get("header", "true")).lower() == "true"
        opts = pacsv.WriteOptions(include_header=header)
        self._write(path, "csv",
                    lambda t, p: pacsv.write_csv(t, p, write_options=opts))

    def json(self, path: str) -> None:
        def write_json(t, p):
            import json as _json
            with open(p, "w") as f:
                for row in t.to_pylist():
                    f.write(_json.dumps(row, default=str) + "\n")
        self._write(path, "json", write_json)

    def avro(self, path: str) -> None:
        from .avro import write_avro
        codec = str(self._options.get("compression", "snappy")).lower()
        codec = {"uncompressed": "null", "zstd": "zstandard"}.get(codec, codec)
        self._write(path, "avro", lambda t, p: write_avro(t, p, codec=codec))

    def hive_text(self, path: str) -> None:
        from .hive_text import write_hive_text
        opts = dict(self._options)
        self._write(path, "txt", lambda t, p: write_hive_text(t, p, opts))
