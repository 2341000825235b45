"""Seeded table generator and the Parquet writer of the benchmark.

A configuration file says what its tables are (`tables`, see manifest.py): for
each its rows, its columns as generator specs and its storage. A configuration
with no `tables` key has the one table this module knows by heart: LINEITEM, a
copy of what `spark_rapids_tpu/datagen.py::tpch_lineitem` draws (uniform values
over the same ranges; not dbgen). Every Parquet table is written as PR 23 wrote
LINEITEM: snappy, every column REQUIRED, dictionary on, one row group a chunk,
pyarrow's page size. The departures from dbgen are listed under `assumed` in
each configuration file. Everything is bulk
numpy, so that 60 M rows take seconds. Nothing here imports the program.

Every 2^20-row chunk of every column has its own generator keyed by (seed,
chunk, table, column), so a table is the same whether it is made whole, in
slices or streamed into row groups, and a column's values do not depend on
which others are drawn.

Column specs, as JSON lists (`[kind, arguments...]`):
  ["key", k]            uniform INT over rows // k values
  ["suppkey", k]        one of the four suppliers of the row's `l_partkey`
  ["int", lo, hi]       uniform INT over [lo, hi]
  ["double", lo, hi]    uniform DOUBLE over [lo, hi)
  ["char1", "RAN"]      one of the letters, CHAR(1)
  ["date"]              uniform DATE over 1992-01-01 .. 1998-12-30, or over
                        [lo, hi) days since 1970 given as ["date", lo, hi]
  ["choice", [values]]  one of the strings, CHAR(longest)
  ["seq"]               the row's index: a dense BIGINT primary key
  ["fk", "table.column", {"unreferenced": u}]
                        a uniform BIGINT draw over the `seq` key of another
                        table; a share u of the keys, spread evenly, is never drawn
  ["after", "fk column", "table.column", lo, hi]
                        the DATE of the row that this table's fk column points
                        at, plus lo..hi days (dbgen ships 1..121 days after the order)
"""

from __future__ import annotations

import os
import threading
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

CHUNK = 1 << 20
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIPINSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
DATE_LO, DATE_HI = 8035, 10590          # days since 1970: 1992-01-01 .. 1998-12-31

#: column -> (kind, arguments); the order is the order the draws are made in
LINEITEM = {
    "l_orderkey": ("key", 4),            # cardinality = rows // 4
    "l_partkey": ("key", 20),
    "l_suppkey": ("suppkey", 100),
    "l_quantity": ("int", 1, 50),
    "l_extendedprice": ("double", 900.0, 105000.0),
    "l_discount": ("double", 0.0, 0.1),
    "l_tax": ("double", 0.0, 0.08),
    "l_returnflag": ("char1", b"RAN"),
    "l_linestatus": ("char1", b"OF"),
    "l_shipdate": ("date",),
    "l_commitdate": ("date",),
    "l_receiptdate": ("date",),
    "l_shipmode": ("choice", SHIPMODES),
    "l_shipinstruct": ("choice", SHIPINSTRUCT),
}
#: bytes a row of the column takes on the device as the question sees it
#: (DOUBLE and BIGINT 8, INT/DATE 4, CHAR(n) n); the roofline counts these, not
#: the engine's layout (its strings also carry 4-byte offsets)
DEVICE_WIDTH = {"key": 4, "suppkey": 8, "int": 4, "double": 8, "char1": 1,
                "date": 4, "seq": 8, "fk": 8, "after": 4}


class Table:
    """One table of a configuration: its rows and its column specs (foreign
    keys resolved to the size of their domain)."""

    def __init__(self, name: str, rows: int, columns: dict, storage: str,
                 stream: tuple = (), cached=None):
        self.name, self.rows, self.storage = name, int(rows), storage
        self.columns = {k: tuple(v) for k, v in columns.items()}
        self.cached = list(cached or self.columns)   # what a resident table holds
        self._stream = list(stream)      # what tells this table's generators from another's
        self._order = list(self.columns)
        self._whole = {}                 # (seed, column) -> the column whole, for `after`
        self._lock = threading.Lock()

    def held(self) -> list:
        """The columns a query can read: a resident table's cached ones, a file's all."""
        return self.cached if self.storage == "resident" else list(self.columns)

    def width(self, column: str) -> int:
        """Bytes a row of the column takes on the device (DEVICE_WIDTH)."""
        spec = self.columns[column]
        if spec[0] == "choice":
            return max(len(v) for v in spec[1])
        return DEVICE_WIDTH[spec[0]]

    def _column(self, seed: int, index: int, name: str, n: int):
        """One column of one chunk."""
        spec = self.columns[name]
        rng = np.random.default_rng(
            [int(seed), int(index), self._order.index(name), *self._stream])
        kind = spec[0]
        if kind == "key":
            return rng.integers(0, max(self.rows // spec[1], 1), n, dtype=np.int32)
        if kind == "suppkey":
            # one of the part's four suppliers, as the program's generator does
            pk = self._column(seed, index, "l_partkey", n).astype(np.int64)
            return (31 * pk + 7 * rng.integers(0, 4, n)) % max(self.rows // spec[1], 1)
        if kind == "int":
            return rng.integers(spec[1], spec[2] + 1, n, dtype=np.int32)
        if kind == "double":
            return rng.random(n) * (spec[2] - spec[1]) + spec[1]
        if kind == "char1":
            return np.frombuffer(spec[1], np.uint8)[rng.integers(0, len(spec[1]), n)] \
                .view("S1")
        if kind == "date":
            lo, hi = spec[1:3] if len(spec) > 1 else (DATE_LO, DATE_HI)
            return rng.integers(lo, hi, n, dtype=np.int32)
        if kind == "seq":
            return np.arange(index * CHUNK, index * CHUNK + n, dtype=np.int64)
        if kind == "fk":
            # spec: ("fk", rows of the domain, unreferenced share)
            domain, u = spec[1], spec[2]
            m = max(domain - int(domain * u), 1)
            # every 1/u-th key is passed over
            return rng.integers(0, m, n, dtype=np.int64) * domain // m
        if kind == "after":
            # spec: ("after", this table's fk column, the Table it points into, its column, lo, hi)
            there = spec[2].whole(seed, spec[3])[self._column(seed, index, spec[1], n)]
            return there + rng.integers(spec[4], spec[5] + 1, n, dtype=np.int32)
        if kind == "choice":
            # kept as the index into its value list
            return rng.integers(0, len(spec[1]), n).astype(np.int8)
        raise ValueError(f"table {self.name}: column {name}: unknown kind {kind!r}")

    def whole(self, seed: int, column: str):
        """A column of all rows, made once a seed: what a foreign key looks up."""
        with self._lock:
            if (seed, column) not in self._whole:
                if any(s != seed for s, _ in self._whole):      # one seed's columns at a time
                    self._whole.clear()
                self._whole[(seed, column)] = self.generate(seed, [column])[column]
            return self._whole[(seed, column)]

    def generate(self, seed: int, columns, start: int = 0, stop: int = None) -> dict:
        """numpy columns of rows [start, stop)."""
        stop = self.rows if stop is None else stop
        first = start // CHUNK
        out = {k: np.empty(stop - start, self._column(seed, first, k, 1).dtype)
               for k in columns}

        def part(index: int) -> None:
            lo = index * CHUNK
            a, b = max(start - lo, 0), min(stop - lo, CHUNK)
            n = min(CHUNK, self.rows - lo)
            for k in columns:
                out[k][lo + a - start:lo + b - start] = self._column(seed, index, k, n)[a:b]

        # numpy's generators release the interpreter lock while they draw; the
        # chunks are written into arrays made once, so no memory is touched twice
        with ThreadPoolExecutor(max_workers=8) as pool:
            list(pool.map(part, range(first, (stop + CHUNK - 1) // CHUNK)))
        return out

    def kept(self, cols: dict, keep) -> dict:
        """What the reference is handed of generated columns: the `keep`
        columns, a choice as its CHAR(n) values."""
        return {k: (np.array(self.columns[k][1], "S")[v]
                    if self.columns[k][0] == "choice" else v)
                for k, v in cols.items() if k in keep}

    def to_arrow(self, cols: dict) -> pa.Table:
        """The generated columns as the Arrow table the program is handed:
        DOUBLE, INT, BIGINT, DATE32, CHAR(1) as string, choices as strings,
        every column REQUIRED as the TPC-H schema declares it."""
        arrays, fields = [], []
        for name, v in cols.items():
            spec = self.columns[name]
            if spec[0] == "char1":
                n = len(v)
                a = pa.StringArray.from_buffers(
                    n, pa.py_buffer(np.arange(n + 1, dtype=np.int32)),
                    pa.py_buffer(np.ascontiguousarray(v).view(np.uint8)))
            elif spec[0] == "date":
                a = pa.array(v, pa.int32()).cast(pa.date32())
            elif spec[0] == "choice":
                a = pa.DictionaryArray.from_arrays(
                    pa.array(v), pa.array(spec[1])).cast(pa.string())
            else:
                a = pa.array(v)
            arrays.append(a)
            fields.append(pa.field(name, a.type, nullable=False))
        return pa.Table.from_arrays(arrays, schema=pa.schema(fields))

    def write_parquet(self, path: str, seed: int, start: int = 0, stop: int = None,
                      keep=()) -> dict:
        """Stream rows [start, stop) into one snappy Parquet file, one row
        group per 2^20-row chunk. Returns the `keep` columns as numpy, for the
        reference."""
        stop = self.rows if stop is None else stop
        os.makedirs(os.path.dirname(path), exist_ok=True)
        names = list(self.columns)
        kept, writer = [], None
        try:
            for lo in range(start, stop, CHUNK):
                cols = self.generate(seed, names, lo, min(lo + CHUNK, stop))
                t = self.to_arrow(cols)
                if writer is None:
                    writer = pq.ParquetWriter(path, t.schema, compression="snappy",
                                              use_dictionary=True)
                writer.write_table(t, row_group_size=CHUNK)
                kept.append(self.kept(cols, keep))
        finally:
            if writer is not None:
                writer.close()
        return {k: np.concatenate([p[k] for p in kept]) for k in (kept[0] if kept else ())}


def _rows(spec, sizes: dict) -> int:
    """A table's rows: a number, or {"of": table, "ratio": [a, b]} = rows of
    that table * a // b (so that a rehearsal shrinks the tables together)."""
    if isinstance(spec, dict):
        a, b = spec["ratio"]
        return max(sizes[spec["of"]] * int(a) // int(b), 1)
    return int(spec)


def tables(config: dict, rows: int = 0) -> dict:
    """{name: Table} of a configuration; `rows`, if given, replaces the rows of
    the configuration's own `table` (a rehearsal). Without a `tables` key: the
    one LINEITEM of PR 23, resident with the configuration's `columns`."""
    main = config.get("table", "lineitem")
    rows = int(rows or config["rows"])
    if "tables" not in config:
        # a projected cache: the generators stay keyed by a column's place among all 14
        return {main: Table(main, rows, LINEITEM, config["storage"],
                            cached=config.get("columns"))}
    sizes, declared = {main: rows}, config["tables"]
    pending = [n for n in declared if n != main]
    while pending:                        # a ratio may name a table declared later
        ready = [n for n in pending if not isinstance(declared[n]["rows"], dict)
                 or declared[n]["rows"]["of"] in sizes]
        if not ready:
            raise ValueError(f"tables {pending}: rows refer to each other in a circle")
        for n in ready:
            sizes[n] = _rows(declared[n]["rows"], sizes)
            pending.remove(n)
    out = {}
    for name, d in declared.items():
        cols = {}
        for c, spec in d["columns"].items():
            if spec[0] == "fk":
                target, column = spec[1].split(".")
                if declared[target]["columns"][column][0] != "seq":
                    raise ValueError(f"{name}.{c}: {spec[1]} is not a seq key")
                opts = spec[2] if len(spec) > 2 else {}
                spec = ("fk", sizes[target], float(opts.get("unreferenced", 0.0)))
            elif spec[0] == "char1":
                spec = ("char1", spec[1].encode())
            cols[c] = spec
        out[name] = Table(name, sizes[name], cols, d["storage"],
                          stream=(zlib.crc32(name.encode()),))
    for name, table in out.items():       # an `after` column looks into a table made above
        for c, spec in table.columns.items():
            if spec[0] == "after":
                target, column = spec[2].split(".")
                fk = declared[name]["columns"][spec[1]]
                if fk[0] != "fk" or fk[1].split(".")[0] != target:
                    raise ValueError(f"{name}.{c}: {spec[1]} is no foreign key into {target}")
                table.columns[c] = ("after", spec[1], out[target], column, spec[3], spec[4])
    return out


def write_parquet(path: str, seed: int, rows: int, start: int = 0, stop: int = None,
                  keep=()) -> dict:
    """PR 23's entry, which tests/test_parquet_device_decode.py calls: rows
    [start, stop) of a `rows`-row LINEITEM as the Parquet cells' file."""
    return tables({"rows": rows, "storage": "parquet"})["lineitem"] \
        .write_parquet(path, seed, start, stop, keep)


def tenant_slices(rows: int, tenants: int) -> list:
    """[(start, stop)] of the even contiguous slices the tenants hold."""
    edges = [rows * i // tenants for i in range(tenants + 1)]
    return list(zip(edges[:-1], edges[1:]))
