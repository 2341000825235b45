"""Seeded LINEITEM generator and the Parquet writer of the benchmark.

A copy of what `spark_rapids_tpu/datagen.py::tpch_lineitem` draws (uniform
values over the same ranges; not dbgen), rewritten in bulk numpy so that 60 M
rows take seconds: the program's generator builds strings row by row. The
departures from dbgen are listed under `assumed` in each configuration file.
Nothing here imports the program.

Every 2^20-row chunk has its own generator keyed by (seed, chunk), so a table
is the same whether it is made whole, in slices or streamed into row groups.
"""

from __future__ import annotations

import os
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
#: (DOUBLE 8, INT/DATE 4, CHAR(1) 1); the roofline counts these, not the
#: engine's layout (its strings also carry 4-byte offsets)
DEVICE_WIDTH = {"key": 4, "suppkey": 8, "int": 4, "double": 8, "char1": 1,
                "date": 4}


def _column(seed: int, index: int, name: str, n: int, total_rows: int):
    """One column of one chunk. Each (seed, chunk, column) has a generator of
    its own, so a column's values do not depend on which others are drawn."""
    spec = LINEITEM[name]
    rng = np.random.default_rng([int(seed), int(index), list(LINEITEM).index(name)])
    kind = spec[0]
    if kind == "key":
        return rng.integers(0, max(total_rows // spec[1], 1), n, dtype=np.int32)
    if kind == "suppkey":
        # one of the part's four suppliers, as the program's generator does
        pk = _column(seed, index, "l_partkey", n, total_rows).astype(np.int64)
        return (31 * pk + 7 * rng.integers(0, 4, n)) % max(total_rows // spec[1], 1)
    if kind == "int":
        return rng.integers(spec[1], spec[2] + 1, n, dtype=np.int32)
    if kind == "double":
        return rng.random(n) * (spec[2] - spec[1]) + spec[1]
    if kind == "char1":
        return np.frombuffer(spec[1], np.uint8)[rng.integers(0, len(spec[1]), n)] \
            .view("S1")
    if kind == "date":
        return rng.integers(DATE_LO, DATE_HI, n, dtype=np.int32)
    # choice: kept as the index into its value list
    return rng.integers(0, len(spec[1]), n).astype(np.int8)


def _chunk(seed: int, index: int, n: int, total_rows: int, columns) -> dict:
    return {name: _column(seed, index, name, n, total_rows) for name in columns}


def generate(seed: int, rows: int, columns, start: int = 0, stop: int = None) -> dict:
    """numpy columns of rows [start, stop) of a `rows`-row LINEITEM."""
    stop = rows if stop is None else stop

    first = start // CHUNK
    probe = _chunk(seed, first, 1, rows, columns)
    out = {k: np.empty(stop - start, probe[k].dtype) for k in columns}

    def part(index: int) -> None:
        lo = index * CHUNK
        a, b = max(start - lo, 0), min(stop - lo, CHUNK)
        for k in columns:
            out[k][lo + a - start:lo + b - start] = \
                _column(seed, index, k, min(CHUNK, rows - lo), rows)[a:b]

    # numpy's generators release the interpreter lock while they draw; the
    # chunks are written into arrays made once, so no memory is touched twice
    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(part, range(first, (stop + CHUNK - 1) // CHUNK)))
    return out


def tenant_slices(rows: int, tenants: int) -> list:
    """[(start, stop)] of the even contiguous slices the tenants hold."""
    edges = [rows * i // tenants for i in range(tenants + 1)]
    return list(zip(edges[:-1], edges[1:]))


def to_arrow(cols: dict, required: bool = True) -> pa.Table:
    """The generated columns as the Arrow table the program is handed:
    DOUBLE, INT, DATE32, CHAR(1) as string, choices as strings."""
    arrays, fields = [], []
    for name, v in cols.items():
        kind = LINEITEM[name][0]
        if kind == "char1":
            n = len(v)
            a = pa.StringArray.from_buffers(
                n, pa.py_buffer(np.arange(n + 1, dtype=np.int32)),
                pa.py_buffer(np.ascontiguousarray(v).view(np.uint8)))
        elif kind == "date":
            a = pa.array(v, pa.int32()).cast(pa.date32())
        elif kind == "choice":
            a = pa.DictionaryArray.from_arrays(
                pa.array(v), pa.array(LINEITEM[name][1])).cast(pa.string())
        else:
            a = pa.array(v)
        arrays.append(a)
        fields.append(pa.field(name, a.type, nullable=not required))
    return pa.Table.from_arrays(arrays, schema=pa.schema(fields))


def write_parquet(path: str, seed: int, rows: int, start: int = 0,
                  stop: int = None, keep=()) -> dict:
    """Stream rows [start, stop) into one snappy Parquet file, one row group
    per 2^20-row chunk, columns REQUIRED as the TPC-H schema declares them.
    Returns the `keep` columns as numpy, for the reference."""
    stop = rows if stop is None else stop
    os.makedirs(os.path.dirname(path), exist_ok=True)
    names = list(LINEITEM)
    kept, writer = [], None
    try:
        for lo in range(start, stop, CHUNK):
            cols = generate(seed, rows, names, lo, min(lo + CHUNK, stop))
            t = to_arrow(cols)
            if writer is None:
                writer = pq.ParquetWriter(path, t.schema, compression="snappy",
                                          use_dictionary=True)
            writer.write_table(t, row_group_size=CHUNK)
            kept.append({k: cols[k] for k in keep})
    finally:
        if writer is not None:
            writer.close()
    return {k: np.concatenate([p[k] for p in kept]) for k in keep}
