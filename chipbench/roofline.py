"""Work a query needs, counted from the question and not from the program,
and the chip's published peaks. A share of the roofline is the least time the
chip could take (here bytes over the HBM rate: Q1 and Q6 do a few operations
per 8-byte value, far under the compute peak) over the device time spent."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device_kind {device_kind!r} is not in chipbench/peaks.json")
    return table[device_kind]


def resident_bytes(table, columns, rows: int) -> int:
    """Bytes a query over a device-resident table (`datagen.Table`) has to
    read: its rows times the device width of each column it references, once."""
    return rows * sum(table.width(c) for c in columns)


def parquet_bytes(metadata, columns) -> int:
    """Bytes a query over a Parquet file has to read: the uncompressed size of
    the referenced column chunks, from the file's own metadata
    (`pyarrow.parquet.FileMetaData`). A fused decode+aggregate reads them once."""
    total = 0
    for rg in range(metadata.num_row_groups):
        group = metadata.row_group(rg)
        for ci in range(group.num_columns):
            col = group.column(ci)
            if col.path_in_schema in columns:
                total += col.total_uncompressed_size
    return total


def least_seconds(nbytes: int, device_kind: str) -> float:
    return nbytes / peaks(device_kind)["hbm_bytes_per_s"]
