"""Work of a mesh session's collective exchange (one `all_to_all` program over
the cell's chips), counted per chip, and the least time a chip could take for
it. The exchange reads every row it is handed once and writes every row it
hands on once, and the rows whose reduce partition lives on another chip cross
the inter-chip interconnect once. Whichever takes longer bounds the program:
the HBM traffic at `hbm_bytes_per_s` or the interconnect traffic at
`ici_bits_per_s` (chipbench/peaks.json; per chip, as the documentation gives
them)."""

from __future__ import annotations

from .roofline import peaks


def hbm_bytes_per_chip(exchanged_bytes: int, chips: int) -> float:
    """Bytes one chip must move through its HBM: its share of the exchanged
    rows, read once where they arrive from the map side and written once
    where the reduce side finds them."""
    return 2.0 * exchanged_bytes / chips


def ici_bytes_per_chip(moved_bytes: int, chips: int) -> float:
    """Bytes one chip must send over the interconnect: its share of the rows
    that changed chip (a chip sends about what it receives)."""
    return moved_bytes / chips


def least_seconds(exchanged_bytes: int, moved_bytes: int, chips: int,
                  device_kind: str) -> float:
    """The least device time a chip could take for exchanges that carried
    `exchanged_bytes` in all, `moved_bytes` of them to another chip."""
    p = peaks(device_kind)
    return max(hbm_bytes_per_chip(exchanged_bytes, chips) / p["hbm_bytes_per_s"],
               ici_bytes_per_chip(moved_bytes, chips) / (p["ici_bits_per_s"] / 8.0))
