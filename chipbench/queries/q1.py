"""TPC-H Q1, pricing summary report, with the specification's validation
literal (DELTA 90: l_shipdate <= 1998-09-02) — the program's side (`build`)
and the plain reference (`reference`)."""

import numpy as np

COLUMNS = {"lineitem": ("l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
                        "l_extendedprice", "l_discount", "l_tax")}
SLO_CLASS = "batch"
SHIP_MAX = 10471                         # 1998-12-01 less 90 days, as days since 1970


def build(F, tables):
    """The DataFrame the window collects. Copied from benchmarks/tpch.py::q1."""
    return (tables["lineitem"].filter(F.col("l_shipdate") <= SHIP_MAX)
            .withColumn("disc_price",
                        F.col("l_extendedprice") * (1 - F.col("l_discount")))
            .withColumn("charge",
                        F.col("l_extendedprice") * (1 - F.col("l_discount"))
                        * (1 + F.col("l_tax")))
            .groupBy("l_returnflag", "l_linestatus")
            .agg(F.sum(F.col("l_quantity")).alias("sum_qty"),
                 F.sum(F.col("l_extendedprice")).alias("sum_base_price"),
                 F.sum(F.col("disc_price")).alias("sum_disc_price"),
                 F.sum(F.col("charge")).alias("sum_charge"),
                 F.avg(F.col("l_quantity")).alias("avg_qty"),
                 F.avg(F.col("l_extendedprice")).alias("avg_price"),
                 F.avg(F.col("l_discount")).alias("avg_disc"),
                 F.count(F.col("l_quantity")).alias("count_order"))
            .sort("l_returnflag", "l_linestatus"))


def reference(tables: dict) -> list:
    """Rows of the answer, ordered by (l_returnflag, l_linestatus): float64
    element arithmetic as SQL DOUBLE prescribes, sums in extended precision."""
    c = tables["lineitem"]
    keep = c["l_shipdate"] <= SHIP_MAX
    key = (c["l_returnflag"].view(np.uint8).astype(np.uint16) << 8) \
        | c["l_linestatus"].view(np.uint8)
    rows = []
    for k in np.flatnonzero(np.bincount(key[keep], minlength=1 << 16)):
        m = keep & (key == k)
        qty, price = c["l_quantity"][m], c["l_extendedprice"][m]
        disc, tax = c["l_discount"][m], c["l_tax"][m]
        disc_price = price * (1 - disc)
        n = int(m.sum())
        s = {name: float(np.sum(v, dtype=np.longdouble)) for name, v in (
            ("price", price), ("disc_price", disc_price),
            ("charge", disc_price * (1 + tax)), ("disc", disc))}
        sum_qty = int(qty.sum(dtype=np.int64))
        rows.append({
            "l_returnflag": chr(k >> 8), "l_linestatus": chr(k & 0xFF),
            "sum_qty": sum_qty, "sum_base_price": s["price"],
            "sum_disc_price": s["disc_price"], "sum_charge": s["charge"],
            "avg_qty": sum_qty / n, "avg_price": s["price"] / n,
            "avg_disc": s["disc"] / n, "count_order": n})
    return rows
