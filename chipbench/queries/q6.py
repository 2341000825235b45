"""TPC-H Q6, forecasting revenue change, with the specification's validation
literals (1994-01-01, one year, discount 0.06 +- 0.01, quantity < 24) — the
program's side (`build`) and the plain reference (`reference`)."""

import numpy as np

COLUMNS = ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")
#: scheduler class a tenant submits this template under
SLO_CLASS = "interactive"
DATE_LO, DATE_HI = 8766, 9131            # 1994-01-01, 1995-01-01 as days since 1970


def build(F, lineitem):
    """The DataFrame the window collects. Copied from benchmarks/tpch.py::q6."""
    return (lineitem.filter((F.col("l_shipdate") >= DATE_LO)
                            & (F.col("l_shipdate") < DATE_HI)
                            & (F.col("l_discount") >= 0.05)
                            & (F.col("l_discount") <= 0.07)
                            & (F.col("l_quantity") < 24))
            .agg(F.sum(F.col("l_extendedprice") * F.col("l_discount"))
                 .alias("revenue")))


def reference(c: dict) -> list:
    """Rows of the answer from the generated numpy columns: float64 products,
    summed in extended precision so the reference is the more exact side."""
    m = ((c["l_shipdate"] >= DATE_LO) & (c["l_shipdate"] < DATE_HI)
         & (c["l_discount"] >= 0.05) & (c["l_discount"] <= 0.07)
         & (c["l_quantity"] < 24))
    if not m.any():
        return [{"revenue": None}]
    prod = c["l_extendedprice"][m] * c["l_discount"][m]
    return [{"revenue": float(np.sum(prod, dtype=np.longdouble))}]
