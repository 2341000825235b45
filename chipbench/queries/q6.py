"""TPC-H Q6, forecasting revenue change (clause 2.4.6) — the program's side
(`build`) and the plain reference (`reference`). Without parameters both use
the specification's validation literals (1994-01-01, one year, discount 0.06
+- 0.01, quantity < 24); a traffic file's `params` may give any set of the
substitution domains (clause 2.4.6.3: `year` 1993..1997, `discount`
0.02..0.09, `quantity` 24..25). Both sides take every literal from `literals`."""

import datetime

import numpy as np

COLUMNS = {"lineitem": ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")}
#: scheduler class a tenant submits this template under
SLO_CLASS = "interactive"
EPOCH = datetime.date(1970, 1, 1).toordinal()


def literals(year=1994, discount=0.06, quantity=24):
    """(first day, day after the last, least discount, greatest, quantity
    bound): dates as days since 1970, the discounts rounded to the cent so
    that neither side sees 0.06 - 0.01 = 0.049999999999999996."""
    return (datetime.date(year, 1, 1).toordinal() - EPOCH,
            datetime.date(year + 1, 1, 1).toordinal() - EPOCH,
            round(discount - 0.01, 2), round(discount + 0.01, 2), quantity)


def build(F, tables, **params):
    """The DataFrame the window collects. Copied from benchmarks/tpch.py::q6."""
    date_lo, date_hi, disc_lo, disc_hi, qty = literals(**params)
    return (tables["lineitem"].filter((F.col("l_shipdate") >= date_lo)
                                      & (F.col("l_shipdate") < date_hi)
                                      & (F.col("l_discount") >= disc_lo)
                                      & (F.col("l_discount") <= disc_hi)
                                      & (F.col("l_quantity") < qty))
            .agg(F.sum(F.col("l_extendedprice") * F.col("l_discount"))
                 .alias("revenue")))


def reference(tables: dict, **params) -> list:
    """Rows of the answer from the generated numpy columns: float64 products,
    summed in extended precision so the reference is the more exact side."""
    date_lo, date_hi, disc_lo, disc_hi, qty = literals(**params)
    c = tables["lineitem"]
    m = ((c["l_shipdate"] >= date_lo) & (c["l_shipdate"] < date_hi)
         & (c["l_discount"] >= disc_lo) & (c["l_discount"] <= disc_hi)
         & (c["l_quantity"] < qty))
    if not m.any():
        return [{"revenue": None}]
    prod = c["l_extendedprice"][m] * c["l_discount"][m]
    return [{"revenue": float(np.sum(prod, dtype=np.longdouble))}]
