"""TPC-H Q3, shipping priority (clause 2.4.3), with the specification's
validation literals (BUILDING, 1995-03-15) — the program's side (`build`) and
the plain reference (`reference`): CUSTOMER |x| ORDERS |x| LINEITEM, grouped
by order, the ten largest revenues."""

import datetime

import numpy as np

COLUMNS = {"customer": ("c_custkey", "c_mktsegment"),
           "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"),
           "lineitem": ("l_orderkey", "l_extendedprice", "l_discount", "l_shipdate")}
SLO_CLASS = "batch"
SEGMENT = "BUILDING"
DATE = 9204                               # 1995-03-15 as days since 1970
EPOCH = datetime.date(1970, 1, 1)


def build(F, tables):
    """The DataFrame the window collects: benchmarks/tpch.py::q3 with the
    specification's third group key, its second sort key and its column order."""
    cust, orders, li = tables["customer"], tables["orders"], tables["lineitem"]
    return (cust.filter(F.col("c_mktsegment") == SEGMENT)
            .join(orders.filter(F.col("o_orderdate") < DATE),
                  on=cust["c_custkey"] == orders["o_custkey"])
            .join(li.filter(F.col("l_shipdate") > DATE),
                  on=orders["o_orderkey"] == li["l_orderkey"])
            .withColumn("rev", F.col("l_extendedprice") * (1 - F.col("l_discount")))
            .groupBy("l_orderkey", "o_orderdate", "o_shippriority")
            .agg(F.sum(F.col("rev")).alias("revenue"))
            .sort(F.col("revenue").desc(), F.col("o_orderdate"))
            .limit(10)
            .select("l_orderkey", "revenue", "o_orderdate", "o_shippriority"))


def reference(tables: dict) -> list:
    """The ten rows, by revenue descending, then o_orderdate, then l_orderkey
    (the specification stops at the date; the key makes the order total).
    Revenues are ranked by float64 sums and the ten that win are summed again
    in extended precision."""
    cust, orders, li = tables["customer"], tables["orders"], tables["lineitem"]
    building = np.zeros(int(cust["c_custkey"].max()) + 1, bool)
    building[cust["c_custkey"][cust["c_mktsegment"] == SEGMENT.encode()]] = True
    open_order = (orders["o_orderdate"] < DATE) & building[orders["o_custkey"]]
    # row of ORDERS that holds each key (a primary key: at most one), -1 for none
    at = np.full(int(max(orders["o_orderkey"].max(), li["l_orderkey"].max())) + 1, -1, np.int64)
    at[orders["o_orderkey"]] = np.arange(len(open_order))
    late = np.flatnonzero(li["l_shipdate"] > DATE)
    row = at[li["l_orderkey"][late]]
    keep = (row >= 0) & open_order[row]
    late, row = late[keep], row[keep]
    rev = li["l_extendedprice"][late] * (1 - li["l_discount"][late])
    total = np.bincount(row, weights=rev, minlength=len(open_order))
    groups = np.flatnonzero(np.bincount(row, minlength=len(open_order)))
    first = np.lexsort((orders["o_orderkey"][groups], orders["o_orderdate"][groups],
                        -total[groups]))[:10]
    return [{"l_orderkey": int(orders["o_orderkey"][g]),
             "revenue": float(np.sum(rev[row == g], dtype=np.longdouble)),
             "o_orderdate": EPOCH + datetime.timedelta(int(orders["o_orderdate"][g])),
             "o_shippriority": int(orders["o_shippriority"][g])}
            for g in groups[first]]
