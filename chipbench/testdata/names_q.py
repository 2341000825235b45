"""A rehearsal template over `tpch-names.json`: ORDERS joined to CUSTOMER and
grouped by the customer's formatted name, every group in name order — a text
column through a join, a group-by, a sort and the result. The program's side
(`build`) and the plain reference (`reference`)."""

import numpy as np

COLUMNS = {"customer": ("c_custkey", "c_name"),
           "orders": ("o_custkey", "o_totalprice")}


def build(F, tables):
    cust, orders = tables["customer"], tables["orders"]
    return (orders.join(cust, on=orders["o_custkey"] == cust["c_custkey"])
            .groupBy("c_name")
            .agg(F.count(F.col("o_totalprice")).alias("orders"),
                 F.sum(F.col("o_totalprice")).alias("total"))
            .sort("c_name"))


def reference(tables: dict) -> list:
    """Every customer with orders, by name: the order count and the sum of
    the order prices in extended precision."""
    cust, orders = tables["customer"], tables["orders"]
    at = np.full(int(max(cust["c_custkey"].max(), orders["o_custkey"].max())) + 1, -1, np.int64)
    at[cust["c_custkey"]] = np.arange(len(cust["c_custkey"]))
    row = at[orders["o_custkey"]]
    keep = np.flatnonzero(row >= 0)
    by = keep[np.argsort(row[keep], kind="stable")]
    groups, starts = np.unique(row[by], return_index=True)
    total = np.add.reduceat(orders["o_totalprice"][by].astype(np.longdouble), starts)
    count = np.diff(np.append(starts, len(by)))
    names = cust["c_name"][groups]
    return [{"c_name": names[i].decode(), "orders": int(count[i]), "total": float(total[i])}
            for i in np.argsort(names, kind="stable")]
