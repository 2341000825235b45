"""MULTICHIP bench: sharded multi-chip query execution over the mesh data
plane (ROADMAP item 2 done-bar).

Runs TPC-H q1/q3/q18 and a TPC-DS sample (q3) through the full framework
twice per query — mesh session (collective exchanges, grouped root
dispatch) vs single-device baseline — via
`spark_rapids_tpu.parallel.sharded.run_mesh_query`, asserting bit-identical
results and O(exchanges) collective launches, then prints ONE compact
parseable JSON summary line LAST (per-chip rows/s, collective-time
breakdown, scaling efficiency vs 1 chip).

Queries are written WITHOUT hand-pruning selects since ISSUE 17: the
logical optimizer's column-pruning pass (plan/optimizer.py, on by
default) narrows every exchange to the referenced columns the way the
hand-written `select`s used to — run() asserts per record that the
planner-pruned plans still run bit-identically with ZERO per-map
exchange fallbacks. String-carrying exchanges (q1's group keys,
q18's final c_name aggregation) ride the collective too since the
dictionary-encode pass landed (`spark.rapids.tpu.exchange.
dictionaryEncode.enabled`): the fabric moves int32 codes plus one
broadcast dictionary per exchange, and the summary records how many
exchanges used it (`string_collectives`, `dict_encode_ms`) — the
per-query `collective_launches` vs `exchanges` split stays the honest
coverage number, now expected to match.

Since the fused dataplane (ISSUE 16) the summary also carries
`compact_fused` (True when every exchange compacted INSIDE the one
cached collective dispatch — False is a regression), the staging-pool
`staging_reuse_hits` counter, and `overlap_segments` (non-zero only when
the opt-in segmented exchange/compute overlap ran; set
``MULTICHIP_OVERLAP=K`` to arm `spark.rapids.tpu.exchange.overlap.*`
with K segments for a round). tools/bench_diff.py gates the
compact/staging phase walls lower-is-better and treats the two new
counters as neutral.

Usage: python benchmarks/multichip.py [--devices N] [--rows N]
(on a machine without N real chips, run through
`__graft_entry__.dryrun_multichip`, which virtualizes an N-device CPU
platform first).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _tpch_tables(s, rows: int, parts: int):
    import benchmarks.tpch as tpch
    return tpch.load_tables(s, rows, parts=parts)


def _q1(rows: int, parts: int):
    def build(s):
        import benchmarks.tpch as tpch
        return tpch.q1(s, _tpch_tables(s, rows, parts))
    return build


def _q3(rows: int, parts: int):
    """TPC-H q3, unpruned: the optimizer's ColumnPruning pass narrows the
    scans and exchange payloads to keys/dates/doubles (what the hand-
    written selects did through r07), so the whole query rides the
    collective data plane."""
    def build(s):
        import spark_rapids_tpu.functions as F
        t = _tpch_tables(s, rows, parts)
        cust = t["customer"].filter(F.col("c_mktsegment") == "BUILDING")
        orders = t["orders"]
        li = t["lineitem"]
        return (cust.join(orders, on=cust["c_custkey"] == orders["o_custkey"])
                .join(li, on=orders["o_orderkey"] == li["l_orderkey"])
                .withColumn("revenue",
                            F.col("l_extendedprice")
                            * (1 - F.col("l_discount")))
                .groupBy("o_orderkey", "o_orderdate")
                .agg(F.sum(F.col("revenue")).alias("revenue"))
                .sort(F.col("revenue").desc(), "o_orderkey")
                .limit(10))
    return build


def _q18(rows: int, parts: int):
    """TPC-H q18, unpruned and FAITHFUL on the group keys: the final
    aggregation groups on c_name + c_custkey like the spec query — the
    c_name string payload rides the collective as dictionary codes (the
    r06 round had to substitute c_custkey to stay fixed-width). Column
    pruning is the optimizer's job now, including the lineitem relation
    referenced on BOTH join branches."""
    def build(s):
        import spark_rapids_tpu.functions as F
        t = _tpch_tables(s, rows, parts)
        li = t["lineitem"]
        orders = t["orders"]
        cust = t["customer"]
        big = (li.groupBy("l_orderkey")
               .agg(F.sum(F.col("l_quantity")).alias("total_qty"))
               .filter(F.col("total_qty") > 150))
        return (orders
                .join(big, on=orders["o_orderkey"] == big["l_orderkey"],
                      how="leftsemi")
                .join(cust, on=orders["o_custkey"] == cust["c_custkey"])
                .join(li, on=orders["o_orderkey"] == li["l_orderkey"])
                .groupBy("c_name", "c_custkey", "o_orderkey",
                         "o_orderdate", "o_totalprice")
                .agg(F.sum(F.col("l_quantity")).alias("sum_qty"))
                .sort(F.col("o_totalprice").desc(), "o_orderdate")
                .limit(100))
    return build


def _tpcds_q3(rows: int, parts: int):
    """TPC-DS q3 sample, unpruned: the optimizer narrows the exchange
    payloads to fixed width (the group keys use the brand ID, not the
    brand string; the name resolves from item downstream in a real
    report)."""
    def build(s):
        import benchmarks.tpcds as tpcds
        import spark_rapids_tpu.functions as F
        t = tpcds.load_tables(s, rows, parts=parts)
        ss = t["store_sales"]
        item = t["item"].filter(F.col("i_manufact_id").between(100, 250))
        nov = t["date_dim"].filter(F.col("d_moy") == 11)
        return (ss.join(nov, on=ss["ss_sold_date_sk"] == nov["d_date_sk"])
                .join(item, on=ss["ss_item_sk"] == item["i_item_sk"])
                .groupBy("d_year", "i_brand_id")
                .agg(F.sum(F.col("ss_ext_sales_price")).alias("sum_agg"))
                .sort("d_year", F.col("sum_agg").desc(), "i_brand_id")
                .limit(100))
    return build


def run(n_devices: int, rows: int) -> dict:
    """All four stages; a stage failure records itself and the remaining
    stages still run (same discipline as bench.py)."""
    from spark_rapids_tpu.parallel.sharded import run_mesh_query, summarize

    # identical batch segmentation in BOTH runs (one batch per reduce
    # partition): float partial-aggregation is only bit-reproducible under
    # identical segmentation — the collective emits ONE block per reduce
    # partition while the per-map path coalesces several, and a different
    # batch split changes the float accumulation order (same property as
    # the reference's GPU-vs-CPU aggregation). Pinning the batch size to
    # the input isolates what the bit-identity check is FOR: the data
    # plane moves every row to the right shard, unchanged.
    extra = {"spark.rapids.sql.batchSizeRows": str(max(rows, 1 << 16))}
    # opt-in overlap round (ISSUE 16): MULTICHIP_OVERLAP=K arms the
    # segmented exchange/compute overlap; bit-identity still asserts
    overlap_k = int(os.environ.get("MULTICHIP_OVERLAP", "0") or 0)
    if overlap_k > 1:
        extra.update({
            "spark.rapids.tpu.exchange.overlap.enabled": "true",
            "spark.rapids.tpu.exchange.overlap.segments": str(overlap_k),
        })
    # fact tables load with parts == mesh size so BOTH plans (mesh and
    # baseline) are structurally identical: the planner sizes exchanges by
    # min(shuffle.partitions, child partitions), so fewer input parts would
    # give the baseline narrower exchanges than the aligned mesh plan —
    # structurally different plans aggregate floats in different orders
    stages = [
        ("tpch_q1", _q1(rows, n_devices), rows),
        ("tpch_q3", _q3(rows, n_devices), rows),
        ("tpch_q18", _q18(rows, n_devices), rows),
        ("tpcds_q3", _tpcds_q3(rows, n_devices), rows),
    ]
    records, input_rows, errors, elapsed = [], {}, {}, {}
    for name, build, n_rows in stages:
        t0 = time.perf_counter()
        try:
            rec = run_mesh_query(name, build, n_devices=n_devices,
                                 extra_conf=extra)
            # ISSUE 17 gate: the hand-written pruning selects are gone —
            # the optimizer-pruned plans must STILL run bit-identically
            # over the collective plane with zero per-map fallbacks
            assert rec["bit_identical"], \
                f"{name}: optimizer-pruned plan not bit-identical"
            assert rec["collective_launches_O_exchanges"], \
                f"{name}: collective launches not O(exchanges)"
            assert not rec["per_map_reasons"], \
                (f"{name}: per-map exchange fallbacks after optimizer "
                 f"pruning: {rec['per_map_reasons']}")
            records.append(rec)
            input_rows[name] = n_rows
        except Exception as e:  # noqa: BLE001 — keep later stages alive
            errors[name] = f"{type(e).__name__}: {e}"[:300]
        elapsed[name] = round(time.perf_counter() - t0, 1)
    summary = summarize(records, n_devices, input_rows)
    summary["rows"] = rows
    summary["stage_elapsed_s"] = elapsed
    if errors:
        summary["errors"] = errors
    import jax
    summary["platform"] = jax.default_backend()
    summary["records"] = records
    return summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0,
                    help="mesh size (default: all visible devices)")
    ap.add_argument("--rows", type=int,
                    default=int(os.environ.get("MULTICHIP_ROWS",
                                               str(1 << 16))))
    args = ap.parse_args()
    import jax

    from spark_rapids_tpu.utils.hw import configure_compile_cache
    configure_compile_cache()
    n = args.devices or len(jax.devices())
    summary = run(n, args.rows)
    records = summary.pop("records", [])
    # full detail first (humans), then the ONE compact machine-read line
    print(json.dumps({"detail": records}, indent=None), flush=True)
    print(json.dumps(summary, separators=(",", ":")), flush=True)
    if summary.get("errors"):
        sys.exit(f"multichip: stages failed: {sorted(summary['errors'])}")


if __name__ == "__main__":
    main()
