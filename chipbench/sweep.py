"""Find an open-loop cell's knee once, on the chip: bring the deployment up,
then offer each of a few fixed rates for a short window and print what came
back. The knee is the highest rate at which nothing is shed and lateness does
not grow through the window (second half no slower than the first); the cell's
traffic file then fixes 0.8 of it. Not part of a benchmark run.

    python -m chipbench.sweep --workload <cell> --rates 4,8,12,16 --seconds 15
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

from . import loadgen, manifest
from .stats import percentile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=2_200_000_033)
    ap.add_argument("--rows", type=int, default=0)
    args = ap.parse_args(argv)
    cell = manifest.Cell(args.workload)
    from . import engine
    engine.configure_jax(manifest.ROOT)
    import jax
    print(f"device {jax.devices()[0].platform} {jax.devices()[0].device_kind}", flush=True)
    dep = engine.Deployment(cell, args.seed, args.rows,
                            os.path.join(manifest.ROOT, ".chipbench_data"), print)
    try:
        for _pass in (1, 2):
            for ten in dep.tenants:
                for q in range(len(dep.templates)):
                    dep.send(loadgen.Record(tenant=ten.index, template=q, due=0.0))
        # one query of each template alone, to know the service times
        for q, tpl in enumerate(cell.traffic["templates"]):
            t = time.perf_counter()
            for _ in range(5):
                dep.send(loadgen.Record(tenant=0, template=q, due=0.0))
            print(f"alone: {tpl['query']} on tenant 0: "
                  f"{(time.perf_counter() - t) / 5 * 1e3:.1f} ms", flush=True)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            traffic = dict(cell.traffic, rate_per_s=rate)
            recs = loadgen.run_open(traffic, args.seconds, args.seed + i, dep.send,
                                    time.perf_counter())
            lat = [(r.done - r.due) * 1e3 for r in recs if not r.failed]
            half = args.seconds / 2
            first = [(r.done - r.due) * 1e3 for r in recs if not r.failed and r.due < half]
            second = [(r.done - r.due) * 1e3 for r in recs if not r.failed and r.due >= half]
            print("RATE " + json.dumps({
                "rate": rate, "sent": len(recs), "failed": sum(r.failed for r in recs),
                "drained_s": max(r.done for r in recs),
                "p50_ms": percentile(lat, 0.5), "p95_ms": percentile(lat, 0.95),
                "mean_first_half_ms": statistics.fmean(first),
                "mean_second_half_ms": statistics.fmean(second),
                "admit_p95_ms": percentile([r.extra.get("admit_wait_ms") or 0.0
                                            for r in recs if not r.failed], 0.95)}),
                  flush=True)
    finally:
        dep.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
