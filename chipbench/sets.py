"""Measure a cell as its bounds are set: two sets of N runs with the same
seeds in both, each run a process of its own, one after the other (this parent
never touches JAX, so each child has the chip to itself).

    python -m chipbench.sets --workload <cell> [--runs 6] [--traced 0]

Prints every result line, then for each end-to-end metric the spread of each
set (interquartile distance over the median, `statistics.quantiles(n=4)`), the
wider of the two, and five times it. With --traced K, K more runs with
--trace 1 on further seeds follow. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

from . import manifest
from .stats import spread


def one_run(workload: str, seed: int, seconds: int, trace: int) -> dict:
    cmd = manifest.benchmark()["command"] + [
        "--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
        "--trace", str(trace)]
    t = time.perf_counter()
    p = subprocess.run(cmd, cwd=manifest.ROOT, capture_output=True, text=True)
    took = time.perf_counter() - t
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    try:
        res = json.loads(last)
    except ValueError:
        res = {"correct": False, "error": (p.stderr or p.stdout)[-1500:]}
    res["_rc"], res["_took_s"], res["_seed"] = p.returncode, took, seed
    print(f"RUN {workload} seed={seed} trace={trace} rc={p.returncode} "
          f"took={took:.1f}s {json.dumps(res)[:3000]}", flush=True)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=0)
    ap.add_argument("--first-seed", type=int, default=2_900_000_011)
    args = ap.parse_args(argv)
    seconds = manifest.benchmark()["run_seconds"]
    seeds = [args.first_seed + 104_729 * i for i in range(args.runs)]
    sets = [[one_run(args.workload, s, seconds, 0) for s in seeds] for _ in (1, 2)]
    traced = [one_run(args.workload, args.first_seed + 15_485_863 * (i + 1), seconds, 1)
              for i in range(args.traced)]
    summary = {"workload": args.workload, "seeds": seeds, "metrics": {},
               "all_correct": all(r.get("correct") for s in sets for r in s)
               and all(r.get("correct") for r in traced)}
    names = sorted({k for s in sets for r in s for k in r.get("metrics", {})})
    for name in names:
        vals = [[r["metrics"][name]["value"] for r in s if name in r.get("metrics", {})]
                for s in sets]
        sp = [spread(v) if len(v) >= 2 else None for v in vals]
        # the first run of the first set is the one that may compile
        steady = [vals[0][1:], vals[1]] if name == "setup_s" else vals
        summary["metrics"][name] = {
            "set1": vals[0], "set2": vals[1], "spreads": sp,
            "medians": [statistics.median(v) if v else None for v in steady],
            "widest_spread": max(x for x in sp if x is not None),
            "five_times": 5 * max(x for x in sp if x is not None)}
    print("SUMMARY " + json.dumps(summary), flush=True)
    out = os.path.join(manifest.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"sets.{args.workload}.json"), "w") as f:
        json.dump({"summary": summary, "sets": sets, "traced": traced}, f, indent=1)
    return 0 if summary["all_correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
