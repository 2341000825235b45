"""A traced run of one cell, with the per-phase table of its trace
(`chipbench/phase_table.py`) built before the harness removes the trace.

    python -m chipbench.probe_phases --workload W --seed N --seconds S [--keep-xplane]

`run.run_cell` runs as the driver's `--trace 1` run does; only `trace.load`
is wrapped, so that the table, the seconds `trace.load` + `trace.reduce` and
the table each took, and (with --keep-xplane) a gzip of the trace are kept.
Also reports rows/s of the window's traced queries against its untraced ones
(sent after the trace stopped). Writes chiprun_out/phases/<W>-<seed>.json.
Not part of a benchmark run: the harness does not build the table yet
(PERF.md section 7).
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import time
import traceback

OUT = os.path.join("chiprun_out", "phases")


def rows_per_s(ctx, recs) -> float:
    """Input rows over the queries' own seconds, sent to answered."""
    secs = sum(r.done - r.sent for r in recs)
    return sum(ctx.rows_of[(r.tenant, r.template)] for r in recs) / secs if secs else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-xplane", action="store_true")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--rows", type=int, default=0)
    args = ap.parse_args(argv)
    from . import phase_table, run
    from . import trace as tm
    os.makedirs(OUT, exist_ok=True)
    tag = f"{args.workload}-{args.seed}"
    got = {}
    load, reduce = tm.load, tm.reduce

    def wrapped_load(path):
        t = time.perf_counter()
        lines = load(path)
        got["load_s"] = time.perf_counter() - t
        if args.keep_xplane:
            with open(path, "rb") as f, gzip.open(os.path.join(OUT, tag + ".xplane.pb.gz"), "wb") as g:
                shutil.copyfileobj(f, g)
        t = time.perf_counter()
        try:
            got["table"] = phase_table.build(path)
        except Exception:       # noqa: BLE001 — the probe keeps the trace to debug it
            got["table_error"] = traceback.format_exc()
        got["table_s"] = time.perf_counter() - t
        return lines

    def wrapped_reduce(lines, **kw):
        t = time.perf_counter()
        out = reduce(lines, **kw)
        got["reduce_s"] = time.perf_counter() - t
        return out

    ctxs = []

    class Ctx(run.Ctx):
        def __init__(self):
            super().__init__()
            ctxs.append(self)

    tm.load, tm.reduce, run.Ctx = wrapped_load, wrapped_reduce, Ctx
    import jax
    if not args.cpu_rehearsal and jax.devices()[0].platform != "tpu":
        print("probe_phases: no TPU", file=sys.stderr)
        return 2
    result = run.run_cell(args.workload, args.seed, args.seconds, True,
                          rehearsal_rows=args.rows)
    ctx = ctxs[0]
    traced = ctx.traced_queries()
    after = [r for r in ctx.completed() if ctx.trace_span and r.sent >= ctx.trace_span[1]]
    out = {"workload": args.workload, "seed": args.seed, "correct": result["correct"],
           "metrics": result["metrics"], "device": result["device"],
           "breakdown": result.get("breakdown"), "traced_queries": len(traced),
           "untraced_queries_after": len(after),
           "rows_per_s_traced": rows_per_s(ctx, traced) if traced else None,
           "rows_per_s_untraced": rows_per_s(ctx, after) if after else None, **got}
    with open(os.path.join(OUT, tag + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    tab = got.get("table") or {}
    print(json.dumps({k: out[k] for k in ("workload", "seed", "correct", "traced_queries",
                                          "untraced_queries_after", "rows_per_s_traced",
                                          "rows_per_s_untraced")}
                     | {k: got.get(k) for k in ("load_s", "reduce_s", "table_s")}
                     | {k: tab.get(k) for k in ("queries", "device_s", "idle_s", "linked_share")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
