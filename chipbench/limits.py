"""Readings the limit of `limits.json` is set from, taken on the chip in one
process (set-up is long, so the seeds share it):

    python -m chipbench.limits --workload <cell> --seeds 12 --seconds 5 [--first-seed N]

For each seed it runs the cell as `chipbench.run` does, with a short window at
the cell's own load, and prints what the comparison read: the program's worst
relative gap on a DOUBLE against the reference (the LOWER reading is the
largest of these), and the control's — the reference put in the program's
place and computed one precision step down, once with the DOUBLE columns
handed over as FLOAT (float32 inputs, float64 arithmetic) and once wholly in
float32 (the UPPER reading is the smallest). Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from . import check, manifest


def control_readings(cell, seed: int, rows: int) -> dict:
    """Worst gap of each control against the float64 reference, over every
    (tenant, template) of the cell, on the columns the run of `seed` had."""
    out = {"f32_inputs": 0.0, "all_f32": 0.0}
    for cols in check.reference_columns(cell, seed, rows).values():
        for tpl in cell.traffic["templates"]:
            q, params = cell.query(tpl["query"]), tpl.get("params", {})
            want = q.reference(cols, **params)
            for name, fn in (("f32_inputs", check.lower_precision),
                             ("all_f32", check.all_float32)):
                got = [{k: (float(v) if hasattr(v, "dtype") else v) for k, v in r.items()}
                       for r in q.reference(fn(cols), **params)]
                c = check.compare_rows(got, want)
                out[name] = max(out[name], c["max_rel_err"])
                out.setdefault(name + "_inexact", 0)
                out[name + "_inexact"] += c["inexact"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_100_000_001)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--controls", type=int, default=3,
                    help="how many of the seeds also read the controls")
    args = ap.parse_args(argv)
    from . import run
    import jax
    print(f"device {jax.devices()[0].platform} {jax.devices()[0].device_kind}", flush=True)
    cell = manifest.Cell(args.workload)
    rows_out = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        res = run.run_cell(args.workload, seed, args.seconds, trace=False,
                           rehearsal_rows=args.rows)
        row = {"seed": seed, "correct": res["correct"],
               "attempted": res["attempted"], "failed": res["failed"],
               **{k: v["value"] for k, v in res["checks"].items()}}
        if i < args.controls:
            row["control"] = control_readings(cell, seed, args.rows)
        rows_out.append(row)
        print("READING " + json.dumps(row), flush=True)
    lower = max(r["double_max_rel_err"] for r in rows_out)
    ctl = [r["control"] for r in rows_out if "control" in r]
    summary = {"workload": args.workload, "seeds": len(rows_out), "lower_reading": lower,
               "upper_f32_inputs": min(c["f32_inputs"] for c in ctl) if ctl else None,
               "upper_all_f32": min(c["all_f32"] for c in ctl) if ctl else None,
               "all_correct": all(r["correct"] for r in rows_out)}
    print("SUMMARY " + json.dumps(summary), flush=True)
    os.makedirs(os.path.join(manifest.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(manifest.ROOT, "chiprun_out",
                           f"limits.{args.workload}.json"), "w") as f:
        json.dump({"summary": summary, "readings": rows_out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
