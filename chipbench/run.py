"""Run one cell of BENCHMARK.json once, in one process that holds the chip.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Generates the cell's data from --seed, brings the configuration up, warms the
cell's own shapes (all of that is set-up), measures for --seconds, checks every
answer of the window against the plain reference, and prints one JSON line:
`correct`, `attempted`, `failed`, `metrics`, `device`, with --trace 1 also
`breakdown`, and last `checks` (each number compared, beside its limit).
Anything else worth reading goes on earlier lines.

Exits 2 without a result line unless jax.devices()[0].platform is "tpu" and
the cell's chips are there. --cpu-rehearsal (with --rows) debugs the control
flow in a sandbox: it never prints the result line and exits 3.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()      # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

from . import check, loadgen, manifest, roofline  # noqa: E402
from . import trace as trace_mod  # noqa: E402


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


class Ctx:
    """What a metric reader may look at. Times are seconds on the harness's
    clock, counted from the window's start unless named otherwise."""

    def __init__(self):
        self.cell = None
        self.records = []            # loadgen.Record of every query of the window
        self.seconds = 0.0           # --seconds
        self.setup_s = 0.0
        self.rows_of = {}            # (tenant, template) -> input rows of the query
        self.bytes_of = {}           # (tenant, template) -> bytes the question needs
        self.before = {}             # engine.counters() at window start
        self.after = {}              # ... once the last query has answered
        self.compile_setup = {}      # CompileCounter.snapshot() at window start
        self.compile_window = {}     # ... delta over the window
        self.trace = None            # trace.reduce(...) with --trace 1
        self.trace_span = None       # (t0, t1) of the traced part, harness clock
        self.device_kind = ""

    def completed(self):
        return [r for r in self.records if not r.failed]

    def traced_queries(self):
        """Queries that ran wholly inside the traced part of the window."""
        if self.trace_span is None:
            return []
        t0, t1 = self.trace_span
        return [r for r in self.completed() if r.sent >= t0 and r.done <= t1]


class Tracer:
    """The benchmark's own profiler session over a part of the window, with
    the Python tracer off, bracketed by the WINDOW_SPAN annotation."""

    def __init__(self, out_dir: str, engine):
        self.dir = out_dir
        self.engine = engine
        self.span = None             # (start, stop) as time.perf_counter() readings
        self._ann = None
        self.t0 = None

    def start(self) -> None:
        import jax.profiler as jp
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jp.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jp.start_trace(self.dir, profiler_options=opts)
        self.engine.trace_annotations(True)
        self._ann = jp.TraceAnnotation(trace_mod.WINDOW_SPAN)
        self._ann.__enter__()
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax.profiler as jp
        t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self.engine.trace_annotations(False)
        jp.stop_trace()
        self.span = (self.t0, t1)


def device_block(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(max(peaks))}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearsal_rows: int = 0, root: str = manifest.ROOT) -> dict:
    """Everything after the look for a chip. Returns the result object."""
    cell = manifest.Cell(workload, root)
    faults = manifest.validate(root)
    if faults:
        raise manifest.ManifestError("; ".join(faults))
    from . import engine
    cache_dir = engine.configure_jax(root)
    import jax
    compiles = engine.CompileCounter()
    dev = jax.devices()[0]
    say(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; jax "
        f"{jax.__version__}; compile cache {cache_dir}")
    say(f"cell {cell.name}: config {cell.config['name']}, traffic "
        f"{cell.entry['traffic']} ({cell.traffic['loop']} loop), seed {seed}")
    if dev.platform == "tpu":
        roofline.peaks(dev.device_kind)          # an unknown kind is an error, now

    ctx = Ctx()
    ctx.cell, ctx.seconds, ctx.device_kind = cell, float(seconds), dev.device_kind
    data_dir = os.path.join(root, ".chipbench_data")
    t = time.perf_counter()
    dep = engine.Deployment(cell, seed, rehearsal_rows, data_dir, say)
    say(f"data and tables: {({n: t.rows for n, t in dep.schema.items()})} rows in "
        f"{time.perf_counter() - t:.1f}s")
    try:
        return _measure(cell, dep, ctx, compiles, engine, seed, trace, root)
    finally:
        dep.stop()


def _measure(cell, dep, ctx, compiles, engine, seed, trace, root) -> dict:
    bad, shown = [], set()
    for tpl, text in zip(cell.traffic["templates"], dep.plans()):
        bad += engine.host_operators(text)
        if text not in shown:            # parameter sets of one template print once
            shown.add(text)
            for ln in text.splitlines():
                say(f"{tpl['query']} plan: {ln.rstrip()}")
    if bad:
        raise SystemExit(f"chipbench: host operators in the plan, the cell "
                         f"measures the device path or nothing: {bad}")
    for ten in dep.tenants:
        for q in range(len(dep.templates)):
            ctx.rows_of[(ten.index, q)] = dep.query_rows(ten.index, q)
            ctx.bytes_of[(ten.index, q)] = dep.query_bytes(ten.index, q)

    # warm-up: every (tenant, template) the window can send, twice — the first
    # compiles or loads the programs, the second must be a plan-cache hit
    t = time.perf_counter()
    for _pass in (1, 2):
        for ten in dep.tenants:
            for q in range(len(dep.templates)):
                rec = loadgen.Record(tenant=ten.index, template=q, due=0.0)
                dep.send(rec)
                if rec.failed:
                    raise SystemExit(f"chipbench: warm-up query failed: {rec.result}")
    say(f"warm-up: {2 * len(dep.tenants) * len(dep.templates)} queries in "
        f"{time.perf_counter() - t:.1f}s; {compiles.snapshot()}")

    ctx.compile_setup = compiles.snapshot()
    ctx.before = engine.counters()
    tracer = Tracer(os.path.join(root, ".chipbench_trace"), engine) if trace else None
    traffic = cell.traffic
    trace_seconds = float(traffic.get("trace_seconds", 10.0))
    ctx.setup_s = time.perf_counter() - T_PROCESS
    say(f"set-up took {ctx.setup_s:.1f}s; window of {ctx.seconds:g}s starts")
    if tracer is not None and traffic["loop"] == "closed":
        tracer.start()           # before the first query, so the traced part holds whole queries
    t_window = time.perf_counter()       # every time of the window counts from here

    def now() -> float:
        return time.perf_counter() - t_window

    def send(rec) -> None:
        if tracer is None:
            dep.send(rec)
            return
        import jax.profiler as jp
        name = f"{trace_mod.QUERY_SPAN} {traffic['templates'][rec.template]['query']}"
        with jp.TraceAnnotation(name):
            dep.send(rec, detail=True)

    if traffic["loop"] == "closed":
        def on_end(_rec) -> None:
            if tracer is not None and tracer.span is None and now() >= trace_seconds:
                tracer.stop()
        ctx.records = loadgen.run_closed(traffic, ctx.seconds, seed, send, t_window, on_end)
        if tracer is not None and tracer.span is None:
            tracer.stop()
    elif traffic["loop"] == "open":
        stopper = None
        if tracer is not None:
            def traced_part() -> None:
                time.sleep(min(1.0, ctx.seconds / 10))
                tracer.start()
                time.sleep(min(trace_seconds, ctx.seconds * 0.8))
                tracer.stop()
            stopper = threading.Thread(target=traced_part, name="tracer")
            stopper.start()
        ctx.records = loadgen.run_open(traffic, ctx.seconds, seed, send, t_window)
        if stopper is not None:
            stopper.join()
    else:
        raise manifest.ManifestError(f"unknown loop {traffic['loop']!r}")
    wall = now()
    ctx.after = engine.counters()
    after = compiles.snapshot()
    ctx.compile_window = {k: after[k] - ctx.compile_setup[k] for k in after}
    device = device_block(cell.chips)
    decode = ctx.after["decode"]
    fell = {k: v for k, v in decode.items() if k.startswith("fallback") and v}
    times = sorted(r.done - r.sent for r in ctx.records)
    say(f"query seconds, sent to answered: min {times[0]:.4f} median "
        f"{times[len(times) // 2]:.4f} max {times[-1]:.4f}; first five in send order "
        f"{[round(r.done - r.sent, 4) for r in ctx.records[:5]]}")
    if traffic["loop"] == "open":
        say(f"open loop: {json.dumps(loadgen.open_summary(ctx.records, wall))}")
    say(f"window: {len(ctx.records)} queries in {wall:.2f}s; compiles inside "
        f"{ctx.compile_window}; decode {decode}; peak_bytes_in_use "
        f"{device['memory_peak_bytes']}")
    if fell:
        raise SystemExit(f"chipbench: the scan fell back to the host decoder: {fell}")

    # the program's state goes before the reference runs
    checker = check.Checker(cell, {t.index: t.columns for t in dep.tenants})
    dep.stop()
    t = time.perf_counter()
    checks = checker.check(ctx.records, {
        "compiles_in_window": ctx.compile_window["compiles"]})
    say(f"reference and comparison took {time.perf_counter() - t:.1f}s")

    if tracer is not None:
        t = time.perf_counter()
        ctx.trace_span = tuple(t - t_window for t in tracer.span)
        lines = trace_mod.load(trace_mod.find_xplane(tracer.dir))
        ctx.trace = trace_mod.reduce(lines, rehearsal=device["platform"] != "tpu")
        say(f"trace read in {time.perf_counter() - t:.1f}s: {ctx.trace['devices']} "
            f"device plane(s), busy from {ctx.trace['ops_line']}")
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        shutil.rmtree(tracer.dir, ignore_errors=True)

    metrics = {}
    for m in cell.metrics("per_layer" if trace else "end_to_end"):
        read, args = cell.reader(m["name"])
        try:
            value = read(ctx, **args)
        except KeyError as e:        # no peaks for a rehearsal's CPU "device"
            if device["platform"] == "tpu":
                raise
            say(f"rehearsal: {m['name']} not read: {e}")
            continue
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": check.verdict(checks), "attempted": len(ctx.records),
              "failed": sum(r.failed for r in ctx.records), "metrics": metrics,
              "device": device}
    if ctx.trace is not None:
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="debug the control flow on a backend that is not a "
                         "TPU; prints no result line and exits 3")
    ap.add_argument("--rows", type=int, default=0,
                    help="rehearsal only: rows in place of the configuration's")
    args = ap.parse_args(argv)
    if args.rows and not args.cpu_rehearsal:
        ap.error("--rows is for --cpu-rehearsal only")
    try:
        cell = manifest.Cell(args.workload)
        import spark_rapids_tpu  # noqa: F401 — the system under test must be there
        import jax
    except (ImportError, OSError, manifest.ManifestError, KeyError) as e:
        print(f"chipbench: cannot start: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    devs = jax.devices()
    if not args.cpu_rehearsal and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        print(f"chipbench: the cell needs {cell.chips} TPU chip(s); jax.devices() "
              f"is {len(devs)} x {devs[0].platform!r}", file=sys.stderr)
        return 2
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      rehearsal_rows=args.rows)
    for name, c in result["checks"].items():
        print(f"check {name}: {c}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    if devs[0].platform != "tpu":
        say(f"rehearsal only: this was NOT a chip run, no result. It would read: "
            f"{json.dumps(result)}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
