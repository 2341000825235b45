"""Records the device trace of `tests/test_trace_phases.py` (chipbench/testdata/phases.xplane.pb)
on the chip: two queries inside the harness's window span, each with `srt.*` annotations two deep,
launches from two threads, and one idle gap under a known phase. Every phase launches a program of
its own name, so a test knows from a program's name which phase launched it:

    srt.query               p_root once; its own time
      srt.stage.launch      p_stage twice
      srt.scan.page_walk    a 40 ms sleep, then p_scan: the gap before p_scan lies under it
    worker thread (no srt.query on its line), inside the query's span:
      srt.segment.launch    p_segment three times

    python -m chipbench.record_phase_trace     # on the chip; writes chiprun_out/phases.xplane.pb
"""
import os
import shutil
import threading
import time

import jax
import jax.numpy as jnp
import jax.profiler as jp

from chipbench import phase_table as pt
from chipbench import trace as tm

SLEEP_S = 0.040


def p_root(x):
    return jnp.cumsum(x * 2.0).sum()


def p_stage(x):
    return (x * 3.0 + 1.0).max()


def p_scan(y):
    return (y @ y).sum()


def p_segment(x):
    return jnp.sort(x)[::7].sum()


def main() -> None:
    out = "chiprun_out/phase_trace"
    shutil.rmtree(out, ignore_errors=True)
    fns = {f.__name__: jax.jit(f) for f in (p_root, p_stage, p_scan, p_segment)}
    x = jnp.ones((1 << 16,), jnp.float32)
    y = jnp.ones((256, 256), jnp.float32)
    arg = {"p_root": x, "p_stage": x, "p_scan": y, "p_segment": x}
    for name, f in fns.items():
        f(arg[name]).block_until_ready()

    def run(name):
        fns[name](arg[name]).block_until_ready()

    def worker():
        with jp.TraceAnnotation("srt.segment.launch"):
            for _ in range(3):
                run("p_segment")

    o = jp.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 2
    jp.start_trace(out, profiler_options=o)
    with jp.TraceAnnotation(tm.WINDOW_SPAN):
        # the device clock runs about a millisecond behind the host's: a
        # launch at the window's first instant would lie before it
        time.sleep(0.005)
        for q in range(2):
            with jp.TraceAnnotation(tm.QUERY_SPAN + " t"), jp.TraceAnnotation("srt.query"):
                run("p_root")
                with jp.TraceAnnotation("srt.stage.launch"):
                    run("p_stage")
                    run("p_stage")
                t = threading.Thread(target=worker, name="worker")
                t.start()
                t.join()
                with jp.TraceAnnotation("srt.scan.page_walk"):
                    time.sleep(SLEEP_S)
                    run("p_scan")
            time.sleep(0.005)
    jp.stop_trace()
    p = tm.find_xplane(out)
    print("xplane", p, os.path.getsize(p))
    lines, stats = pt.load(p)
    if jax.devices()[0].platform == "tpu":
        print(tm.reduce(lines))
    print(pt.table(lines, stats))
    shutil.copy(p, "chiprun_out/phases.xplane.pb")


if __name__ == "__main__":
    main()
