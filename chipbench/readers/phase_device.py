"""Device ms, launches or idle ms of one phase of the served path, from the
traced window's per-phase table (`chipbench/phase_table.py`: each device
program joined to the `srt.*` phase that launched it by the trace's own launch
ids, each idle gap to the phase its launching thread stood in). `what` is
`device_ms`, `launches` or `idle_ms`; `per` is `query` (the traced queries) or
`count` (the phase's own `srt.<phase>` events in them); `nested` adds what
nests under the phase. Numerator and denominator come from the same trace.

The table is `ctx.phase_table`; a harness that does not build it (PERF.md
section 7), or a program without the phase, reads as nothing.
"""

from chipbench.phase_table import select

WHAT = {"device_ms": ("device_s", 1e3), "launches": ("launches", 1.0),
        "idle_ms": ("idle_s", 1e3)}


def read(ctx, phase, what, per, nested=False):
    if what not in WHAT:
        raise ValueError(f"what is {what!r}, not one of {sorted(WHAT)}")
    if per not in ("query", "count"):
        raise ValueError(f"per is {per!r}, not 'query' or 'count'")
    tab = getattr(ctx, "phase_table", None)
    if not tab or not tab["queries"] or phase not in tab["counts"]:
        return None
    key, scale = WHAT[what]
    n = tab["queries"] if per == "query" else tab["counts"][phase]
    return select(tab, phase, nested)[key] * scale / n
