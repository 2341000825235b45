"""Device time and idle time by the `srt.*` phase that caused them, from one
profiler trace (`.xplane.pb`), on the profiler's one clock.

The trace carries the join itself, as flow ids: a producer event carries
`_pt`/`_p` (a flow's type and id), its consumer `_ct`/`_c` with the same two.

1. a device program (`XLA Modules` event, `_ct` 12) is linked to the host
   event that enqueued it (`DoEnqueueProgram`, `_pt` 12); where that fails,
   by `run_id` and the device's ordinal;
2. from there the chain runs back through the consumers that cover each
   step on its thread's line, innermost first, to their producers: the
   enqueue lies in a `tpu::System::Execute=>IssueSequencedEvent` (`_ct` 7) —
   on the launching thread, or on a runtime pool thread that issues a
   deferred launch — whose producer `tpu::System::Execute` (`_pt` 7) lies in
   the launching thread's `PJRT_LoadedExecutable_Execute` (`_ct` 14), whose
   producer is the `PJRT_LoadedExecutable_Execute linkage` event (`_pt` 14)
   on the same thread's Python line: the line the program's `srt.*`
   annotations are on (jaxlib and the runtime record a thread on two lines);
3. on that line, the `srt.*` annotations that cover the linkage instant name
   the phase: the innermost is the phase, the enclosing ones its path;
4. an idle gap of a device plane (between the unions of its `XLA Ops`, as
   `trace.reduce` has them) goes to the phase in which the thread that launched
   the program ending the gap stood at the gap's midpoint, put on the host's
   clock (the device's runs up to ~1.3 ms behind it: `clock_lag_ms`).

Only what lies inside a traced query counts per query: a `chipbench.query`
span wholly inside the window, and any thread's annotations inside its
interval (a mesh session's chip workers). The rest goes to named buckets, so
that device seconds over all rows equal the window's program seconds and idle
seconds the window's idle time. Seconds and launches are the mean over device
planes, as `trace.reduce` gives them. `host_tracer_level` must stay at 2: the
runtime's launch events are what step 1 and 2 read.
"""

from __future__ import annotations

import re
from bisect import bisect_right
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import trace as tm

PREFIX = "srt."
#: buckets beside the phases
UNLINKED = "(unlinked)"          # no chain from the program to a Python line
NO_PHASE = "(no phase)"          # linked, no srt.* annotation there
OUTSIDE = "(outside queries)"    # launched, or the gap lay, outside the traced queries
NO_NEXT = "(no next program)"    # a gap that no program of its plane ends
#: host events whose flow stats are kept (the chain of step 2); every other
#: event only by name and time
FLOW_EVENTS = ("DoEnqueueProgram", "PJRT_LoadedExecutable_Execute", "tpu::System::Execute",
               "tpu::System::Execute=>IssueSequencedEvent")
LINKAGE = " linkage"
HOPS = 6            # steps of the chain from an enqueue to its linkage
LOOKBACK = 16       # consumer events started since the one that encloses t
STATS = ("_ct", "_c", "_pt", "_p", "run_id", "device_ordinal")


def load(path: str) -> Tuple[List[tm.Line], Dict[Tuple[int, int], dict]]:
    """`trace.load`'s lines, and the flow stats of the events that carry the
    join: {(line index, event index): {stat: value}} for `XLA Modules` events,
    the runtime's launch events and the Python lines' `... linkage` events."""
    from jax.profiler import ProfileData
    lines, stats = [], {}
    for plane in ProfileData.from_file(path).planes:
        device = bool(tm.DEVICE_PLANE.match(plane.name))
        for ln in plane.lines:
            modules = device and ln.name in tm.MODULE_LINES
            li = len(lines)
            names, start, end = [], [], []
            for e in ln.events:
                n = e.name
                if modules or (not device and (n in FLOW_EVENTS or n.endswith(LINKAGE))):
                    st = {k: v for k, v in e.stats if k in STATS}
                    if st:
                        stats[(li, len(names))] = st
                names.append(n)
                start.append(e.start_ns)
                end.append(e.start_ns + e.duration_ns)
            lines.append(tm.Line(plane.name, ln.name, names,
                                 np.asarray(start, np.float64),
                                 np.asarray(end, np.float64)))
    return lines, stats


def _stacks_at(starts, ends, names, times) -> List[Tuple[str, ...]]:
    """For each time (any order) the names of the events of one thread's
    line that cover it, outermost first. Events of a thread nest."""
    order = np.lexsort((-ends, starts))        # an enclosing event first
    out: List[Tuple[str, ...]] = [()] * len(times)
    stack: List[int] = []
    j = 0
    for k in np.argsort(times, kind="stable"):
        t = times[k]
        while j < len(order) and starts[order[j]] <= t:
            while stack and ends[stack[-1]] <= starts[order[j]]:
                stack.pop()
            stack.append(order[j])
            j += 1
        while stack and ends[stack[-1]] <= t:
            stack.pop()
        out[k] = tuple(names[i] for i in stack if ends[i] > t)
    return out


class _Host:
    """The host lines' flow events, indexed for the walk of step 2."""

    def __init__(self, lines: List[tm.Line], stats: Dict[Tuple[int, int], dict]):
        self.lines = lines
        self.producer: Dict[Tuple[int, int], Tuple[int, int]] = {}
        self.enqueue_by_run: Dict[Tuple[int, int], Tuple[int, int]] = {}
        consumers: Dict[int, list] = defaultdict(list)   # line -> [(start, end, flow)]
        for (li, ei), st in stats.items():
            ln = lines[li]
            if tm.DEVICE_PLANE.match(ln.plane):
                continue
            if "_pt" in st and "_p" in st:
                self.producer[(int(st["_pt"]), int(st["_p"]))] = (li, ei)
            if "_ct" in st and "_c" in st:
                consumers[li].append((ln.start[ei], ln.end[ei], (int(st["_ct"]), int(st["_c"]))))
            if ln.names[ei] == "DoEnqueueProgram" and "run_id" in st:
                self.enqueue_by_run[(int(st["run_id"]), int(st.get("device_ordinal", -1)))] = (li, ei)
        self.consumers = {li: sorted(v) for li, v in consumers.items()}
        self.starts = {li: [c[0] for c in v] for li, v in self.consumers.items()}

    def covering(self, li: int, t: float):
        """Flows of the consumer events of line li that cover t, innermost
        first (they nest, a few deep; an SPMD launch issues one event a
        device inside its execute)."""
        v = self.consumers.get(li, ())
        k = bisect_right(self.starts.get(li, ()), t) - 1
        for s, e, flow in v[max(k - LOOKBACK, 0):k + 1][::-1]:
            if e >= t:
                yield flow

    def python_of(self, enq: Tuple[int, int]) -> Optional[Tuple[int, float]]:
        """(Python line, linkage instant) of the launch that made an enqueue."""
        li, ei = enq
        t = self.lines[li].start[ei]
        for _ in range(HOPS):
            hit = next((self.producer[f] for f in self.covering(li, t) if f in self.producer), None)
            if hit is None:
                return None
            li, ei = hit
            t = float(self.lines[li].start[ei])
            if self.lines[li].names[ei].endswith(LINKAGE):
                return li, t
        return None


def _query_spans(lines: List[tm.Line], lo: float, hi: float) -> np.ndarray:
    """[[start, end]] of the `chipbench.query` spans wholly inside the window."""
    out = []
    for ln in lines:
        if tm.DEVICE_PLANE.match(ln.plane):
            continue
        for i, n in enumerate(ln.names):
            if n.startswith(tm.QUERY_SPAN) and ln.start[i] >= lo and ln.end[i] <= hi:
                out.append((ln.start[i], ln.end[i]))
    return np.asarray(sorted(out), np.float64).reshape(-1, 2)


def _inside(spans: np.ndarray, t: float) -> bool:
    k = int(np.searchsorted(spans[:, 0], t, side="right")) - 1
    return k >= 0 and spans[k, 1] >= t


def _phase_key(stack: Tuple[str, ...]) -> str:
    """'query/result.drain/segment.launch' from the covering srt.* names."""
    path = [n[len(PREFIX):] for n in stack if n.startswith(PREFIX)]
    return "/".join(path) if path else NO_PHASE


def table(lines: List[tm.Line], stats: Dict[Tuple[int, int], dict], top: int = 8) -> dict:
    """The per-phase table of one trace: for each phase path (outermost first,
    '/'-joined) the device seconds and launches of the programs it launched,
    the idle seconds it left, and its top programs; for each phase name the
    count of its `srt.<phase>` events inside the traced queries. Seconds and
    launches are the mean over device planes."""
    lo, hi = tm.window(lines)
    spans = _query_spans(lines, lo, hi)
    host = _Host(lines, stats)
    mods = tm._device_lines(lines, tm.MODULE_LINES)
    ops = tm._device_lines(lines, tm.OPS_LINES)
    n_dev = max(len(ops), 1)
    line_index = {id(ln): i for i, ln in enumerate(lines)}
    rows: Dict[str, Dict[str, float]] = defaultdict(lambda: {"device_s": 0.0, "launches": 0.0,
                                                            "idle_s": 0.0})
    programs: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    # per Python line: the instants at which we need its srt.* stack
    wanted: Dict[int, List[float]] = defaultdict(list)
    launches = []           # (plane, module index, (python line, linkage t) or None)
    skew: Dict[str, float] = {}
    for plane, ln in sorted(mods.items()):
        li = line_index[id(ln)]
        m = tm.DEVICE_PLANE.match(plane)
        ordinal = int(m.group(2)) if m else -1
        skew[plane] = 0.0
        for ei in range(len(ln.names)):
            st = stats.get((li, ei), {})
            enq = host.producer.get((int(st["_ct"]), int(st["_c"]))) \
                if "_ct" in st and "_c" in st else None
            if enq is None and "run_id" in st:
                enq = host.enqueue_by_run.get((int(st["run_id"]), ordinal))
            if enq is not None:
                lag = lines[enq[0]].start[enq[1]] - ln.start[ei]
                skew[plane] = max(skew[plane], float(lag))
            py = host.python_of(enq) if enq is not None else None
            launches.append((plane, ei, py))
            if py is not None:
                wanted[py[0]].append(py[1])

    # idle gaps per device plane, as trace.reduce cuts them; a gap's midpoint
    # is looked up on the host's clock: a device clock that runs behind the
    # host's (by up to ~1.3 ms on a v5e) would start a program before its
    # enqueue, so it is moved by the least lag that keeps every linked
    # program at or after its enqueue
    gaps = []               # (host-clock mid, length, python line or None)
    for plane, oln in sorted(ops.items()):
        s, e = tm.union(*tm.clip(oln.start, oln.end, lo, hi))
        edges_s = np.concatenate(([lo], e))
        edges_e = np.concatenate((s, [hi]))
        mln = mods.get(plane)
        mod_end = mln.end if mln is not None else np.zeros(0)
        order = np.argsort(mod_end, kind="stable")
        ends_sorted = mod_end[order]
        by_index = {rec[1]: rec[2] for rec in launches if rec[0] == plane}
        lag = skew.get(plane, 0.0)
        for a, b in zip(edges_s, edges_e):
            if b <= a:
                continue
            mid = (a + b) / 2 + lag
            k = int(np.searchsorted(ends_sorted, b, side="right"))
            if k >= len(order):
                gaps.append((mid, b - a, NO_NEXT))
                continue
            py = by_index.get(int(order[k]))
            if py is None:
                gaps.append((mid, b - a, UNLINKED))
                continue
            gaps.append((mid, b - a, py[0]))
            wanted[py[0]].append(mid)

    stacks: Dict[Tuple[int, float], Tuple[str, ...]] = {}
    counts: Dict[str, int] = defaultdict(int)
    for li in set(wanted) | {i for i, ln in enumerate(lines)
                             if ln.plane.startswith("/host") and any(
                                 n.startswith(PREFIX) for n in ln.names)}:
        ln = lines[li]
        sel = [i for i, n in enumerate(ln.names) if n.startswith(PREFIX)]
        starts, ends = ln.start[sel], ln.end[sel]
        names = [ln.names[i] for i in sel]
        for i in range(len(sel)):
            if len(spans) and _inside(spans, starts[i]):
                counts[names[i][len(PREFIX):]] += 1
        times = np.asarray(sorted(set(wanted.get(li, ()))), np.float64)
        for t, stk in zip(times, _stacks_at(starts, ends, names, times)):
            stacks[(li, float(t))] = stk

    def key_of(py, t) -> str:
        if not len(spans) or not _inside(spans, t):
            return OUTSIDE
        return _phase_key(stacks[(py, float(t))])

    total_device = 0.0
    for plane, ei, py in launches:
        ln = mods[plane]
        d = min(ln.end[ei], hi) - max(ln.start[ei], lo)
        if d <= 0:
            continue
        total_device += d
        k = UNLINKED if py is None else key_of(py[0], py[1])
        rows[k]["device_s"] += d / 1e9 / n_dev
        rows[k]["launches"] += 1 / n_dev
        programs[k][re.sub(r"\(\d+\)$", "", ln.names[ei])] += d / 1e9 / n_dev
    idle_total = 0.0
    for mid, length, who in gaps:
        idle_total += length
        k = who if isinstance(who, str) else key_of(who, mid)
        rows[k]["idle_s"] += length / 1e9 / n_dev
    unlinked = rows[UNLINKED]["device_s"] if UNLINKED in rows else 0.0
    return {"window_s": (hi - lo) / 1e9, "devices": len(ops), "queries": len(spans),
            "clock_lag_ms": {p: v / 1e6 for p, v in sorted(skew.items())},
            "device_s": total_device / 1e9 / n_dev, "idle_s": idle_total / 1e9 / n_dev,
            "linked_share": 1.0 - unlinked / (total_device / 1e9 / n_dev) if total_device else None,
            "phases": {k: dict(v) for k, v in sorted(rows.items())},
            "programs": {k: [[p, s] for p, s in sorted(v.items(), key=lambda kv: -kv[1])[:top]]
                         for k, v in sorted(programs.items())},
            "counts": dict(sorted(counts.items()))}


def build(path: str) -> dict:
    """The table of the trace at `path` (a `.xplane.pb`)."""
    return table(*load(path))


def select(tab: dict, phase: str, nested: bool = False) -> dict:
    """Sums over the rows whose innermost phase is `phase`, or with `nested`
    every row at or under it."""
    out = {"device_s": 0.0, "launches": 0.0, "idle_s": 0.0}
    for key, row in tab["phases"].items():
        path = key.split("/")
        if path[-1] == phase or (nested and phase in path):
            for k in out:
                out[k] += row[k]
    return out
