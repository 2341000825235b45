"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports:
device busy time as the union of the intervals in which an operation ran,
the idle share of the traced window, the device programs that took most time,
and the longest idle gaps named by the host span that covers each.

Read with `jax.profiler.ProfileData` alone. The traced part of a run is
bracketed by one host annotation, WINDOW_SPAN, written by the harness on the
profiler's own clock; everything is clipped to it.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

WINDOW_SPAN = "chipbench.window"
QUERY_SPAN = "chipbench.query"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
#: the device line whose events are single operations; busy time is their union
OPS_LINES = ("XLA Ops",)
#: the device line whose events are whole programs, named jit_<fn>(<id>)
MODULE_LINES = ("XLA Modules",)


@dataclass
class Line:
    plane: str
    name: str
    names: List[str]
    start: np.ndarray          # ns
    end: np.ndarray            # ns


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str) -> List[Line]:
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            names, start, end = [], [], []
            for e in ln.events:
                names.append(e.name)
                start.append(e.start_ns)
                end.append(e.start_ns + e.duration_ns)
            lines.append(Line(plane.name, ln.name, names,
                              np.asarray(start, np.float64),
                              np.asarray(end, np.float64)))
    return lines


def union(start: np.ndarray, end: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Disjoint sorted intervals covering the same points as the given ones."""
    if len(start) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start, kind="stable")
    s, e = start[order], np.maximum.accumulate(end[order])
    first = np.concatenate(([True], s[1:] > e[:-1]))
    last = np.concatenate((first[1:], [True]))
    return s[first], e[last]


def clip(start, end, lo: float, hi: float):
    s, e = np.clip(start, lo, hi), np.clip(end, lo, hi)
    keep = e > s
    return s[keep], e[keep]


def window(lines: List[Line]) -> Tuple[float, float]:
    """[start, end) in ns of the harness's WINDOW_SPAN annotation."""
    for ln in lines:
        if DEVICE_PLANE.match(ln.plane):
            continue
        for i, n in enumerate(ln.names):
            if n == WINDOW_SPAN:
                return float(ln.start[i]), float(ln.end[i])
    raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")


def _device_lines(lines: List[Line], wanted, rehearsal: bool = False) -> Dict[str, Line]:
    """plane -> its line named in `wanted`. In a sandbox rehearsal there is no
    device plane: the CPU client's executor threads stand in, so that the
    control flow after the trace can be run."""
    if rehearsal:
        return {f"{ln.plane}/{ln.name}": ln for ln in lines
                if "XLAPjRtCpuClient" in ln.name and len(ln.start)}
    return {ln.plane: ln for ln in lines
            if DEVICE_PLANE.match(ln.plane) and ln.name in wanted and len(ln.start)}


#: host threads that belong to the runtime's pools; what they run says
#: nothing about what the program's own threads were doing in a gap
RUNTIME_THREADS = re.compile(r"^(pjrt-|futex-|EventFD|tfrt-|tf_|grpc|profiler)")


def innermost_at(ln: Line, times: np.ndarray) -> List[int]:
    """For each time (ascending) the index of the innermost event of one
    thread's line that covers it, or -1. Events of a thread nest, so one sweep
    with a stack answers all the times."""
    order = np.argsort(ln.start, kind="stable")
    out, stack, j = [], [], 0
    for t in times:
        while j < len(order) and ln.start[order[j]] <= t:
            stack.append(order[j])
            j += 1
        while stack and ln.end[stack[-1]] <= t:
            stack.pop()
        out.append(stack[-1] if stack else -1)
    return out


def name_gaps(hosts: List[Line], mids: np.ndarray) -> List[str]:
    """The host span each gap is named by: over the program's threads, the
    shortest span that covers the gap's midpoint."""
    best = ["(no host span)"] * len(mids)
    best_len = np.full(len(mids), np.inf)
    for ln in hosts:
        for g, i in enumerate(innermost_at(ln, mids)):
            if i >= 0:
                d = ln.end[i] - ln.start[i]
                if 0 < d < best_len[g]:
                    best[g], best_len[g] = ln.names[i], d
    return best


def reduce(lines: List[Line], top: int = 10, rehearsal: bool = False) -> dict:
    """busy_s (mean over device planes), window_s, idle_share, the device
    programs by total time and the idle gaps by covering host span."""
    lo, hi = window(lines)
    ops = _device_lines(lines, OPS_LINES, rehearsal)
    if not ops:
        raise ValueError("the trace has no device plane with events: no "
                         "operation ran on a device inside the traced window")
    busy_per_plane, gaps_by_name = [], {}
    hosts = [ln for ln in lines if ln.plane.startswith("/host") and len(ln.start)
             and not RUNTIME_THREADS.match(ln.name)]
    for plane, ln in sorted(ops.items()):
        s, e = union(*clip(ln.start, ln.end, lo, hi))
        busy_per_plane.append(float((e - s).sum()))
        edges_s = np.concatenate(([lo], e))
        edges_e = np.concatenate((s, [hi]))
        gap = edges_e - edges_s
        keep = np.flatnonzero(gap > 0)
        mids = (edges_s[keep] + edges_e[keep]) / 2      # ascending
        for name, d in zip(name_gaps(hosts, mids), gap[keep]):
            gaps_by_name[name] = gaps_by_name.get(name, 0.0) + float(d)
    programs: Dict[str, float] = {}
    launches = 0
    for plane, ln in sorted(_device_lines(lines, MODULE_LINES, rehearsal).items()):
        s, e = np.clip(ln.start, lo, hi), np.clip(ln.end, lo, hi)
        for n, d in zip(ln.names, e - s):
            if d > 0:
                launches += 1
                key = re.sub(r"\(\d+\)$", "", n)
                programs[key] = programs.get(key, 0.0) + float(d)
    n_dev = len(busy_per_plane)
    busy_s = sum(busy_per_plane) / n_dev / 1e9
    window_s = (hi - lo) / 1e9

    def top_of(d: dict) -> list:
        return [[k, v / 1e9 / n_dev] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s, "devices": n_dev,
            "launches": launches / n_dev,
            "ops_line": sorted({ln.name for ln in ops.values()}),
            "device_ops": top_of(programs), "idle_gaps": top_of(gaps_by_name)}


def describe(lines: List[Line], limit: int = 6) -> str:
    """Planes, lines and first events — for reading a new trace by hand."""
    out = []
    for ln in lines:
        out.append(f"{ln.plane} | {ln.name} | {len(ln.names)} events")
        for n, s, e in list(zip(ln.names, ln.start, ln.end))[:limit]:
            out.append(f"    {n[:100]}  start={s:.0f} dur={e - s:.0f}")
    return "\n".join(out)
