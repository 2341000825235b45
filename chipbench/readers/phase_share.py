"""A share, in %, of the root phase `query` over the window's queries
(chipbench/phases.py).

`what` = "blocked": of the root's wall outside its `wait` phases (the carry
fetch, the semaphore: there the thread is meant to be blocked), the part its
thread was off the CPU, wall - cpu — the GIL, a lock, an allocation or a
launch that waits.
`what` = "unattributed": the root's self time, what no nested phase covers."""

from chipbench.phases import OUTSIDE_ROOT, ROOT, window_queries


def read(ctx, what):
    queries = window_queries(ctx)
    roots = [q["phases"] for q in queries or () if ROOT in q["phases"]]
    if not roots:
        return None
    if what == "unattributed":
        part = sum(p[ROOT]["wall_ns"] - p[ROOT]["child_wall_ns"]
                   for p in roots)
        whole = sum(p[ROOT]["wall_ns"] for p in roots)
    elif what == "blocked":
        part = whole = 0
        for p in roots:
            waits = [c for n, c in p.items()
                     if c["cat"] == "wait" and n not in OUTSIDE_ROOT]
            wall = p[ROOT]["wall_ns"] - sum(c["wall_ns"] for c in waits)
            cpu = p[ROOT]["cpu_ns"] - sum(c["cpu_ns"] for c in waits)
            part += wall - cpu
            whole += wall
    else:
        raise ValueError(f"what is {what!r}, not 'blocked' or 'unattributed'")
    return 100.0 * part / whole if whole > 0 else None
