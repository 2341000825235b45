"""The window's queries as the program itself timed them: the per-query
phase summaries of `spark_rapids_tpu.obs.metrics.recent_queries()` (one per
executed query, always on; docs/observability.md "Span model"). A summary
holds, per phase of the served path, `{count, wall_ns, cpu_ns,
child_wall_ns, cat}`: self time is wall - child_wall, and wall - cpu the time
the query's thread was off the CPU (`cpu_ns` is always there for the root
phase and for `wait` phases; for the others only in a traced query).

Nothing runs after the window, so its queries are the last
`len(ctx.records)` summaries. Of those the ones the profiler's annotations
were off for (`annotated` false) are taken — host times then do not carry the
profiler's weight — or all of them where there is none. A program without
the ring (the parent of the PR that added it), or a ring shorter than the
window, reads as nothing.
"""

from __future__ import annotations

from typing import List, Optional

ROOT = "query"
#: wait phases that lie outside the root phase
OUTSIDE_ROOT = ("sched.admit_wait",)


def window_queries(ctx) -> Optional[List[dict]]:
    try:
        from spark_rapids_tpu.obs.metrics import recent_queries
    except ImportError:
        return None
    n = len(ctx.records)
    recent = recent_queries(n) if n else []
    if not n or len(recent) < n:
        return None
    plain = [q for q in recent if not q["annotated"]]
    return plain or recent
