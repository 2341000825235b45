"""Wall ms of one phase of the served path (`phase`), summed over the window's
queries (chipbench/phases.py), per query (`per` = "query") or per occurrence
(`per` = "count": a per-batch phase counts its batches)."""

from chipbench.phases import window_queries


def read(ctx, phase, per):
    queries = window_queries(ctx)
    if not queries:
        return None
    cells = [q["phases"][phase] for q in queries if phase in q["phases"]]
    if per == "query":
        n = len(queries)
    elif per == "count":
        n = sum(c["count"] for c in cells)
    else:
        raise ValueError(f"per is {per!r}, not 'query' or 'count'")
    if not cells or not n:
        return None
    return sum(c["wall_ns"] for c in cells) / 1e6 / n
