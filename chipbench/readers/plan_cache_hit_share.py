"""Share (%) of the window's plan-cache lookups that found their plan, from
the program's `serving/plan_cache.py::stats()`: hits / (hits + misses). A
query whose literals differ from the cached plan's is a hit that re-binds
them. A program without the counters, or a window without a lookup, reads
as nothing."""


def read(ctx):
    before, after = ctx.before.get("plan_cache", {}), ctx.after.get("plan_cache", {})
    if "hits" not in after or "misses" not in after:
        return None
    hits = after["hits"] - before.get("hits", 0)
    lookups = hits + after["misses"] - before.get("misses", 0)
    return 100.0 * hits / lookups if lookups > 0 else None
