"""A counter of the program's per-query summaries (`counter`: what the plan's
nodes counted, docs/observability.md "Span model"), as the mean a query over
the window's queries (chipbench/phases.py). A program that does not count it
(the parent of the PR that added it) reads as nothing."""

from chipbench.phases import window_queries


def read(ctx, counter):
    queries = window_queries(ctx)
    values = [q.get("counters", {}).get(counter) for q in queries or ()]
    if not values or any(v is None for v in values):
        return None
    return sum(values) / len(values)
