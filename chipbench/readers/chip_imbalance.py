"""How unevenly a mesh session's work fell on its chips, in %: over the
window's queries (chipbench/phases.py) the rows each chip's partition tasks
took in — the summary counters `<prefix><r>`, one a chip — the largest over
their mean. 100 % is an even split; what the hash of the join keys gives lies
a little above it. No such counter (a one-chip session, or the parent of the
PR that added them): nothing."""

from chipbench.phases import window_queries


def read(ctx, prefix):
    per_chip = {}
    for q in window_queries(ctx) or ():
        for name, n in q.get("counters", {}).items():
            if name.startswith(prefix):
                per_chip[name] = per_chip.get(name, 0) + n
    total = sum(per_chip.values())
    if not total:
        return None
    return 100.0 * max(per_chip.values()) / (total / len(per_chip))
