"""A percentile, in ms, of the time from when a query was due to be sent to
its answer on the host, over ALL queries of the window (or of one scheduler
class). A failed or shed query counts as the slowest seen, never as fast."""

from chipbench.stats import percentile


def read(ctx, q, slo_class=None):
    classes = [t.get("class") for t in ctx.cell.traffic["templates"]]
    recs = [r for r in ctx.records
            if slo_class is None or classes[r.template] == slo_class]
    ok = [(r.done - r.due) * 1e3 for r in recs if not r.failed]
    if not ok:
        return None
    worst = max(ok)
    return percentile(ok + [worst] * (len(recs) - len(ok)), q)
