"""A percentile, in ms, of the admission-queue wait the scheduler reports for
each query (`session.last_admit_wait_ms()`)."""

from chipbench.stats import percentile


def read(ctx, q):
    waits = [r.extra["admit_wait_ms"] for r in ctx.completed()
             if r.extra.get("admit_wait_ms") is not None]
    return percentile(waits, q) if waits else None
