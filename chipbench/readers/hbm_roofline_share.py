"""Least time the chip could take for the traced queries — the bytes the
questions need (chipbench/roofline.py) over the published HBM rate — as a
share of ALL device busy time of the traced window, whatever program spent it.
Bounded by bytes: Q1 and Q6 do a few operations per value read."""

from chipbench.roofline import least_seconds


def read(ctx):
    done = ctx.traced_queries()
    if ctx.trace is None or not done or ctx.trace["busy_s"] <= 0:
        return None
    nbytes = sum(ctx.bytes_of[(r.tenant, r.template)] for r in done)
    return 100.0 * least_seconds(nbytes, ctx.device_kind) / ctx.trace["busy_s"]
