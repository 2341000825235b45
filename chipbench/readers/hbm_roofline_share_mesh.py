"""`hbm_roofline_share` for a cell whose state is sharded over its chips: the
least time the cell's chips could take for the traced queries — the bytes the
questions need (chipbench/roofline.py) over the chips' HBM rates together —
as a share of the device busy time a chip (`trace.reduce` gives the mean over
the device planes). The one-chip reader sets one chip's rate against that
mean, so with sharded state it would read `chips` times too high."""

from chipbench.roofline import peaks


def read(ctx):
    done = ctx.traced_queries()
    if ctx.trace is None or not done or ctx.trace["busy_s"] <= 0:
        return None
    nbytes = sum(ctx.bytes_of[(r.tenant, r.template)] for r in done)
    rate = ctx.cell.chips * peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (nbytes / rate) / ctx.trace["busy_s"]
