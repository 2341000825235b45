"""The collective exchange program's share of its roofline: the least device
time a chip could take for the exchanges of the traced queries
(chipbench/mesh_roofline.py: their bytes through HBM once each way, or the
bytes that changed chip over the interconnect, whichever is longer) over the
device time a chip spent in the program `program` (`trace.reduce` lists the
ten programs that took most time, each as the mean over the device planes).
The bytes are what the plan's exchanges counted in each query's summary
(`exchange.bytes`, `mesh.bytes_moved`), taken as the mean a query of the
window. Nothing where the program is not among the ten, or the counters are
not there (the parent of the PR that added them)."""

from chipbench.mesh_roofline import least_seconds
from chipbench.readers.query_counter import read as counter


def read(ctx, program):
    done = ctx.traced_queries()
    if ctx.trace is None or not done:
        return None
    spent = sum(s for name, s in ctx.trace["device_ops"] if name == program)
    exchanged = counter(ctx, "exchange.bytes")
    moved = counter(ctx, "mesh.bytes_moved")
    if spent <= 0 or not exchanged or moved is None:
        return None
    least = least_seconds(exchanged * len(done), moved * len(done),
                          ctx.cell.chips, ctx.device_kind)
    return 100.0 * least / spent
