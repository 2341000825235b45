"""How late the load generator ran, in ms: a query's send time less the later
of its due time and the moment its tenant's previous query answered. A starved
generator must not be read as a fast server."""

from chipbench.stats import percentile


def read(ctx, q):
    late = [(r.sent - max(r.due, r.free_at)) * 1e3 for r in ctx.records]
    return percentile(late, q) if late else None
