"""Device programs launched per query, counted in the trace (one event of the
XLA Modules line each). The program's own `calls_by_kind` does not see the
compiled stage's launches, so the count is taken on the device."""


def read(ctx):
    n = len(ctx.traced_queries())
    if ctx.trace is None or not n or not ctx.trace.get("launches"):
        return None
    return ctx.trace["launches"] / n
