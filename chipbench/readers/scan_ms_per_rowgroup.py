"""ms per row group of the scan node's timers (`keys` among decodeTime,
hostDecodeTime, uploadTime), summed over the window's queries."""


def read(ctx, keys):
    groups = ctx.after["decode"]["row_groups"] - ctx.before["decode"]["row_groups"]
    scans = [r.extra["scan"] for r in ctx.completed() if r.extra.get("scan")]
    if groups <= 0 or not any(k in s for s in scans for k in keys):
        return None
    return sum(s.get(k, 0) for s in scans for k in keys) / 1e6 / groups
