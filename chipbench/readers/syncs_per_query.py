"""Blocking device-to-host transfers the program's SyncLedger counted in the
window, per completed query (an exact count)."""


def read(ctx):
    n = len(ctx.completed())
    return (ctx.after["syncs"] - ctx.before["syncs"]) / n if n else None
