"""Input rows of every query completed in the window (the rows of every table
the query reads) over the wall time from the window's start to the last
completion (the query in flight when the window closes finishes and counts)."""


def read(ctx):
    done = ctx.completed()
    if not done:
        return None
    return sum(ctx.rows_of[(r.tenant, r.template)] for r in done) / max(r.done for r in done)
