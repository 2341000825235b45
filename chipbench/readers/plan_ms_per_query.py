"""Wall ms inside the scheduler's `plan.build` span (plan-cache fetch or full
planning) per query of the window, from the program's `plan.build_ms` histogram."""


def read(ctx):
    n = ctx.after["plan_count"] - ctx.before["plan_count"]
    if n <= 0:
        return None
    return (ctx.after["plan_ms"] - ctx.before["plan_ms"]) / n
