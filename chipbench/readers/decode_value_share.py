"""Share (%) of the values the device decoder produced in the window that
took `part` of its paths, from the program's `device_decode.decode_stats()`
(`values` = rows x device-decoded columns; `part` names another of its
counters, a subset of `values`). A program without the counters (the parent
of the PR that added them), or a window that decoded nothing, reads as
nothing — not as 0 %."""


def read(ctx, part):
    before, after = ctx.before["decode"], ctx.after["decode"]
    if "values" not in after or part not in after:
        return None
    values = after["values"] - before.get("values", 0)
    if values <= 0:
        return None
    return 100.0 * (after[part] - before.get(part, 0)) / values
