"""Process start to window start: import, data, upload, compile or cache load, warm-up."""


def read(ctx):
    return ctx.setup_s
