"""XLA backend compiles before the window: their count, or their seconds
(`what`), from JAX's `/jax/core/compile/backend_compile_duration` events. A
persistent-cache hit fires the event too, with the time the load took."""


def read(ctx, what):
    return ctx.compile_setup[what]
