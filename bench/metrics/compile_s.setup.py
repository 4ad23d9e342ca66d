"""compile_s.setup: seconds of XLA backend compiles during set-up, from
JAX's compile events (0 where every program came from the cache)."""


def read(ctx):
    return ctx.get("compile_s")
