"""build_s.setup: host seconds of ``ExperimentEngine.build`` over the
bank in set-up (it returns host arrays, so the time is synced)."""


def read(ctx):
    return ctx.get("build_s")
