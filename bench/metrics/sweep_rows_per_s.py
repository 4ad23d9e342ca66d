"""sweep_rows_per_s: (app, config) estimate rows returned to the host by
the window's sweeps, over the window's whole length."""


def read(ctx):
    reqs = ctx["requests"]
    if not reqs:
        return None
    return sum(r[2] for r in reqs) / ctx["window_s"]
