"""trial_lanes_per_s: trial-lanes (trials x apps x schemes) of every study
completed in the window, over the window's whole length; every study's
statistics are on the host when it counts."""


def read(ctx):
    reqs = ctx["requests"]
    if not reqs:
        return None
    return sum(r[2] for r in reqs) / ctx["window_s"]
