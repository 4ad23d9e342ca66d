"""trial_lanes_per_s.dense: trial_lanes_per_s of the cells whose studies
bring their dense per-trial arrays home, where the host between studies
sets the pace: trial-lanes (trials x apps x schemes) of every study
completed in the window, over the window's whole length. A name of its
own, so that its bound, set from the host's wider spread, is not the
scan cells'."""


def read(ctx):
    reqs = ctx["requests"]
    if not reqs:
        return None
    return sum(r[2] for r in reqs) / ctx["window_s"]
