"""idle_share.dense: percent of the window in which the device ran no
operation (mean over the devices traced), in the cells that report
trial_lanes_per_s.dense."""

from bench.trace import reduce


def read(ctx):
    ev = ctx.get("trace")
    win = ev and reduce.span(ev, "window")
    if not win:
        return None
    share = reduce.idle_share(ev, *win)
    return None if share is None else 100.0 * share
