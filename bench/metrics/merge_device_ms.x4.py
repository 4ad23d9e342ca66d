"""merge_device_ms.x4: device milliseconds per study of the trial-axis
merge, the all-reduce ops of the trial scan's ``psum`` (op names
``all-reduce*``, or ``psum*`` where XLA keeps the name of the JAX
operation), mean over the devices traced. Each device's time is the
union of those ops' intervals, so an op nested in another counts once.
A four-chip trace holds millions of op events: one pass picks the few
merge ops out."""

from bench.trace import reduce

PREFIXES = ("all-reduce", "psum")


def read(ctx):
    ev = ctx.get("trace")
    win = ev and reduce.span(ev, "window")
    if not win or not ctx["requests"]:
        return None
    merge = {"device": [e for e in ev["device"]
                        if e["name"].startswith(PREFIXES)]}
    devs = reduce.devices(ev)
    total = sum(t - s for d in devs
                for s, t in reduce.busy_intervals(merge, *win, d))
    if total <= 0:
        return None
    return total / len(devs) / 1e6 / len(ctx["requests"])
