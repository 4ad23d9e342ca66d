"""device_skew.x4: the busiest device's busy time in the window over the
mean busy time of the devices traced (1 where every device is busy
alike). Busy time is the union of a device's op intervals; the events
are split by device in one pass, as a four-chip trace holds millions."""

from bench.trace import reduce


def read(ctx):
    ev = ctx.get("trace")
    win = ev and reduce.span(ev, "window")
    if not win:
        return None
    by_device: dict = {}
    for e in ev["device"]:
        by_device.setdefault(e["device"], []).append(e)
    busy = [sum(t - s for s, t in
                reduce.busy_intervals({"device": ops}, *win, d))
            for d, ops in by_device.items()]
    if not busy or sum(busy) <= 0:
        return None
    return max(busy) / (sum(busy) / len(busy))
