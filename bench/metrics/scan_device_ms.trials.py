"""scan_device_ms.trials: device milliseconds per study of the streaming
trial-scan programs, attributed by XLA module name through
``bench/trace/modules.json`` (layer ``trial_scan``)."""

from bench.trace import reduce


def read(ctx):
    ev = ctx.get("trace")
    win = ev and reduce.span(ev, "window")
    if not win or not ctx["requests"]:
        return None
    by = reduce.time_by(ev, *win, reduce.layer_of(reduce.module_table()))
    if "trial_scan" not in by:
        return None
    return by["trial_scan"] / 1e6 / len(ctx["requests"])
