"""fused_device_ms.sweep: device milliseconds per sweep of the fused
sweep megaprogram, attributed by XLA module name through
``bench/trace/modules.json`` (layer ``fused_sweep``)."""

from bench.trace import reduce


def read(ctx):
    ev = ctx.get("trace")
    win = ev and reduce.span(ev, "window")
    if not win or not ctx["requests"]:
        return None
    by = reduce.time_by(ev, *win, reduce.layer_of(reduce.module_table()))
    if "fused_sweep" not in by:
        return None
    return by["fused_sweep"] / 1e6 / len(ctx["requests"])
