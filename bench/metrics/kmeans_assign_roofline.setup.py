"""kmeans_assign_roofline.setup: percent of its roofline the k-means
assignment kernel reached over the build.

Each fit is one XLA program, and the kernel's calls in set-up are
grouped by the program they ran in, in time order of their first call:
the first program's calls are the first fit's the driver reports
(``ctx["kernels"]["kmeans_assign"]``: the BBV fit, then the RFV fit,
each with its shape). The least time of a call is the larger of its
operations over the chip's peak FLOP/s and its bytes over the HBM
bandwidth (``bench/lib``); the share is the least time of every call
over their device time. Nothing is read where the programs found do not
number the fits.
"""

from bench.lib import peaks, roofline
from bench.trace import reduce


def read(ctx):
    ev = ctx.get("trace")
    fits = (ctx.get("kernels") or {}).get("kmeans_assign")
    win = ev and reduce.span(ev, "setup")
    if not win or not fits:
        return None
    calls = reduce.calls_of(ev, *win, "kmeans_assign", reduce.module_table())
    by_program: dict = {}
    for e in calls:
        by_program.setdefault(e["module"], []).append(e)
    if len(by_program) != len(fits):
        return None
    peak = peaks.peaks(ctx["device_kind"])
    least = spent = 0.0
    for fit, group in zip(fits, by_program.values()):
        ops, nbytes = roofline.kmeans_assign_counts(fit["n"], fit["k"],
                                                    fit["d"])
        t, _ = roofline.least_time(ops, nbytes, peak["flops_bf16"],
                                   peak["hbm_bytes_per_s"])
        least += t * len(group)
        spent += sum(e["dur_ns"] for e in group) / 1e9
    return 100.0 * least / spent if spent > 0 else None
