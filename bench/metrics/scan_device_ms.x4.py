"""scan_device_ms.x4: device milliseconds per study of the sharded
streaming trial-scan programs, mean over the devices traced. A program
is found by its XLA module name: under the mesh the scan is still the
module ``jit_prog``, layer ``trial_scan`` of ``bench/trace/modules.json``,
whose patterns are compiled once for the millions of op events of a
four-chip trace."""

import fnmatch
import re

from bench.trace import reduce


def read(ctx):
    ev = ctx.get("trace")
    win = ev and reduce.span(ev, "window")
    if not win or not ctx["requests"]:
        return None
    pats = reduce.module_table()["trial_scan"]["modules"]
    scan = re.compile("|".join(fnmatch.translate(p) for p in pats))
    by = reduce.time_by(ev, *win, lambda e: "scan" if e["module"]
                        and scan.match(e["module"]) else None)
    if "scan" not in by:
        return None
    return by["scan"] / 1e6 / len(ctx["requests"])
