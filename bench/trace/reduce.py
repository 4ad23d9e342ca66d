"""From a profiler trace to the numbers the per-layer metrics read.

``load_events`` flattens the ``.xplane.pb`` that ``jax.profiler`` writes
into plain events: device operations (planes ``/device:*``, the line of
XLA ops) and the host spans the drivers open with
``jax.profiler.TraceAnnotation``. Everything else works on those plain
events, so the tests can run it on a small recorded trace.

Times are nanoseconds on the trace's one clock. Where several devices
are traced, a device total is the mean over the devices.
"""

from __future__ import annotations

import bisect
import fnmatch
import glob
import json
import os
import pathlib

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_LINE = "python"
MODULE_TABLE = pathlib.Path(__file__).with_name("modules.json")


def load_events(trace_dir: str, span_names) -> dict:
    """{"device": [...], "spans": [...]} from the newest trace under
    ``trace_dir``. A device event is ``{"device", "name", "module",
    "start_ns", "dur_ns"}``, one per XLA op on a ``/device:*`` plane; a
    span is ``{"name", "start_ns", "dur_ns"}`` for host annotations named
    in ``span_names`` (a span name may end in ``*``)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {"device": [], "spans": []}
    pd = ProfileData.from_file(files[-1])
    device, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            runs = [{"name": ev.name, "start_ns": float(ev.start_ns),
                     "dur_ns": float(ev.duration_ns)}
                    for ev in (lines[MODULES_LINE].events
                               if MODULES_LINE in lines else ())]
            ops = [{"device": plane.name, "name": op_name(ev.name),
                    "module": dict(ev.stats).get("hlo_module"),
                    "start_ns": float(ev.start_ns),
                    "dur_ns": float(ev.duration_ns)}
                   for ev in (lines[OPS_LINE].events
                              if OPS_LINE in lines else ())]
            device.extend(nest(with_modules(ops, runs)))
        elif plane.name.startswith("/host:"):
            # the annotating thread's line: "python" on a CPU host,
            # "python3" on a TPU host
            for line in plane.lines:
                if not line.name.startswith(HOST_SPAN_LINE):
                    continue
                for ev in line.events:
                    if any(fnmatch.fnmatchcase(ev.name, p)
                           for p in span_names):
                        spans.append({"name": ev.name,
                                      "start_ns": float(ev.start_ns),
                                      "dur_ns": float(ev.duration_ns)})
    return {"device": device, "spans": spans}


def op_name(text: str) -> str:
    """An XLA op's instruction name. A TPU trace names an op event by its
    whole HLO line (``%fusion.3 = f32[8]{0} fusion(...), calls=...``); a
    CPU trace by the name alone."""
    return text.split(" = ", 1)[0].lstrip("%") if " = " in text else text


def nest(ops: list) -> list:
    """``ops`` of one device, each marked with its ``depth`` (0: an op no
    other op contains) and whether it is a ``leaf`` (contains none). A
    TPU trace nests ops: a ``while`` op's event spans its body's."""
    open_: list = []
    for op in sorted(ops, key=lambda o: (o["start_ns"], -o["dur_ns"])):
        while open_ and (open_[-1]["start_ns"] + open_[-1]["dur_ns"]
                         <= op["start_ns"]):
            open_.pop()
        op["depth"], op["leaf"] = len(open_), True
        if open_:
            open_[-1]["leaf"] = False
        open_.append(op)
    return ops


def with_modules(ops: list, runs: list) -> list:
    """``ops`` with each op that names no XLA module given the name of the
    module run (an event of the device's module line) in which it
    starts."""
    runs = sorted(runs, key=lambda r: r["start_ns"])
    starts = [r["start_ns"] for r in runs]
    for op in ops:
        if op["module"] is None:
            i = bisect.bisect_right(starts, op["start_ns"]) - 1
            if i >= 0 and op["start_ns"] < starts[i] + runs[i]["dur_ns"]:
                op["module"] = runs[i]["name"]
    return ops


def span(events: dict, name: str):
    """(start, end) of the first host span called ``name``, or None."""
    for s in events["spans"]:
        if s["name"] == name:
            return s["start_ns"], s["start_ns"] + s["dur_ns"]
    return None


def devices(events: dict) -> list[str]:
    return sorted({e["device"] for e in events["device"]})


def _clipped(events: dict, lo: float, hi: float, device=None):
    for e in events["device"]:
        if device is not None and e["device"] != device:
            continue
        s, t = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
        if t > s:
            yield e, s, t


def busy_intervals(events: dict, lo: float, hi: float, device) -> list:
    """Merged [start, end) intervals in which ``device`` ran an op."""
    ivs = sorted((s, t) for _, s, t in _clipped(events, lo, hi, device))
    merged: list[list[float]] = []
    for s, t in ivs:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return merged


def busy_ns(events: dict, lo: float, hi: float) -> float:
    """Mean over devices of the union of op intervals inside [lo, hi)."""
    devs = devices(events)
    if not devs:
        return 0.0
    return sum(sum(t - s for s, t in busy_intervals(events, lo, hi, d))
               for d in devs) / len(devs)


def idle_share(events: dict, lo: float, hi: float):
    """1 - busy / window, or None where no device op was traced."""
    if hi <= lo or not devices(events):
        return None
    return 1.0 - busy_ns(events, lo, hi) / (hi - lo)


def module_table(path=MODULE_TABLE) -> dict:
    with open(path) as fh:
        return json.load(fh)


def time_by(events: dict, lo: float, hi: float, key, ops="top") -> dict:
    """{key(event): device ns inside [lo, hi)}, mean over devices, over
    the ops no other contains (``ops="top"``) or those that contain none
    (``"leaf"``); events whose key is None are left out."""
    devs = devices(events)
    out: dict = {}
    for e, s, t in _clipped(events, lo, hi):
        if (e.get("depth", 0) > 0 if ops == "top"
                else not e.get("leaf", True)):
            continue
        k = key(e)
        if k is not None:
            out[k] = out.get(k, 0.0) + (t - s)
    return {k: v / max(len(devs), 1) for k, v in out.items()}


def layer_of(table: dict):
    """Key function mapping an event to the layer whose patterns
    (``fnmatch``) it matches, per ``modules.json``: any of ``modules``
    (the XLA module) or any of ``ops`` (the op name)."""
    def match(value, pattern):
        return value is not None and fnmatch.fnmatchcase(value, pattern)

    def key(e):
        mod, op = e.get("module"), e["name"]
        for layer, pats in table.items():
            if (any(match(mod, p) for p in pats.get("modules", []))
                    or any(match(op, p) for p in pats.get("ops", []))):
                return layer
        return None
    return key


def top_ops(events: dict, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` innermost op names with the most device time, [name,
    seconds]."""
    by = time_by(events, lo, hi, lambda e: e["name"], ops="leaf")
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(events: dict, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` longest gaps between ops on the first device inside
    [lo, hi), each labelled by the innermost host span open at its
    middle: [label, seconds]."""
    devs = devices(events)
    if not devs:
        return []
    ivs = busy_intervals(events, lo, hi, devs[0])
    edges = [lo] + [x for iv in ivs for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + t) / 2
        open_ = [sp for sp in events["spans"]
                 if sp["start_ns"] <= mid < sp["start_ns"] + sp["dur_ns"]]
        label = min(open_, key=lambda sp: sp["dur_ns"])["name"] \
            if open_ else "none"
        out.append([label, (t - s) / 1e9])
    return out


def calls_of(events: dict, lo: float, hi: float, layer: str,
             table: dict) -> list:
    """Events of ``layer`` that start inside [lo, hi) on the first
    device, in time order."""
    devs = devices(events)
    if not devs:
        return []
    key = layer_of(table)
    return sorted((e for e in events["device"]
                   if e["device"] == devs[0] and lo <= e["start_ns"] < hi
                   and key(e) == layer), key=lambda e: e["start_ns"])
