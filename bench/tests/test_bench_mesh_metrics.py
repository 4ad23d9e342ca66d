"""The four-chip cell's per-layer metrics on a small recorded trace.

``data/trace_mesh.json`` holds the plain events, in the form
``reduce.load_events`` gives, of a profiler trace of two 1,024-trial
``dg`` studies over two apps of the bank on a 2 x 2 ``("app", "trial")``
mesh of four CPU devices: each device's XLA ops (on a CPU host the op
events of ``device_ordinal`` n stand in for device n's) and the driver's
``window`` and ``study`` spans. Each metric is checked against a plain
recount over the same events, and reads nothing, never 0, where the
trace holds nothing it reads.
"""

from __future__ import annotations

import json
import pathlib

import pytest

from bench.lib import registry
from bench.trace import reduce

DATA = pathlib.Path(__file__).with_name("data")
METRICS = ("merge_device_ms.x4", "device_skew.x4", "scan_device_ms.x4")


@pytest.fixture(scope="module")
def events():
    return json.loads((DATA / "trace_mesh.json").read_text())


def ctx_of(events):
    """A metric's context for the recorded window: one request per
    recorded study."""
    lo, _ = reduce.span(events, "window")
    reqs = [((s["start_ns"] - lo) / 1e9,
             (s["start_ns"] + s["dur_ns"] - lo) / 1e9, 1024)
            for s in events["spans"] if s["name"] == "study"]
    return {"trace": events, "requests": reqs}


def in_window(events, e):
    lo, hi = reduce.span(events, "window")
    return lo <= e["start_ns"] and e["start_ns"] + e["dur_ns"] <= hi


def test_recorded_trace_spans_four_devices(events):
    assert len(reduce.devices(events)) == 4
    assert len(ctx_of(events)["requests"]) == 2
    for dev in reduce.devices(events):
        assert any(e["name"] == "all-reduce" or e["name"].startswith("psum")
                   for e in events["device"] if e["device"] == dev)


def test_merge_device_ms_is_the_all_reduce_time_per_study(events):
    got = registry.metric("merge_device_ms.x4").read(ctx_of(events))
    merge = [e for e in events["device"] if in_window(events, e)
             and (e["name"].startswith("all-reduce")
                  or e["name"].startswith("psum"))]
    # no merge op nests in another here, so their sum is their union
    assert all(e["leaf"] for e in merge)
    recount = sum(e["dur_ns"] for e in merge) / 4 / 1e6 / 2
    assert got == pytest.approx(recount)
    assert 0 < got < registry.metric("scan_device_ms.x4").read(
        ctx_of(events))


def test_device_skew_is_the_busiest_device_over_the_mean(events):
    got = registry.metric("device_skew.x4").read(ctx_of(events))
    lo, hi = reduce.span(events, "window")
    busy = []
    for dev in reduce.devices(events):
        ivs = sorted((max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"],
                                                  hi))
                     for e in events["device"] if e["device"] == dev)
        total, end = 0.0, lo
        for s, t in ivs:                       # union, by a sweep
            total += max(t - max(s, end), 0.0)
            end = max(end, t)
        busy.append(total)
    assert got == pytest.approx(max(busy) * 4 / sum(busy))
    assert got >= 1.0


def test_scan_device_ms_is_the_scan_modules_time_per_study(events):
    got = registry.metric("scan_device_ms.x4").read(ctx_of(events))
    top = [e for e in events["device"] if in_window(events, e)
           and e["module"] == "jit_prog" and e["depth"] == 0]
    assert got == pytest.approx(sum(e["dur_ns"] for e in top) / 4 / 1e6 / 2)


@pytest.mark.parametrize("name", METRICS)
def test_nothing_to_read_is_none(events, name):
    read = registry.metric(name).read
    ctx = ctx_of(events)
    assert read({"trace": None, "requests": ctx["requests"]}) is None
    empty = {"device": [], "spans": events["spans"]}
    assert read({"trace": empty, "requests": ctx["requests"]}) is None
    # a trace with ops but none of the metric's own
    if name == "merge_device_ms.x4":
        none_merged = {"device": [e for e in events["device"]
                                  if not e["name"].startswith(("all-reduce",
                                                               "psum"))],
                       "spans": events["spans"]}
        assert read({"trace": none_merged,
                     "requests": ctx["requests"]}) is None
    if name == "scan_device_ms.x4":
        no_scan = {"device": [e for e in events["device"]
                              if e["module"] != "jit_prog"],
                   "spans": events["spans"]}
        assert read({"trace": no_scan, "requests": ctx["requests"]}) is None
