"""The trace reduction on a small recorded trace.

``data/trace_small.json`` holds the plain events of a profiler trace of
two 1,024-trial studies over two apps of the bank, in the form
``reduce.load_events`` gives: the XLA ops (here recorded on a CPU host,
whose op events stand in for one device's) and the driver's ``window``
and ``study`` spans. Each number the reduction gives is checked against
a plain recount over the same events.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np
import pytest

from bench.trace import reduce

DATA = pathlib.Path(__file__).with_name("data") / "trace_small.json"


@pytest.fixture(scope="module")
def events():
    return json.loads(DATA.read_text())


@pytest.fixture(scope="module")
def window(events):
    return reduce.span(events, "window")


def raster(events, lo, hi, device, step=100.0):
    """Boolean busy mask of ``device`` on a grid of ``step`` ns."""
    n = int(np.ceil((hi - lo) / step))
    busy = np.zeros(n, bool)
    for e in events["device"]:
        if e["device"] != device:
            continue
        s = int(np.floor((max(e["start_ns"], lo) - lo) / step))
        t = int(np.ceil((min(e["start_ns"] + e["dur_ns"], hi) - lo) / step))
        if t > s:
            busy[s:t] = True
    return busy


def test_recorded_trace_has_device_ops_and_spans(events, window):
    assert window is not None
    assert len(reduce.devices(events)) == 1
    assert len(events["device"]) > 50
    names = {s["name"] for s in events["spans"]}
    assert {"window", "study"} <= names


def test_busy_time_is_the_union_of_op_intervals(events, window):
    lo, hi = window
    dev = reduce.devices(events)[0]
    busy = reduce.busy_ns(events, lo, hi)
    recount = raster(events, lo, hi, dev).sum() * 100.0
    # the raster rounds each interval out to the 100 ns grid
    n_ops = sum(1 for e in events["device"] if e["device"] == dev)
    assert busy <= recount <= busy + 200.0 * n_ops
    assert 0 < busy <= sum(e["dur_ns"] for e in events["device"])


def test_idle_share_is_one_minus_busy_over_window(events, window):
    lo, hi = window
    share = reduce.idle_share(events, lo, hi)
    assert share == pytest.approx(1 - reduce.busy_ns(events, lo, hi)
                                  / (hi - lo))
    assert 0 < share < 1
    assert reduce.idle_share({"device": [], "spans": []}, lo, hi) is None


def test_time_by_module_sums_each_modules_ops(events, window):
    lo, hi = window
    table = reduce.module_table()
    by = reduce.time_by(events, lo, hi, reduce.layer_of(table))
    assert by.get("trial_scan", 0) > 0
    key = reduce.layer_of(table)
    want = sum(min(e["start_ns"] + e["dur_ns"], hi) - max(e["start_ns"], lo)
               for e in events["device"]
               if key(e) == "trial_scan"
               and e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo)
    assert by["trial_scan"] == pytest.approx(want)


def test_top_ops_are_the_largest_by_name(events, window):
    lo, hi = window
    top = reduce.top_ops(events, lo, hi, n=3)
    by = reduce.time_by(events, lo, hi, lambda e: e["name"])
    assert [t[0] for t in top] == sorted(by, key=lambda k: -by[k])[:3]
    assert top[0][1] == pytest.approx(max(by.values()) / 1e9)


def test_idle_gaps_are_labelled_by_the_innermost_open_span(events, window):
    lo, hi = window
    gaps = reduce.idle_gaps(events, lo, hi, n=5)
    assert gaps and len(gaps) <= 5
    secs = [g[1] for g in gaps]
    assert secs == sorted(secs, reverse=True)
    dev = reduce.devices(events)[0]
    busy = raster(events, lo, hi, dev)
    # the longest gap is no shorter than the longest idle run on the grid
    # less one grid step at either end
    runs, cur = [], 0
    for b in busy:
        cur = 0 if b else cur + 1
        runs.append(cur)
    assert secs[0] * 1e9 >= (max(runs) - 2) * 100.0
    assert {g[0] for g in gaps} <= {s["name"] for s in events["spans"]} | {
        "none"}


@pytest.mark.parametrize("text,name", [
    ("%kmeans_assign_padded.12 = (s32[10,1,7168]{2,1,0:T(1,128)S(1)}, "
     "f32[10,1,7168]{2,1,0:T(1,128)}) custom-call(f32[10,7168,128] %pad.145),"
     " custom_call_target=\"tpu_custom_call\"", "kmeans_assign_padded.12"),
    ("%slice_bitcast_fusion.9 = s32[10,6861,1] fusion(s32[10,1,7168] "
     "%jit_kmeans_assign_padded_.8), kind=kLoop", "slice_bitcast_fusion.9"),
    ("bitcast_copy_fusion", "bitcast_copy_fusion"),
])
def test_op_events_are_named_by_their_instruction(text, name):
    """A TPU trace names an op by its whole HLO line; a name pattern is
    matched against the instruction's name alone, not its operands."""
    assert reduce.op_name(text) == name
    key = reduce.layer_of({"kmeans_assign": {"ops": ["kmeans_assign_padded*"]}})
    hit = key({"name": reduce.op_name(text), "module": None})
    assert (hit == "kmeans_assign") == name.startswith("kmeans_assign_padded")


def test_nested_ops_count_once():
    """A loop's event spans its body's ops: module time counts the loop,
    the top ops list the body's innermost ops."""
    def op(name, s, d):
        return {"device": "/device:TPU:0", "name": name, "module": "jit_prog(1)",
                "start_ns": s, "dur_ns": d}

    ops = reduce.nest([op("while.2", 0.0, 100.0), op("fusion.1", 10.0, 30.0),
                       op("fusion.2", 50.0, 40.0), op("copy.3", 120.0, 10.0)])
    ev = {"device": ops, "spans": []}
    assert [(o["depth"], o["leaf"]) for o in ops] == [
        (0, False), (1, True), (1, True), (0, True)]
    assert reduce.time_by(ev, 0.0, 200.0, lambda e: e["module"]) == {
        "jit_prog(1)": 110.0}
    assert reduce.top_ops(ev, 0.0, 200.0) == [
        ["fusion.2", 4e-8], ["fusion.1", 3e-8], ["copy.3", 1e-8]]
    assert reduce.busy_ns(ev, 0.0, 200.0) == 110.0
