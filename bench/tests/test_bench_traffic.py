"""Traffic from the seed, and the arithmetic of the end-to-end metrics."""

from __future__ import annotations

import numpy as np
import pytest

from bench.lib import registry

TRIALS = registry.driver("trials")


def test_same_seed_gives_the_same_studies():
    big = 2 ** 31 + 12345
    assert [TRIALS.study_seed(big, i) for i in range(50)] == \
        [TRIALS.study_seed(big, i) for i in range(50)]


def test_another_seed_gives_other_studies():
    a = [TRIALS.study_seed(7, i) for i in range(50)]
    b = [TRIALS.study_seed(8, i) for i in range(50)]
    assert not set(a) & set(b)
    assert len(set(a)) == 50


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 - 1, 2 ** 33 + 5])
def test_study_seeds_fit_a_prng_key(seed):
    s = TRIALS.study_seed(seed, 3)
    assert 0 <= s < 2 ** 31


SWEEPS = registry.driver("sweeps")
PAIRS = {"pairs": [["bbv", "mean"], ["rfv", "random"], ["dg", "centroid"],
                   ["dg", "random"]]}


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 12345])
def test_every_seed_sends_the_same_mix_of_plans(seed):
    draws = [SWEEPS.draw(PAIRS, seed, i) for i in range(40)]
    assert draws == [SWEEPS.draw(PAIRS, seed, i) for i in range(40)]
    for b in range(10):
        block = [p for p, _ in draws[4 * b:4 * b + 4]]
        assert sorted(block) == sorted(tuple(p) for p in PAIRS["pairs"])
    assert all((s == 0) == (p[1] != "random") for p, s in draws)


def test_another_seed_sends_the_plans_in_another_order():
    a = [SWEEPS.draw(PAIRS, 7, i) for i in range(40)]
    b = [SWEEPS.draw(PAIRS, 8, i) for i in range(40)]
    assert [p for p, _ in a] != [p for p, _ in b]
    assert not {s for _, s in a if s} & {s for _, s in b if s}


def test_checked_studies_are_drawn_from_the_seed():
    outs = list(range(40))
    a = TRIALS.sample_studies(outs, 5, 3)
    assert a == TRIALS.sample_studies(outs, 5, 3)
    assert len(set(a)) == 3
    assert TRIALS.sample_studies(outs[:2], 5, 3) == [0, 1]


@pytest.mark.parametrize("name", ["trial_lanes_per_s",
                                  "trial_lanes_per_s.dense"])
def test_rate_counts_all_work_over_the_whole_window(name):
    read = registry.metric(name).read
    # two studies of 100 lanes; the window ran 4 s in all (the second
    # study ended at 3 s, after a 1 s stall the host clock also counts)
    ctx = dict(requests=[(0.0, 1.0, 100), (2.0, 3.0, 100)], window_s=4.0)
    assert read(ctx) == 50.0
    assert read(dict(requests=[], window_s=4.0)) is None


def test_setup_and_build_metrics_read_the_set_up_record():
    ctx = dict(setup_s=41.5, build_s=22.25, compile_s=0.5)
    assert registry.metric("setup_s").read(ctx) == 41.5
    assert registry.metric("build_s.setup").read(ctx) == 22.25
    assert registry.metric("compile_s.setup").read(ctx) == 0.5


def test_trace_metrics_find_nothing_without_a_trace():
    ctx = dict(trace=None, requests=[(0, 1, 1)])
    for name in ("idle_share.trials", "idle_share.dense",
                 "scan_device_ms.trials", "kmeans_assign_roofline.setup"):
        assert registry.metric(name).read(ctx) is None


def test_kmeans_roofline_pairs_calls_with_fits_in_time_order():
    """Two fits' kernel calls, each fit one program: the first program's
    calls are the first fit's, each fit with its own shape."""
    from bench.lib import peaks, roofline

    read = registry.metric("kmeans_assign_roofline.setup").read
    fits = [dict(n=[120000, 40000], k=20, d=15),
            dict(n=[6861, 2000], k=20, d=38)]

    def ev(t, dur, name="kmeans_assign_padded.12", prog="fit(1)"):
        return {"device": "/device:TPU:0", "name": name,
                "module": f"jit__kmeans_fit_stacked_{prog}", "start_ns": t,
                "dur_ns": dur}

    trace = {"spans": [{"name": "setup", "start_ns": 0.0,
                        "dur_ns": 1e9}],
             "device": [ev(10.0, 1e6), ev(2e6, 1e6, "kmeans_assign_padded.11"),
                        ev(5e6, 2e5, prog="fit(2)"),
                        ev(6e6, 2e5, prog="fit(2)"),
                        ev(7e6, 2e5, "kmeans_assign_padded.11", prog="fit(2)"),
                        ev(8e6, 5e5, name="fusion.1")]}
    peak = peaks.peaks("TPU v5 lite")
    least = 0.0
    for fit, calls in zip(fits, (2, 3)):
        t, _ = roofline.least_time(
            *roofline.kmeans_assign_counts(fit["n"], fit["k"], fit["d"]),
            peak["flops_bf16"], peak["hbm_bytes_per_s"])
        least += calls * t
    ctx = dict(trace=trace, kernels={"kmeans_assign": fits},
               device_kind="TPU v5 lite")
    assert read(ctx) == pytest.approx(100.0 * least / 2.6e-3)
    # a program more than the fits: nothing to read
    trace["device"].append(ev(9e6, 1e5, prog="fit(3)"))
    assert read(ctx) is None


def test_ops_without_a_module_take_the_module_run_they_start_in():
    from bench.trace import reduce

    runs = [{"name": "jit_prog(7)", "start_ns": 100.0, "dur_ns": 50.0},
            {"name": "jit_traced(9)", "start_ns": 0.0, "dur_ns": 40.0}]
    ops = [{"name": "a", "module": None, "start_ns": 120.0},
           {"name": "b", "module": None, "start_ns": 10.0},
           {"name": "c", "module": None, "start_ns": 60.0},
           {"name": "d", "module": "jit_x", "start_ns": 110.0}]
    got = {o["name"]: o["module"] for o in reduce.with_modules(ops, runs)}
    assert got == {"a": "jit_prog(7)", "b": "jit_traced(9)", "c": None,
                   "d": "jit_x"}
    table = reduce.module_table()
    assert reduce.layer_of(table)(dict(ops[0], name="fusion.3")) \
        == "trial_scan"


def test_p95_is_over_every_request_of_the_window():
    read = registry.metric("request_p95_ms").read
    # 100 requests: 90 of 10 ms and 10 slow ones of 1 s
    reqs = [(float(i), float(i) + (1.0 if i % 10 == 9 else 0.01), 70)
            for i in range(100)]
    assert read(dict(requests=reqs)) == pytest.approx(1000.0)
    assert read(dict(requests=reqs[:50])) == pytest.approx(
        1e3 * float(np.percentile([r[1] - r[0] for r in reqs[:50]], 95)))
    rows = registry.metric("sweep_rows_per_s").read
    assert rows(dict(requests=reqs, window_s=200.0)) == 35.0
