"""The trial cells' output check catches the control and planted faults.

CPU only, at a size a test run holds: two apps of the bank, 512-trial
studies. Each test drives the rest of a benchmark run (the look for a
chip waived) with the timed path broken underneath, and sees a number
that a sound run keeps within its limit go over it.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.lib import bank, registry
from bench.reference import trials as ref_trials

CELL = "trials_1k.k20"
APPS = ["505.mcf_r", "520.omnetpp_r"]


def small_cell():
    cell = registry.resolve_cell(registry.benchmark(), CELL)
    cell["config"]["apps"] = APPS
    cell["workload"]["params"].update(trials=512, check_studies=1)
    return cell


def run_once(seed=3_000_000_007):
    args = run.parse_args(["--workload", CELL, "--seed", str(seed),
                           "--seconds", "0.2", "--trace", "0"])
    return run.run_cell(args, cell=small_cell(), require_accelerator=False,
                        log=lambda msg: None)


@pytest.fixture(scope="module")
def sound():
    return run_once()


@pytest.fixture
def fresh_programs():
    from repro.experiments import montecarlo

    montecarlo._streaming_program.cache_clear()
    yield
    montecarlo._streaming_program.cache_clear()


def caught(sound_checks, faulted_checks):
    """Numbers a sound run keeps within the limit and the fault breaks."""
    return [k for k, c in faulted_checks.items()
            if c["value"] > c["limit"]
            and sound_checks[k]["value"] <= sound_checks[k]["limit"]]


def test_sound_run_reads_every_number(sound):
    assert sound["attempted"] >= 1
    assert set(sound["checks"]) == set(
        small_cell()["workload"]["params"]["limits"])
    assert sound["checks"]["count_gap"]["value"] == 0


def test_state_left_unchanged_is_caught(sound, fresh_programs, monkeypatch):
    from repro.core.sampling import tables

    monkeypatch.setattr(tables, "trial_stats_update",
                        lambda stats, *a, **k: stats)
    res = run_once()
    assert not res["correct"]
    assert "count_gap" in caught(sound["checks"], res["checks"])


def test_half_the_batch_left_out_is_caught(sound, fresh_programs,
                                           monkeypatch):
    from repro.core.sampling import tables

    update = tables.trial_stats_update

    def half(stats, err, half_w, covered, valid):
        t = err.shape[-1]
        keep = jnp.arange(t) < t // 2
        return update(stats, err, half_w, covered, valid & keep)

    monkeypatch.setattr(tables, "trial_stats_update", half)
    res = run_once()
    assert not res["correct"]
    assert "count_gap" in caught(sound["checks"], res["checks"])


def test_altered_answer_is_caught(sound, fresh_programs, monkeypatch):
    from repro.experiments import montecarlo

    chunk = montecarlo._stratified_chunk

    def altered(u, truth, crit, *tables):
        est, err, half_w, covered = chunk(u, truth, crit, *tables)
        est = est * 1.001
        err = 100.0 * jnp.abs(est - truth[:, None]) / truth[:, None]
        return est, err, half_w, jnp.abs(est - truth[:, None]) <= half_w

    monkeypatch.setattr(montecarlo, "_stratified_chunk", altered)
    res = run_once()
    assert not res["correct"]
    assert caught(sound["checks"], res["checks"])


def test_lloyd_loop_left_at_its_start_is_caught(sound, monkeypatch):
    """k-means with no Lloyd iteration: centroids stay at their seeds,
    labels are nearest those seeds."""
    from repro.experiments import engine

    fit = engine.kmeans_bank
    monkeypatch.setattr(engine, "kmeans_bank",
                        lambda *a, **k: fit(*a, **dict(k, max_iters=0)))
    res = run_once()
    assert not res["correct"]
    assert {"bbv_centroid_gap", "rfv_centroid_gap"} <= set(
        caught(sound["checks"], res["checks"]))


def test_dalenius_gurney_refinement_dropped_is_caught(sound, monkeypatch):
    """Equal-count intervals of the baseline instead of the refined cuts."""
    from repro.experiments import engine

    dg = engine.dalenius_gurney_strata
    monkeypatch.setattr(engine, "dalenius_gurney_strata",
                        lambda x, L, **k: dg(x, L, max_iters=0))
    res = run_once()
    assert not res["correct"]
    assert "dg_gap" in caught(sound["checks"], res["checks"])


def test_bfloat16_control_is_caught():
    """The reference in the program's place, one precision step down."""
    cell = small_cell()
    config, params = cell["config"], cell["workload"]["params"]
    drv = registry.driver("trials")
    ctx = dict(config=config, params=params, seed=0,
               devices=jax.devices()[:1], clock=time.perf_counter)
    state = drv.setup(ctx)
    res = drv.request(state, 11, 0)["out"]
    prog = drv.extract(state)
    ref = bank.reference_build(config, prog["apps"])
    inputs = drv.study_inputs(config, ref, prog, params["config_index"])
    kw = dict(units_per_trial=params["units_per_trial"], keep=True)
    r = ref_trials.study(inputs, res["seed"], params["trials"],
                         params["schemes"], **kw)
    c = ref_trials.study(inputs, res["seed"], params["trials"],
                         params["schemes"], xp=jnp, dtype=jnp.bfloat16, **kw)
    control = {"stats": c, "estimates": {s: c[s]["estimates"] for s in c}}
    limits = params["limits"]
    sound_g = drv.compare_study(res, r)
    control_g = drv.compare_study(control, r)
    over = [k for k, v in control_g.items() if v > limits[k]]
    assert over, control_g
    assert all(sound_g[k] <= limits[k] for k in over)
    assert np.isfinite(list(control_g.values())).all()
