"""The sweep cell's output check catches the control and planted faults.

CPU only, at a size a test run holds: two apps of the bank at L = 50.
Each test drives the rest of a benchmark run (the look for a chip
waived) with the timed path broken underneath, and sees a number that a
sound run keeps within its limit go over it.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.lib import bank, registry

CELL = "sweeps.k50"
APPS = ["505.mcf_r", "520.omnetpp_r"]


def small_cell():
    """The cell as its own files give it: ``BENCHMARK.json`` leaves it
    out while the fused sweep it drives does not finish on a TPU v5e."""
    wl = registry.workload(CELL)
    cfg = registry.config(wl["config"])
    cfg["apps"] = APPS
    entry = {k: wl[k] for k in ("name", "config", "traffic", "chips")}
    return dict(entry=entry, workload=wl, config=cfg, config_entry=None)


def run_once(seed=3_000_000_009):
    args = run.parse_args(["--workload", CELL, "--seed", str(seed),
                           "--seconds", "0.5", "--trace", "0"])
    return run.run_cell(args, cell=small_cell(), require_accelerator=False,
                        log=lambda msg: None)


@pytest.fixture(scope="module")
def sound():
    return run_once()


@pytest.fixture
def fresh_programs():
    from repro.experiments import fused

    fused.fused_sweep_program.cache_clear()
    fused._BLOCK_CACHE.clear()
    yield
    fused.fused_sweep_program.cache_clear()
    fused._BLOCK_CACHE.clear()


def caught(sound_checks, faulted_checks):
    """Numbers a sound run keeps within the limit and the fault breaks."""
    return [k for k, c in faulted_checks.items()
            if c["value"] > c["limit"]
            and sound_checks[k]["value"] <= sound_checks[k]["limit"]]


def test_sound_run_reads_every_number(sound):
    assert sound["attempted"] >= 12
    assert set(sound["checks"]) == set(
        small_cell()["workload"]["params"]["limits"])
    assert sound["checks"]["memo_value_gap"]["value"] < 1e-5


def test_memo_left_unchanged_is_caught(sound, fresh_programs, monkeypatch):
    from repro.simcpu.cache import MemoBank

    monkeypatch.setattr(MemoBank, "absorb_selected",
                        lambda self, *a, **k: None)
    res = run_once()
    assert not res["correct"]
    assert "memo_set_off" in caught(sound["checks"], res["checks"])


def _patch_estimate(monkeypatch, wrap):
    from repro.core.sampling.plan import Estimator

    stage = Estimator.estimate_stage
    monkeypatch.setattr(Estimator, "estimate_stage",
                        staticmethod(wrap(stage)))


def test_half_the_strata_left_out_is_caught(sound, fresh_programs,
                                            monkeypatch):
    def wrap(stage):
        def half(cpi, valid, weights, truth):
            keep = jnp.arange(valid.shape[-1]) < valid.shape[-1] // 2
            return stage(cpi, valid & keep, weights, truth)
        return half

    _patch_estimate(monkeypatch, wrap)
    res = run_once()
    assert not res["correct"]
    assert "rows_off" in caught(sound["checks"], res["checks"])


@pytest.mark.parametrize("rows", ["every row", "one row of one app"])
def test_altered_estimate_is_caught(sound, fresh_programs, monkeypatch,
                                    rows):
    """Estimates altered where they are produced: every (app, config)
    row, or one row, a seventh of one app's rows and fewer of the
    window's."""
    def wrap(stage):
        def altered(cpi, valid, weights, truth):
            est, _ = stage(cpi, valid, weights, truth)
            est = est * 1.001 if rows == "every row" \
                else est.at[0, 1].multiply(1.001)
            return est, 100.0 * jnp.abs(est - truth) / truth
        return altered

    _patch_estimate(monkeypatch, wrap)
    res = run_once()
    assert not res["correct"]
    assert "rows_off" in caught(sound["checks"], res["checks"])


def test_bfloat16_control_is_caught():
    """The reference in the program's place, one precision step down."""
    cell = small_cell()
    config, params = cell["config"], cell["workload"]["params"]
    drv = registry.driver("sweeps")
    ctx = dict(config=config, params=params, seed=0,
               devices=jax.devices()[:1], clock=time.perf_counter)
    state = drv.setup(ctx)
    drv.warm(state)
    outputs = [drv.request(state, 13, i)["out"] for i in range(24)]
    prog = drv.extract(state)
    ref = bank.reference_build(config, prog["apps"])
    limits = params["limits"]
    sound_g = drv.readings(config, params, prog, ref, outputs, 13)
    control_g = drv.readings(config, params, prog, ref, outputs, 13,
                             dtype=jnp.bfloat16)
    over = [k for k, v in control_g.items() if v > limits[k]]
    assert over, control_g
    assert all(sound_g[k] <= limits[k] for k in over)
