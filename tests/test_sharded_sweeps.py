"""App-sharded sweep engine tests: stacked populations, memo-bank merge,
vmapped Monte-Carlo trials, and sharded-vs-single-host equivalence.

The sharded tests need forced host devices, e.g.:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_sharded_sweeps.py

(scripts/ci.sh runs a CI_FORCE_DEVICES=8 matrix leg); on a single device
they skip and the single-device equivalence/reference tests still run.
"""

import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.core.clustering import kmeans_bank
from repro.core.sampling import (Centroid, DaleniusGurney, RandomUnit,
                                 RFVClusters, SamplingPlan)
from repro.experiments import (ExperimentEngine, SweepSpec, TrialSpec,
                               plan_selection, run_sweep, run_trials,
                               trial_uniforms)
from repro.simcpu import (CONFIGS, MemoBank, cpi_bank, evaluate_regions,
                          get_population_bank, make_cached_simulator)

APP = "505.mcf_r"
APPS2 = ("505.mcf_r", "520.omnetpp_r")

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


# ------------------------------------------------ stacked population bank
def test_population_bank_stacks_and_masks():
    bank = get_population_bank(APPS2)
    assert bank.features.shape[0] == 2
    assert bank.features.shape[2] == bank.pops[0].features.shape[1]
    for a, pop in enumerate(bank.pops):
        n = pop.n_regions
        assert bank.n_regions[a] == n
        assert bank.mask[a, :n].all() and not bank.mask[a, n:].any()
        np.testing.assert_allclose(bank.features[a, :n],
                                   pop.features.astype(np.float32))


def test_cpi_bank_matches_per_app_eval():
    bank = get_population_bank(APPS2)
    mat = cpi_bank(bank.features, CONFIGS[:3])          # (A, 3, N)
    for a, pop in enumerate(bank.pops):
        n = pop.n_regions
        for c in range(3):
            ref = evaluate_regions(pop.features, CONFIGS[c])["cpi"]
            np.testing.assert_allclose(mat[a, c, :n], ref,
                                       rtol=1e-5, atol=1e-6)


def test_kmeans_bank_padding_invariance():
    """Zero-weight padding rows change nothing for the real rows."""
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(3.0 * i, 0.3, (50, 4))
                        for i in range(3)]).astype(np.float32)
    plain = kmeans_bank(x[None], 3, weights=np.ones((1, x.shape[0])), seed=1)
    padded_x = np.concatenate([x, np.zeros((37, 4), np.float32)])[None]
    padded_w = np.concatenate([np.ones(x.shape[0]), np.zeros(37)])[None]
    padded = kmeans_bank(padded_x, 3, weights=padded_w, seed=1)
    np.testing.assert_array_equal(plain.labels[0],
                                  padded.labels[0, :x.shape[0]])
    np.testing.assert_allclose(plain.centroids[0], padded.centroids[0],
                               rtol=1e-6, atol=1e-7)


# ------------------------------------------------ memo bank merge
def test_memo_bank_merge_values_and_charges():
    a = make_cached_simulator(APP)
    b = make_cached_simulator(APP)
    a.simulate_cpi(np.arange(10), CONFIGS[0])
    b.simulate_cpi(np.arange(5, 15), CONFIGS[0])        # 5-region overlap
    a.bank.merge(b.bank)
    row, col = 0, 0
    assert a.bank.mask[row, col, :15].all()
    # both devices paid for their own misses: 10 + 10, overlap included
    assert a.bank.charges[row, col] == 20
    assert a.ledger.regions_simulated == 20
    served = a.simulate_cpi(np.arange(15), CONFIGS[0])
    assert a.ledger.regions_simulated == 20             # all hits post-merge
    np.testing.assert_allclose(
        served, evaluate_regions(a.pop.features, CONFIGS[0],
                                 np.arange(15))["cpi"], rtol=1e-5, atol=1e-6)


def test_memo_bank_merge_app_partition_equals_single_host():
    """Disjoint app partitions merge to the same totals as one shared bank."""
    shared = ExperimentEngine()
    shared.build(APPS2)
    parts = [ExperimentEngine(), ExperimentEngine()]
    parts[0].app(APPS2[0])
    parts[1].app(APPS2[1])
    merged = MemoBank()
    merged.merge(parts[0].memo)
    merged.merge(parts[1].memo)
    assert merged.total_charges() == shared.memo.total_charges()
    assert sorted(merged.names) == sorted(shared.memo.names)


# ------------------------------------------------ Monte-Carlo trials
@pytest.fixture(scope="module")
def engine():
    eng = ExperimentEngine()
    eng.app(APP)
    return eng


def test_run_trials_matches_numpy_loop(engine):
    """run_trials == a per-trial/per-stratum numpy loop on the same seeds."""
    spec = TrialSpec(trials=32, seed=3, config_index=6)
    res = run_trials(engine, spec, apps=(APP,))
    exp = engine.app(APP)
    truth = float(exp.truth[6])

    # SRS scheme: n-unit draws from the census pool
    census = exp.census(6)
    n = np.float32(census.size)
    u = trial_uniforms(spec, "random", 1, spec.units_per_trial)[0]
    for t in range(spec.trials):
        idx = np.minimum((u[t] * n).astype(np.int32), census.size - 1)
        est = census[idx].mean()
        assert res.estimates["random"][0, t] == pytest.approx(est, rel=1e-5)
        assert res.errors["random"][0, t] == pytest.approx(
            100 * abs(est - truth) / truth, rel=1e-4)

    # stratified schemes: one unit per non-empty stratum, weighted sum
    pools = {"bbv": (exp.bbv_labels, exp.bbv_weights, census),
             "rfv": (exp.rfv_labels, exp.rfv_weights, exp.cpi(6, exp.idx1)),
             "dg": (exp.dg_labels, exp.dg_weights, exp.cpi(6, exp.idx1))}
    for scheme, (labels, weights, pool) in pools.items():
        u = trial_uniforms(spec, scheme, 1, exp.num_strata)[0]
        members = [np.flatnonzero(labels == h) for h in range(exp.num_strata)]
        for t in range(0, spec.trials, 7):
            est = 0.0
            for h, m in enumerate(members):
                if m.size == 0:
                    continue
                pick = min(int(np.float32(u[t, h]) * np.float32(m.size)),
                           m.size - 1)
                est += weights[h] * pool[m[pick]]
            assert res.estimates[scheme][0, t] == pytest.approx(
                est, rel=1e-5), (scheme, t)


def test_run_trials_charges_phase1_pool_once(engine):
    exp = engine.app(APP)
    before = exp.sim.ledger.regions_simulated
    run_trials(engine, TrialSpec(trials=8, config_index=5), apps=(APP,))
    # rfv/dg pools re-measure the phase-1 sample on config 5: charged once
    assert exp.sim.ledger.regions_simulated - before == exp.idx1.size
    run_trials(engine, TrialSpec(trials=16, config_index=5), apps=(APP,))
    assert exp.sim.ledger.regions_simulated - before == exp.idx1.size


def test_sweep_spec_trials_plumbing(engine):
    table = run_sweep(engine, SweepSpec(
        apps=(APP,), scheme="rfv", config_indices=(0, 6),
        trials=TrialSpec(trials=16, config_index=6)))
    by_cfg = {r.config_index: r for r in table}
    assert by_cfg[6].p95_err_pct is not None
    assert by_cfg[0].p95_err_pct is None
    # the CI-claim bridge columns ride along at the trial config
    assert by_cfg[6].ci_half_pct is not None and by_cfg[6].ci_half_pct > 0
    assert by_cfg[6].coverage is not None
    assert 0.0 <= by_cfg[6].coverage <= 1.0
    assert by_cfg[0].ci_half_pct is None and by_cfg[0].coverage is None
    hdr = table.to_csv().splitlines()[0]
    for col in ("p95_err_pct", "ci_half_pct", "coverage"):
        assert col in hdr


def test_run_trials_ci_matches_collapsed_reference(engine):
    """Per-trial CI half-widths == a hand-built collapsed-pairs reference
    (eq. 4 over occupied strata in baseline-CPI order), and coverage is
    the fraction of trials whose CI contains the truth."""
    from repro.core.sampling.types import critical_value

    spec = TrialSpec(trials=16, seed=3, config_index=6)
    res = run_trials(engine, spec, apps=(APP,))
    exp = engine.app(APP)
    truth = float(exp.truth[6])

    labels, weights = exp.dg_labels, exp.dg_weights
    pool = exp.cpi(6, exp.idx1)
    baseline = exp.cpi0_1.astype(np.float32)
    L = exp.num_strata
    members = [np.flatnonzero(labels == h) for h in range(L)]
    occ = [h for h in range(L) if members[h].size]
    key = np.array([baseline[members[h]].mean() if members[h].size
                    else np.inf for h in range(L)], np.float32)
    order = [h for h in np.argsort(key, kind="stable") if members[h].size]
    v_cnt = len(occ)
    df = v_cnt - v_cnt // 2
    crit = critical_value(spec.confidence, float(df))

    u = trial_uniforms(spec, "dg", 1, L)[0]
    for t in range(0, spec.trials, 5):
        y = {}
        for h in occ:
            m = members[h]
            pick = min(int(np.float32(u[t, h]) * np.float32(m.size)),
                       m.size - 1)
            y[h] = float(pool[m[pick]])
        ys = [y[h] for h in order]
        ws = [float(weights[h]) for h in order]
        var = 0.0
        g_count = v_cnt // 2
        for j in range(g_count):
            tri = (v_cnt % 2 == 1) and (j == g_count - 1)
            idx = [2 * j, 2 * j + 1] + ([2 * j + 2] if tri else [])
            vals = np.array([ys[i] for i in idx])
            s2 = (vals[0] - vals[1]) ** 2 / 4.0 if not tri \
                else float(vals.var(ddof=1))
            var += sum(ws[i] ** 2 for i in idx) * s2
        half_ref = crit * np.sqrt(var)
        assert res.half_widths["dg"][0, t] == pytest.approx(
            half_ref, rel=2e-4), t
    # coverage is the empirical fraction of covering trials
    covers = (np.abs(res.estimates["dg"][0] - truth)
              <= res.half_widths["dg"][0])
    assert res.coverage["dg"][0] == pytest.approx(covers.mean(), abs=1e-6)
    # every scheme reports (A, T) half-widths and (A,) coverage in [0, 1]
    for scheme in spec.schemes:
        assert res.half_widths[scheme].shape == (1, spec.trials)
        assert 0.0 <= float(res.coverage[scheme][0]) <= 1.0


# ------------------------------------------------ satellite bug fixes
def test_weighted_cpi_all_empty_selection_contract(engine):
    exp = engine.app(APP)
    empty = [np.empty(0, np.int64)] * 4
    w = np.full(4, 0.25)
    with pytest.warns(UserWarning, match="every stratum selection is empty"):
        ests = exp.weighted_cpi_all(empty, w)
    assert ests.shape == (len(CONFIGS),)
    assert np.isnan(ests).all()
    with pytest.raises(ValueError, match="every stratum selection is empty"):
        exp.weighted_cpi_all(empty, w, strict=True)


def test_dg_selection_masks_empty_strata(engine):
    """Empty dg strata must yield empty selections — and no NaN anywhere
    in the centroid path (historically [nan] centroids leaked into the
    distance computation)."""
    exp = engine.app(APP)
    crafted = dataclasses.replace(
        exp, dg_labels=np.where(exp.dg_labels == 3, 0, exp.dg_labels),
        dg_weights=np.bincount(
            np.where(exp.dg_labels == 3, 0, exp.dg_labels),
            minlength=exp.num_strata) / exp.dg_labels.size)
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # NaN ops would warn
        sel, w = plan_selection(crafted,
                                SamplingPlan(DaleniusGurney(), Centroid()))
    assert sel[3].size == 0                  # masked out, not NaN-selected
    assert sum(s.size for s in sel) == exp.num_strata - 1
    assert np.isfinite(w).all()


def test_random_selection_with_trailing_empty_stratum(engine):
    """A trailing empty stratum puts its gather offset at the row width;
    the random policy must clamp, not IndexError."""
    exp = engine.app(APP)
    last = exp.num_strata - 1
    relabeled = np.where(exp.dg_labels == last, 0, exp.dg_labels)
    crafted = dataclasses.replace(
        exp, dg_labels=relabeled,
        dg_weights=np.bincount(relabeled, minlength=exp.num_strata)
        / relabeled.size)
    sel, w = plan_selection(crafted,
                            SamplingPlan(DaleniusGurney(), RandomUnit()),
                            seed=11)
    assert sel[last].size == 0
    assert sum(s.size for s in sel) == exp.num_strata - 1
    for h, s in enumerate(sel):
        if s.size:
            assert relabeled[np.flatnonzero(crafted.idx1 == s[0])[0]] == h


# ------------------------------------------------ sharded equivalence
@needs_devices
def test_sharded_engine_matches_single_host():
    from repro.launch.mesh import make_app_mesh
    single = ExperimentEngine()
    sharded = ExperimentEngine(mesh=make_app_mesh())
    spec = SweepSpec(apps=APPS2, scheme="rfv", policy="centroid")
    t1 = run_sweep(single, spec)
    t2 = run_sweep(sharded, spec)
    np.testing.assert_allclose(t1.column("estimate"), t2.column("estimate"),
                               rtol=1e-7)
    s1 = run_sweep(single, SweepSpec(apps=APPS2, scheme="srs"))
    s2 = run_sweep(sharded, SweepSpec(apps=APPS2, scheme="srs"))
    np.testing.assert_allclose(s1.column("estimate"), s2.column("estimate"),
                               rtol=1e-7)
    np.testing.assert_allclose(s1.column("margin_pct"),
                               s2.column("margin_pct"), rtol=1e-5)
    # identical Monte-Carlo draws -> identical trial estimates and CIs
    mc1 = run_trials(single, TrialSpec(trials=64), apps=APPS2)
    mc2 = run_trials(sharded, TrialSpec(trials=64), apps=APPS2)
    for scheme in mc1.errors:
        np.testing.assert_allclose(mc1.errors[scheme], mc2.errors[scheme],
                                   rtol=1e-6)
        np.testing.assert_allclose(mc1.half_widths[scheme],
                                   mc2.half_widths[scheme], rtol=1e-6)
        np.testing.assert_allclose(mc1.coverage[scheme],
                                   mc2.coverage[scheme], rtol=1e-6)
    # merged ledger totals equal single-host totals
    assert sharded.memo.total_charges() == single.memo.total_charges()
    for e1, e2 in zip(single.build(APPS2), sharded.build(APPS2)):
        assert e1.sim.ledger.regions_simulated == \
            e2.sim.ledger.regions_simulated



def _ledgers(engine):
    return [None if l is None else l.regions_simulated
            for l in engine.memo.ledgers]


def _app_mesh_engines(n):
    from repro.launch.mesh import make_app_mesh
    return [ExperimentEngine(mesh=make_app_mesh()) for _ in range(n)]


@needs_devices
def test_sharded_fused_sweep_matches_staged():
    """On the ("app",) mesh the fused megaprogram agrees with the staged
    chain: estimates to 1e-6, charge matrix and ledgers exactly."""
    fused, staged = _app_mesh_engines(2)
    spec = SweepSpec(apps=APPS2, plan=SamplingPlan(RFVClusters(), Centroid()))
    tf = run_sweep(fused, spec)
    ts = run_sweep(staged, dataclasses.replace(spec, fused=False))
    np.testing.assert_allclose(tf.column("estimate"), ts.column("estimate"),
                               rtol=1e-6)
    np.testing.assert_array_equal(fused.memo.charges, staged.memo.charges)
    assert _ledgers(fused) == _ledgers(staged)


@needs_devices
def test_sharded_coalesced_matches_serial_bitwise():
    """On the ("app",) mesh, eight coalesced same-shape sweeps equal
    eight serial ``run_sweep`` calls bitwise, ledgers included."""
    from repro.serving import run_coalesced_sweeps

    serial_eng, coal_eng = _app_mesh_engines(2)
    plan = SamplingPlan(RFVClusters(), RandomUnit())
    specs = [SweepSpec(apps=APPS2, plan=plan, config_indices=(0, 1, 2),
                       selection_seed=s) for s in range(8)]
    serial = [run_sweep(serial_eng, s) for s in specs]
    coal = run_coalesced_sweeps(coal_eng, specs)
    for st, ct in zip(serial, coal):
        for col in ("estimate", "err_pct", "truth", "n_units"):
            np.testing.assert_array_equal(
                np.asarray(st.column(col), float),
                np.asarray(ct.column(col), float))
    assert _ledgers(serial_eng) == _ledgers(coal_eng)


@needs_devices
def test_sharded_service_memo_cap_bounds_residency():
    """On the ("app",) mesh a capped, spilling service keeps resident
    memo columns at the cap after every tick and charges what an
    uncapped service charges."""
    from repro.serving import SweepService

    capped_eng, free_eng = _app_mesh_engines(2)
    plan = SamplingPlan(RFVClusters(), Centroid())
    specs = [SweepSpec(apps=APPS2, plan=plan, config_indices=cfg_is)
             for cfg_is in ((0, 1, 2), (3, 4, 5), (0, 1, 2))]
    capped = SweepService(capped_eng, memo_cap=2, spill=True)
    for spec in specs:
        capped.submit(spec)
        capped.tick()
        assert len(capped_eng.memo.resident_columns()) <= 2
    assert capped.stats().evicted_cols > 0
    free = SweepService(free_eng)
    for spec in specs:
        free.submit(spec)
    free.drain()
    assert _ledgers(capped_eng) == _ledgers(free_eng)


# ------------------------------------------------ mesh paths, always run
_MESH_CHILD = r"""
import numpy as np
from repro.core.sampling import Centroid, RFVClusters, SamplingPlan
from repro.experiments import (ExperimentEngine, SweepSpec, TrialSpec,
                               run_sweep, run_trials)
from repro.launch.mesh import make_app_mesh, make_app_trial_mesh

apps = ("505.mcf_r", "520.omnetpp_r")
single, sharded = ExperimentEngine(), ExperimentEngine(mesh=make_app_mesh())
spec = SweepSpec(apps=apps, plan=SamplingPlan(RFVClusters(), Centroid()))
t1, t4 = run_sweep(single, spec), run_sweep(sharded, spec)
np.testing.assert_array_equal(t1.column("estimate"), t4.column("estimate"))
assert single.memo.total_charges() == sharded.memo.total_charges()
# the trial mesh on the one-device engine's bank: the CPU perf model's
# census bits depend on how many apps share its dispatch
tspec = TrialSpec(trials=512, schemes=("random", "rfv"), keep_trials=True)
r1 = run_trials(single, tspec, apps=apps)
r4 = run_trials(single, tspec, apps=apps,
                mesh=make_app_trial_mesh(app_devices=2))
for s in tspec.schemes:
    for leaf in ("count", "cover", "half_n", "err_hist", "half_hist"):
        np.testing.assert_array_equal(getattr(r1.stats[s], leaf),
                                      getattr(r4.stats[s], leaf))
    for field in ("estimates", "errors", "half_widths"):
        np.testing.assert_array_equal(getattr(r1, field)[s],
                                      getattr(r4, field)[s])
print("mesh paths ok")
"""


def test_mesh_paths_on_four_cpu_devices():
    """The ("app",) sweep and ("app", "trial") trial paths against one
    device, in a child with four CPU devices — so a JAX API break in the
    mesh code fails tier-1 instead of skipping with the 8-device tests.
    The child stays on the CPU and never loads the TPU library."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   p for p in ("src", os.environ.get("PYTHONPATH")) if p))
    out = subprocess.run([sys.executable, "-c", _MESH_CHILD],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "mesh paths ok" in out.stdout
