"""SamplingPlan API tests: string-shim parity, registry round-trips,
plug-in extensibility (RankedSetUnit), and the jitted on-device
sweep-estimation contract.

The parity suite is the acceptance bar of the plan redesign: every
legacy ``(scheme, policy)`` string pair must produce a *bitwise
identical* ``ResultsTable`` through the deprecated shim and through the
explicit ``SamplingPlan`` spelling — the shim constructs the equivalent
plan, so both run the same code path.
"""

import dataclasses
import warnings

import numpy as np
import pytest

import repro.core.sampling.plan as plan_mod
from repro.core.sampling import (Centroid, DaleniusGurney, RandomUnit,
                                 RankedSetUnit, RFVClusters, SamplingPlan,
                                 StratumMean, TwoPhaseFlow)
from repro.experiments import (ExperimentEngine, ResultsTable, SweepRow,
                               SweepSpec, TrialSpec, plan_selection,
                               run_sweep, run_trials, trial_uniforms)

APP = "505.mcf_r"       # smallest population: fast to build

LEGACY_SCHEMES = ("bbv", "rfv", "dg")
LEGACY_POLICIES = ("centroid", "mean", "random")


@pytest.fixture(scope="module")
def engine():
    eng = ExperimentEngine()
    eng.app(APP)
    return eng


# ------------------------------------------------- string-vs-plan parity
@pytest.mark.parametrize("scheme", LEGACY_SCHEMES)
@pytest.mark.parametrize("policy", LEGACY_POLICIES)
def test_legacy_strings_bitwise_equal_plan(engine, scheme, policy):
    """Every legacy (scheme, policy) pair == its plan via the shim,
    row-for-row bitwise (same floats, same labels)."""
    with pytest.warns(DeprecationWarning, match="deprecated"):
        legacy = SweepSpec(apps=(APP,), scheme=scheme, policy=policy,
                           config_indices=(0, 6), selection_seed=11)
    modern = SweepSpec(apps=(APP,),
                       plan=SamplingPlan.from_strings(scheme, policy),
                       config_indices=(0, 6), selection_seed=11)
    t_legacy = run_sweep(engine, legacy)
    t_modern = run_sweep(engine, modern)
    assert t_legacy.rows == t_modern.rows       # SweepRow dataclass eq
    assert all(r.scheme == scheme for r in t_modern.rows)


def test_scheme_selection_shim_warns_and_matches(engine):
    from repro.experiments import scheme_selection
    exp = engine.app(APP)
    with pytest.warns(DeprecationWarning, match="scheme_selection"):
        sel_a, w_a = scheme_selection(exp, "rfv", "centroid")
    sel_b, w_b = plan_selection(exp, SamplingPlan(RFVClusters(), Centroid()))
    np.testing.assert_array_equal(w_a, w_b)
    for a, b in zip(sel_a, sel_b):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------- registry round-trips
def test_registry_round_trip():
    assert set(LEGACY_SCHEMES) <= set(plan_mod.registered_stratifiers())
    assert {"centroid", "mean", "random", "ranked_set"} \
        <= set(plan_mod.registered_policies())
    plan = SamplingPlan.from_strings("dg", "mean")
    assert isinstance(plan.stratifier, DaleniusGurney)
    assert isinstance(plan.policy, StratumMean)
    assert plan.scheme == "dg" and plan.policy_name == "mean"
    # "cpi" is the historic TwoPhaseFlow alias for the same design: it
    # resolves, but is NOT a second scheme name (no separate PRNG
    # fold-in, no separate row label)
    assert isinstance(plan_mod.make_stratifier("cpi"), DaleniusGurney)
    assert "cpi" not in plan_mod.registered_stratifiers()
    with pytest.raises(ValueError, match="unknown trial scheme"):
        TrialSpec(schemes=("cpi",))
    with pytest.warns(DeprecationWarning):
        spec = SweepSpec(apps=(APP,), scheme="cpi")
    assert spec.scheme == "dg"               # label normalized


def test_registry_unknown_names_raise_with_listing():
    with pytest.raises(ValueError, match="unknown stratifier.*registered"):
        plan_mod.make_stratifier("bogus")
    with pytest.raises(ValueError, match="unknown selection policy"):
        plan_mod.make_policy("bogus")


def test_make_stratifier_filters_params():
    """Shims pass one kwargs superset; factories take only their fields."""
    s = plan_mod.make_stratifier("rfv", num_strata=7, seed=3,
                                 backend="jnp", per_stratum=4)
    assert s == RFVClusters(num_strata=7, seed=3, backend="jnp")
    p = plan_mod.make_policy("random", per_stratum=4, num_strata=7)
    assert p == RandomUnit(per_stratum=4)


def test_spec_validation_at_construction():
    with pytest.raises(ValueError, match="unknown stratifier"):
        SweepSpec(apps=(APP,), scheme="bogus")
    with pytest.raises(ValueError, match="unknown selection policy"):
        SweepSpec(apps=(APP,), scheme="rfv", policy="bogus")
    with pytest.raises(ValueError, match="no selection policy"):
        SweepSpec(apps=(APP,), scheme="srs", policy="centroid")
    with pytest.raises(ValueError, match="unknown trial scheme"):
        TrialSpec(schemes=("random", "bogus"))
    # stale strings alongside plan= must not be silently relabeled
    with pytest.raises(ValueError, match="conflict with plan"):
        SweepSpec(apps=(APP,), scheme="bbv",
                  plan=SamplingPlan(RFVClusters(), Centroid()))
    # matching strings (or the defaults) are fine
    spec = SweepSpec(apps=(APP,), scheme="rfv", policy="centroid",
                     plan=SamplingPlan(RFVClusters(), Centroid()))
    assert spec.scheme == "rfv"


def test_plans_are_static_pytrees():
    import jax
    plan = SamplingPlan(RFVClusters(), RandomUnit(per_stratum=2))
    leaves = jax.tree_util.tree_leaves(plan)
    assert leaves == []                      # hyperparameters, not data
    back = jax.tree_util.tree_map(lambda x: x, plan)
    assert back == plan


# ------------------------------------------------- plug-in extensibility
def test_ranked_set_unit_runs_through_sweep(engine):
    """The in-repo order-statistic policy reaches run_sweep purely via
    the registry — no engine/sweep edits — and its picks match a numpy
    rank-within-stratum reference."""
    spec = SweepSpec(
        apps=(APP,), plan=SamplingPlan.from_strings("rfv", "ranked_set"),
        config_indices=(6,))
    assert spec.policy == "ranked_set"       # row label from the plan
    table = run_sweep(engine, spec)
    assert len(table) == 1
    assert np.isfinite(table.rows[0].estimate)

    exp = engine.app(APP)
    sel, _ = plan_selection(exp, SamplingPlan(RFVClusters(),
                                              RankedSetUnit()))
    base = exp.cpi0_1
    for h in range(exp.num_strata):
        members = np.flatnonzero(exp.rfv_labels == h)
        if members.size == 0:
            assert sel[h].size == 0
            continue
        ranked = members[np.argsort(base[members], kind="stable")]
        median = ranked[int(round(0.5 * (members.size - 1)))]
        assert sel[h][0] == exp.idx1[median], h


def test_plugin_policy_via_registry_only(engine):
    """A policy defined against plan.py alone (no engine imports) plugs
    into the batched selection path."""

    @dataclasses.dataclass(frozen=True)
    class FirstUnit(plan_mod.SelectionPolicy):
        """Deterministic reference plug-in: lowest-index member unit."""

        name = "first_unit"

        def __call__(self, ctx):
            # offsets point at each stratum's first member in index order
            pos = np.minimum(ctx.offsets, max(ctx.order.shape[1] - 1, 0))
            return np.take_along_axis(ctx.order, pos, axis=1)

    plan_mod.register_policy("first_unit", FirstUnit)
    try:
        exp = engine.app(APP)
        sel, _ = plan_selection(
            exp, SamplingPlan.from_strings("dg", "first_unit"))
        for h in range(exp.num_strata):
            members = np.flatnonzero(exp.dg_labels == h)
            if members.size:
                assert sel[h][0] == exp.idx1[members.min()], h
            else:
                assert sel[h].size == 0
    finally:
        plan_mod._POLICIES.pop("first_unit", None)


def test_plugin_stratifier_runs_trials(engine):
    """A registered stratifier plug-in is a valid TrialSpec scheme with
    draws independent of the canonical schemes'."""

    @dataclasses.dataclass(frozen=True)
    class RFVAgain(RFVClusters):
        """Plug-in reusing the engine's RFV artifacts under a new name."""

        name = "rfv2"

    plan_mod.register_stratifier("rfv2", RFVAgain)
    try:
        spec = TrialSpec(trials=8, schemes=("rfv", "rfv2"), config_index=6)
        res = run_trials(engine, spec, apps=(APP,))
        assert res.estimates["rfv2"].shape == (1, 8)
        # same stratification, different fold-in position => new draws
        u1 = trial_uniforms(spec, "rfv", 1, 20)
        u2 = trial_uniforms(spec, "rfv2", 1, 20)
        assert not np.allclose(u1, u2)
        assert plan_mod.trial_scheme_index("rfv2", ("random", "bbv", "rfv",
                                                    "dg")) >= 4
    finally:
        plan_mod._STRATIFIERS.pop("rfv2", None)


# ------------------------------------------------- on-device estimation
def test_sweep_estimates_dispatch_marker_and_parity(engine):
    """Stratified sweep estimates come from the jitted StratumTables
    program (dispatch marker set, correct lane geometry) and equal the
    host-numpy weighted-mean reference."""
    from repro.experiments.engine import plan_selection_bank

    plan_mod._reset_sweep_dispatch()
    assert plan_mod.last_sweep_dispatch() is None
    plan = SamplingPlan(RFVClusters(), Centroid())
    table = run_sweep(engine, SweepSpec(apps=(APP,), plan=plan,
                                        config_indices=(0, 3, 6)))
    marker = plan_mod.last_sweep_dispatch()
    assert marker is not None, "no on-device sweep estimation dispatched"
    assert marker["batch_shape"] == (1, 3)
    assert marker["num_strata"] == engine.num_strata

    exp = engine.app(APP)
    picks, valid, weights = plan_selection_bank([exp], plan)
    cpi = exp.cpi_for(picks[0], config_indices=(0, 3, 6))   # (3, L)
    w = np.where(valid[0], weights[0], 0.0)
    ref = (cpi * w[None, :]).sum(axis=1) / w.sum()
    np.testing.assert_allclose(table.column("estimate"), ref, rtol=1e-9)


@pytest.mark.parametrize("a_n,c_n", [(10, 7), (2048, 64)],
                         ids=["paper_10x7", "service_2048x64"])
def test_sweep_estimates_match_host_reduction(a_n, c_n):
    """The jitted sweep estimation equals the float64 host reduction
    (covered-weight renormalized weighted mean, percent error) on
    synthetic lanes with invalid picks, at the paper's matrix and at a
    service-scale batch."""
    from repro.core.sampling import WeightedPoint

    rng = np.random.default_rng(1)
    cpi = rng.normal(2.0, 0.6, (a_n, c_n, 20))
    valid = rng.random((a_n, 20)) > 0.1
    valid[:, 0] = True                        # no fully-empty app lanes
    weights = rng.random((a_n, 20))
    weights /= weights.sum(axis=1, keepdims=True)
    truth = rng.normal(2.0, 0.1, (a_n, c_n))

    est, err = WeightedPoint().sweep_estimates(cpi, valid, weights, truth)
    w = np.where(valid, weights, 0.0)
    ref = (cpi * w[:, None, :]).sum(axis=2) / w.sum(axis=1)[:, None]
    np.testing.assert_allclose(est, ref, rtol=1e-6)
    np.testing.assert_allclose(err, 100.0 * np.abs(ref - truth) / truth,
                               rtol=1e-6)
    assert plan_mod.last_sweep_dispatch()["batch_shape"] == (a_n, c_n)


def test_srs_sweep_has_no_plan_and_no_marker(engine):
    plan_mod._reset_sweep_dispatch()
    spec = SweepSpec(apps=(APP,), scheme="srs", config_indices=(0,))
    assert spec.plan is None
    run_sweep(engine, spec)
    assert plan_mod.last_sweep_dispatch() is None


# ------------------------------------------------- ResultsTable.matrix
def test_matrix_respects_spec_config_order():
    rows = [SweepRow(app="a", scheme="rfv", config_index=c,
                     estimate=float(c), truth=1.0, err_pct=0.0, n_units=1)
            for c in (6, 0, 3)]
    mat = ResultsTable(rows).matrix("estimate")
    # first-appearance order (6, 0, 3) — NOT sorted (0, 3, 6)
    np.testing.assert_array_equal(mat[:, 0], [6.0, 0.0, 3.0])


# ------------------------------------------------- TwoPhaseFlow shims
@pytest.fixture(scope="module")
def flow_inputs():
    rng = np.random.default_rng(5)
    y0 = rng.normal(2.0, 0.7, 240)
    feats = y0[:, None] + rng.normal(0.0, 0.1, (240, 4))
    idx1 = np.arange(240)
    return idx1, y0, feats


def test_flow_stratify_string_shim_matches_object(flow_inputs):
    idx1, y0, feats = flow_inputs
    flow = TwoPhaseFlow(population_size=1000,
                        rng=np.random.default_rng(0))
    with pytest.warns(DeprecationWarning, match="stratify"):
        legacy = flow.stratify(idx1, y0, feats, num_strata=6, scheme="rfv",
                               seed=3)
    modern = flow.stratify(idx1, y0, feats,
                           scheme=RFVClusters(num_strata=6, seed=3))
    np.testing.assert_array_equal(legacy.labels, modern.labels)
    np.testing.assert_allclose(legacy.centroids, modern.centroids)
    assert legacy.scheme == modern.scheme == "rfv"
    # the historic "cpi" name still resolves (to DaleniusGurney)
    with pytest.warns(DeprecationWarning):
        dg = flow.stratify(idx1, y0, None, num_strata=6, scheme="cpi")
    assert dg.scheme == "dg"
    # keywords conflicting with a Stratifier OBJECT raise, not ignore
    with pytest.raises(ValueError, match="conflicts with the Stratifier"):
        flow.stratify(idx1, y0, feats, scheme=RFVClusters(num_strata=6),
                      num_strata=30)
    with pytest.raises(ValueError, match="conflicts with the Stratifier"):
        flow.stratify(idx1, y0, feats, scheme=RFVClusters(num_strata=6),
                      kmeans_backend="np")
    # matching keywords are fine
    ok = flow.stratify(idx1, y0, feats,
                       scheme=RFVClusters(num_strata=6, seed=3),
                       num_strata=6, seed=3)
    np.testing.assert_array_equal(ok.labels, modern.labels)


def test_flow_select_string_shim_matches_object(flow_inputs):
    idx1, y0, feats = flow_inputs
    flow = TwoPhaseFlow(population_size=1000,
                        rng=np.random.default_rng(0))
    strat = flow.stratify(idx1, y0, feats,
                          scheme=RFVClusters(num_strata=6, seed=3))
    for policy_name, policy in (("centroid", Centroid()),
                                ("mean", StratumMean()),
                                ("random", RandomUnit())):
        with pytest.warns(DeprecationWarning, match="select"):
            legacy = flow.select(strat, policy=policy_name, seed=9)
        modern = flow.select(strat, policy=policy, seed=9)
        assert len(legacy) == len(modern)
        for a, b in zip(legacy, modern):
            np.testing.assert_array_equal(a, b)
    # per_stratum forwards through the string shim (RandomUnit field)
    with pytest.warns(DeprecationWarning):
        multi = flow.select(strat, policy="random", per_stratum=3, seed=9)
    assert max(s.size for s in multi) == 3
    # ... and overrides a policy OBJECT's own configuration too
    multi_obj = flow.select(strat, policy=RandomUnit(), per_stratum=3,
                            seed=9)
    for a, b in zip(multi, multi_obj):
        np.testing.assert_array_equal(a, b)
    # one-unit-only policies refuse a multi-unit request loudly
    with pytest.raises(NotImplementedError, match="one unit per stratum"):
        flow.select(strat, policy=RankedSetUnit(), per_stratum=2)


def test_trials_pool_kind_and_stratifier_instance(engine):
    """A stratifier's declared pool_kind drives trial cost semantics,
    and run_sweep's trial study uses the plan's configured stratifier
    instance (not a default-constructed registry copy)."""
    from repro.experiments import run_sweep

    resolved = []

    @dataclasses.dataclass(frozen=True)
    class FreeRFV(RFVClusters):
        """RFV labels over the phase-1 pool, census-valued (free)."""

        name = "rfvfree"
        pool_kind = "census"

        def resolve(self, exps):
            resolved.append(self)
            return super().resolve(exps)

    plan_mod.register_stratifier("rfvfree", FreeRFV)
    try:
        exp = engine.app(APP)
        before = exp.sim.ledger.regions_simulated
        run_trials(engine, TrialSpec(trials=4, schemes=("rfvfree",),
                                     config_index=4), apps=(APP,))
        # census-kind pool: analysis-only, nothing charged
        assert exp.sim.ledger.regions_simulated == before
        # run_sweep threads ITS stratifier instance into the MC study
        configured = FreeRFV(seed=1)
        resolved.clear()
        run_sweep(engine, SweepSpec(
            apps=(APP,), plan=SamplingPlan(configured, Centroid()),
            config_indices=(4,),
            trials=TrialSpec(trials=4, config_index=4)))
        assert any(s is configured for s in resolved)
    finally:
        plan_mod._STRATIFIERS.pop("rfvfree", None)


def test_ranked_set_select_local_via_flow(flow_inputs):
    idx1, y0, feats = flow_inputs
    flow = TwoPhaseFlow(population_size=1000,
                        rng=np.random.default_rng(0))
    strat = flow.stratify(idx1, y0, None,
                          scheme=DaleniusGurney(num_strata=5))
    picked = flow.select(strat, policy=RankedSetUnit(rank_fraction=1.0))
    for h in range(5):
        members = np.flatnonzero(strat.labels == h)
        if members.size:
            top = members[np.argmax(y0[members])]
            assert picked[h][0] == idx1[top]


def test_deprecated_warning_is_not_an_error_path(engine):
    """The shims must stay fully functional: a legacy spec drives a
    complete sweep with trials attached."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        spec = SweepSpec(apps=(APP,), scheme="dg", policy="random",
                         config_indices=(6,),
                         trials=TrialSpec(trials=8, config_index=6))
    table = run_sweep(engine, spec)
    assert table.rows[0].p95_err_pct is not None
