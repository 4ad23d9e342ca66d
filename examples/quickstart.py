"""Quickstart: the paper's two-phase stratified sampling flow, end to end.

Runs the recommended methodology (paper Fig. 14) on one synthetic SPECint
application through the app-sharded experiment engine and prints every
artifact: the phase-1 estimate, the strata, the 20-region day-to-day
estimate, its error vs ground truth, a collapsed-strata confidence
interval from those same 20 runs, and a Monte-Carlo check of the whole
scheme (``run_trials``: 200 vmapped selection trials in one dispatch).

Every simulation goes through the engine's shared ``MemoBank``: a region
is *charged* once per configuration, so re-measuring regions the flow
already paid for (e.g. re-reading phase-1 results) costs nothing — the
ledger matches the paper's "number of region simulations" cost unit
exactly.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core.sampling import (Centroid, RFVClusters, SamplingPlan,
                                 Stratification, TwoPhaseFlow, srs_estimate)
from repro.experiments import (ExperimentEngine, TrialSpec, plan_selection,
                               run_trials)
from repro.runtime.compile_cache import enable_compile_cache

APP = "502.gcc_r"          # the paper's hardest application
NUM_STRATA = 20


def main() -> None:
    enable_compile_cache()
    engine = ExperimentEngine()
    # ONE stacked build: census truth, phase-1 SRS, BBV/RFV/DG strata.
    # (Add more app names — or mesh=make_app_mesh() — and the same call
    # builds them all batched over the app axis.)
    exp = engine.app(APP)
    ledger = exp.sim.ledger
    true0, true6 = float(exp.truth[0]), float(exp.truth[6])

    # Step 1 — initial characterization: large SRS on the baseline config
    # (measured — and charged — during the engine build).
    est1 = srs_estimate(exp.cpi0_1)
    print(f"[1] phase-1: n={exp.idx1.size} regions,  "
          f"CPI = {est1.mean:.3f} ± {est1.margin_pct:.2f}%  "
          f"(true {true0:.3f})")

    # Steps 2+3 — stratify on RFVs, pick centroids: one SamplingPlan.
    plan = SamplingPlan(stratifier=RFVClusters(), policy=Centroid())
    selected, weights = plan_selection(exp, plan)
    print(f"[2] stratified into {exp.num_strata} strata, "
          f"weights {np.round(np.sort(weights)[-3:], 3)} (top 3)")

    # Step 3 self-check: estimate the baseline from the 20 regions. These
    # were already simulated on config 0 in phase 1, so the memo bank
    # serves them for free — watch the ledger stand still.
    before = ledger.regions_simulated
    est0 = float(exp.weighted_cpi_all(selected, weights,
                                      config_indices=(0,))[0])
    err0 = 100 * abs(est0 - true0) / true0
    print(f"[3] 20-region estimate of baseline: {est0:.3f} "
          f"(error {err0:.2f}% vs phase-1/census; "
          f"{ledger.regions_simulated - before} new simulations — "
          "cache hits are free)")

    # Step 4a — day-to-day study of a NEW configuration (Config 6).
    before = ledger.regions_simulated
    est6 = float(exp.weighted_cpi_all(selected, weights,
                                      config_indices=(6,))[0])
    cost = ledger.regions_simulated - before
    print(f"[4a] Config-6 estimate from {cost} simulations: {est6:.3f} "
          f"(true {true6:.3f}, error {100*abs(est6-true6)/true6:.2f}%)")

    # ... with a practical CI from the same 20 runs (collapsed strata).
    # Empty strata (possible for some app/seed pairs) are dropped from
    # values, weights, and ordering consistently, weights renormalized.
    # Config 6 for these regions is now memoized: zero additional cost.
    from repro.core.sampling import collapsed_strata_estimate
    from repro.simcpu import CONFIGS
    before = ledger.regions_simulated
    occupied = [h for h, s in enumerate(selected) if s.size]
    y_h = np.array([float(exp.sim.simulate_cpi(selected[h], CONFIGS[6])[0])
                    for h in occupied])
    w_h = weights[occupied] / weights[occupied].sum()
    order = np.array([exp.cpi0_1[exp.rfv_labels == h].mean()
                      for h in occupied])
    ci = collapsed_strata_estimate(y_h, w_h, order_by=order)
    print(f"     collapsed-strata 95% CI: ±{ci.margin_pct:.1f}%  "
          f"covers truth: {ci.covers(true6)}  "
          f"({ledger.regions_simulated - before} new simulations)")

    # Step 4b — periodic multi-unit CI check (tight, ~10x cheaper than SRS).
    # The flow's CI machinery runs directly off the engine's artifacts
    # (it collapses under-sampled strata itself).
    strat = Stratification(
        labels=exp.rfv_labels, weights=weights,
        centroids=exp.rfv_centroids, features=exp.rfv_z,
        phase1_indices=exp.idx1, phase1_baseline_y=exp.cpi0_1, scheme="rfv")
    flow = TwoPhaseFlow(population_size=exp.sim.pop.n_regions,
                        rng=np.random.default_rng(0))
    before = ledger.regions_simulated
    est_ci = flow.ci_check(strat,
                           lambda i: exp.sim.simulate_cpi(i, CONFIGS[6]),
                           per_stratum_sizes=np.full(NUM_STRATA, 8))
    cost = ledger.regions_simulated - before
    print(f"[4b] CI-check from {cost} simulations: {est_ci.mean:.3f} "
          f"± {est_ci.margin_pct:.2f}%  covers truth: "
          f"{est_ci.covers(true6)}")

    print(f"total simulation budget spent: {ledger.regions_simulated} "
          f"regions ({ledger.instructions_simulated/1e9:.1f} B instructions; "
          f"{exp.sim.hits} cache hits avoided re-simulation)")

    # Step 5 — Monte-Carlo sanity check of the whole design: 200 random-
    # selection trials per scheme folded into vmapped (trial, stratum)
    # axes — ONE dispatch per scheme, no Python trial loops. (The rfv/dg
    # pools re-measure the phase-1 sample on Config 6, charged once.)
    before = ledger.regions_simulated
    mc = run_trials(engine, TrialSpec(trials=200), apps=(APP,))
    p95 = {s: float(mc.p95(s)[0]) for s in mc.errors}
    print(f"[5] Monte-Carlo p95 |error| over 200 trials "
          f"(+{ledger.regions_simulated - before} simulations):  "
          f"random {p95['random']:.1f}%  bbv {p95['bbv']:.1f}%  "
          f"rfv {p95['rfv']:.1f}%  dg {p95['dg']:.1f}%")


if __name__ == "__main__":
    main()
