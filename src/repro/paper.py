"""The paper's figures and tables, reproduced through the study engine.

One function per artifact: Figs. 1, 5 and 7-13, Table IV, the gcc
cluster-count case of §V.B.1 and two directions §VI.C proposes. Each
takes an ``ExperimentEngine`` and returns its numbers. ``CLAIMS`` are
the paper's seven headline results as predicates over those numbers;
``tests/test_paper_claims.py`` holds each of them.

``python -m repro.paper`` prints every figure's numbers as
``figure.key,value`` rows, then one ``claim_<name>,PASS|FAIL`` row per
claim, and exits non-zero when a claim fails. More than one device
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``) gives the
engine an ``("app",)`` mesh.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Callable

import numpy as np

from .core.clustering import Standardizer, kmeans
from .core.sampling import (Estimate, SamplingPlan, StratumSummary,
                            collapsed_strata_estimate,
                            phase2_sizes_for_margin, select_centroid,
                            srs_estimate)
from .experiments import (NUM_STRATA, ExperimentEngine, SweepSpec,
                          TrialSpec, plan_selection, run_sweep, run_trials)
from .simcpu import APP_NAMES, CONFIGS

__all__ = ["CLAIMS", "FIGURES", "Claim", "main"]

EMPIRICAL_TRIALS = 1000     # Fig. 8's Monte-Carlo trials per app and scheme


# ---------------------------------------------------------------- Fig 1/6
def cpi_distributions(engine: ExperimentEngine) -> dict:
    """Fig. 1 + Fig. 6: CPI dispersion per app; aggregating into longer
    regions (10M/100M instructions = means of 10/100 consecutive 1M
    regions) lowers it."""
    cv = {}
    for exp in engine.apps(APP_NAMES):
        cpi = exp.census(0)
        cvs = []
        for agg in (1, 10, 100):
            n = (cpi.shape[0] // agg) * agg
            c = cpi[:n].reshape(-1, agg).mean(axis=1)
            cvs.append(float(c.std() / c.mean()))
        cv[exp.name] = {"1M": cvs[0], "10M": cvs[1], "100M": cvs[2]}
    mono = sum(1 for v in cv.values() if v["1M"] >= v["10M"] >= v["100M"])
    return {"cv": cv, "monotone_apps": mono}


# ------------------------------------------------------------------ Fig 5
def config_sweep(engine: ExperimentEngine) -> dict:
    """Fig. 5: per-app IPC across Configs 0-6 from the phase-1 sample
    (one batched dispatch per app evaluates all seven configs)."""
    table = run_sweep(engine, SweepSpec(apps=tuple(APP_NAMES),
                                        scheme="srs"))
    ipc = {r.app: {} for r in table}
    for r in table:
        if r.config_index in (0, 6):
            ipc[r.app][f"cfg{r.config_index}"] = 1.0 / r.estimate
            ipc[r.app][f"margin_pct_cfg{r.config_index}"] = r.margin_pct
    geo = np.exp(np.log(1.0 / table.matrix("estimate")).mean(axis=1))
    return {"ipc": ipc, "geo0": float(geo[0]), "geo6": float(geo[6]),
            "speedup": float(geo[6] / geo[0])}


# ---------------------------------------------------------------- helpers
def _analytical_margin(exp, scheme: str, cfg_i: int) -> float:
    """95% margin (%) of one-unit-per-stratum stratified sampling from
    exact within-stratum variances (the census for BBV, phase 1 for
    RFV/DG); ``random`` is simple random sampling of 20 units."""
    if scheme == "random":
        cpi = exp.census(cfg_i)
        n = 20
        est = Estimate(mean=float(cpi.mean()),
                       variance=float(cpi.var(ddof=1)) / n, n=n,
                       df=float(n - 1))
        return est.margin_pct
    if scheme == "bbv":
        labels, weights = exp.bbv_labels, exp.bbv_weights
        cpi = exp.census(cfg_i)
    else:
        labels = exp.rfv_labels if scheme == "rfv" else exp.dg_labels
        weights = exp.rfv_weights if scheme == "rfv" else exp.dg_weights
        cpi = exp.cpi(cfg_i, exp.idx1)
    summ = []
    for h in range(NUM_STRATA):
        m = labels == h
        if m.sum() < 2:
            summ.append(StratumSummary(weight=float(weights[h]), n=2,
                                       mean=float(cpi[m].mean())
                                       if m.any() else 0.0, var=0.0))
            continue
        summ.append(StratumSummary(weight=float(weights[h]), n=1,
                                   mean=float(cpi[m].mean()),
                                   var=float(cpi[m].var(ddof=1))))
    # one unit per stratum: v(ybar) = sum W_h^2 s_h^2 (n_h = 1)
    var = sum(s.weight ** 2 * s.var for s in summ)
    mean = sum(s.weight * s.mean for s in summ)
    est = Estimate(mean=mean, variance=var, n=NUM_STRATA,
                   df=float(NUM_STRATA // 2))
    return est.margin_pct


def _max_err(exp, sel, weights) -> float:
    """Worst percent error over the seven configs of the weighted
    estimate from ``sel`` (one batched dispatch)."""
    ests = exp.weighted_cpi_all(sel, weights)
    return float((100 * np.abs(ests - exp.truth) / exp.truth).max())


def _stratify_phase1(exp, feats: np.ndarray) -> float:
    """Worst config error of centroid selection from L k-means strata
    of the phase-1 sample on ``feats`` (one row per phase-1 unit)."""
    _, z = Standardizer.fit_transform(feats)
    z = np.asarray(z)
    km = kmeans(z, NUM_STRATA, seed=0, restarts=2)
    w = np.bincount(km.labels, minlength=NUM_STRATA) / exp.idx1.size
    sel = [exp.idx1[s] for s in select_centroid(km.labels, z, km.centroids)]
    return _max_err(exp, sel, w)


# ------------------------------------------------------------------ Fig 7
def ci_analytical(engine: ExperimentEngine) -> dict:
    """Fig. 7: analytical 95% margins at n = 20 for the four schemes
    (config 6, strata from config-0 data)."""
    margins = {}
    for name in APP_NAMES:
        exp = engine.app(name)
        margins[name] = {s: _analytical_margin(exp, s, 6)
                         for s in ("random", "bbv", "rfv", "dg")}
    worse = [n for n, m in margins.items() if m["bbv"] > m["random"]]
    return {"margins": margins, "bbv_worse": len(worse),
            "bbv_worse_apps": worse,
            "rfv_margin_lt12pct": sum(1 for m in margins.values()
                                      if m["rfv"] < 12.0)}


# ------------------------------------------------------------------ Fig 8
def ci_empirical(engine: ExperimentEngine) -> dict:
    """Fig. 8: Monte-Carlo 95th-percentile |error| at n = 20 per scheme,
    and the empirical coverage of the per-trial 95% intervals."""
    res = run_trials(engine, TrialSpec(trials=EMPIRICAL_TRIALS),
                     apps=tuple(APP_NAMES))
    p95 = {name: {k: float(np.percentile(res.errors[k][a], 95))
                  for k in res.errors}
           for a, name in enumerate(res.apps)}
    return {"p95_err": p95,
            "coverage": {k: float(np.mean(v))
                         for k, v in res.coverage.items()}}


# ------------------------------------------------------------------ Fig 9
def ci_collapsed(engine: ExperimentEngine) -> dict:
    """Fig. 9: the practically computable interval, collapsed strata
    from exactly 20 simulations of config 6 (one random unit per RFV
    stratum)."""
    margin, covers = {}, {}
    for name in APP_NAMES:
        exp = engine.app(name)
        sel, weights = plan_selection(
            exp, SamplingPlan.from_strings("rfv", "random"), seed=3)
        y = np.array([float(exp.cpi(6, s)[0]) for s in sel if s.size])
        w = np.array([weights[h] for h, s in enumerate(sel) if s.size])
        order = np.array([exp.cpi0_1[exp.rfv_labels == h].mean()
                          for h, s in enumerate(sel) if s.size])
        est = collapsed_strata_estimate(y, w / w.sum(), order_by=order)
        margin[name] = est.margin_pct
        covers[name] = bool(est.covers(exp.truth[6]))
    return {"margin_pct": margin, "covers": covers,
            "coverage": sum(covers.values())}


# ----------------------------------------------------------- Figs 10, 11
def _selection_errors(engine: ExperimentEngine, policy: str) -> dict:
    """Worst config error per app and scheme with ``policy`` selection
    (one ``run_sweep`` per scheme over every app)."""
    out = {name: {} for name in APP_NAMES}
    for scheme in ("bbv", "rfv", "dg"):
        table = run_sweep(engine, SweepSpec(
            apps=tuple(APP_NAMES),
            plan=SamplingPlan.from_strings(scheme, policy)))
        for name in APP_NAMES:
            out[name][scheme] = float(
                table.filter(app=name).column("err_pct").max())
    return out


def selection_centroid(engine: ExperimentEngine) -> dict:
    """Fig. 10: measured errors over Configs 0-6 with centroid
    selection."""
    per_app = _selection_errors(engine, "centroid")
    return {"max_err": per_app,
            "worst_bbv": max(v["bbv"] for v in per_app.values()),
            "worst_rfv": max(v["rfv"] for v in per_app.values())}


def selection_mean(engine: ExperimentEngine) -> dict:
    """Fig. 11: mean selection (the unit nearest its stratum's
    baseline-CPI mean)."""
    per_app = _selection_errors(engine, "mean")
    return {"max_err": per_app,
            "worst_bbv": max(v["bbv"] for v in per_app.values())}


# -------------------------------------------------------------- Fig 12/13
def distribution_approx(engine: ExperimentEngine) -> dict:
    """Figs. 12/13: the CPI distribution approximated by 20 and by 500
    selected regions; Kolmogorov-Smirnov distance to the census."""
    ks = {}
    for name in APP_NAMES:
        exp = engine.app(name)
        census = np.sort(exp.census(0))
        ks[name] = {}
        for k in (20, 500):
            if k == 20:
                sel, weights = plan_selection(
                    exp, SamplingPlan.from_strings("rfv", "centroid"))
            else:
                km = kmeans(exp.rfv_z, min(k, exp.idx1.size // 2), seed=0)
                w = np.bincount(km.labels,
                                minlength=km.centroids.shape[0]).astype(float)
                local = select_centroid(km.labels, exp.rfv_z, km.centroids)
                sel, weights = [exp.idx1[l] for l in local], w / w.sum()
            vals, ws = [], []
            for h, s in enumerate(sel):
                if s.size:
                    vals.append(float(exp.cpi(0, s)[0]))
                    ws.append(weights[h])
            vals = np.asarray(vals)
            ws = np.asarray(ws) / np.sum(ws)
            order = np.argsort(vals)
            vals, ws = vals[order], ws[order]
            census_cdf = np.searchsorted(census, vals, side="right") \
                / census.size
            ks[name][k] = float(np.max(np.abs(np.cumsum(ws) - census_cdf)))
    return {"ks": ks, "improved_at_500": sum(
        1 for v in ks.values() if v[500] <= v[20] + 1e-9)}


# --------------------------------------------------------------- Table IV
def two_phase_sizing(engine: ExperimentEngine) -> dict:
    """Table IV: phase-2 sizes for a margin of at most 1.5x the phase-1
    random margin, RFV against BBV strata; the reduction factors against
    simple random sampling."""
    sizes = {}
    for name in APP_NAMES:
        exp = engine.app(name)
        cpi6_p1 = exp.cpi(6, exp.idx1)
        n1 = exp.idx1.size
        est1 = srs_estimate(cpi6_p1)
        sizes[name] = {"random": n1,
                       "random_margin_pct": est1.margin_pct}
        # BBV: the phase-1 units classified into the census BBV strata
        for scheme, labels, weights in (
                ("rfv", exp.rfv_labels, exp.rfv_weights),
                ("bbv", exp.bbv_labels[exp.idx1], exp.bbv_weights)):
            stds = np.array([cpi6_p1[labels == h].std(ddof=1)
                             if (labels == h).sum() > 1 else 0.0
                             for h in range(NUM_STRATA)])
            mean = float(np.sum(weights * np.array(
                [cpi6_p1[labels == h].mean() if (labels == h).any() else 0.0
                 for h in range(NUM_STRATA)])))
            between = float(np.sum(weights * (np.array(
                [cpi6_p1[labels == h].mean() if (labels == h).any() else mean
                 for h in range(NUM_STRATA)]) - mean) ** 2))
            try:
                n_h = phase2_sizes_for_margin(
                    weights, stds, n1, between,
                    target_margin_abs=1.5 * est1.margin,
                    allocation="neyman")
                sizes[name][scheme] = int(n_h.sum())
            except ValueError:
                sizes[name][scheme] = n1   # unattainable: the full sample
    totals = {s: sum(v[s] for v in sizes.values())
              for s in ("random", "rfv", "bbv")}
    return {"sizes": sizes,
            **{f"total_{s}": n for s, n in totals.items()},
            "reduction_rfv": totals["random"] / max(totals["rfv"], 1),
            "reduction_bbv": totals["random"] / max(totals["bbv"], 1)}


# ------------------------------------------------ gcc k-sensitivity (V.B.1)
def gcc_cluster_sensitivity(engine: ExperimentEngine) -> dict:
    """§V.B.1: raising gcc's BBV clusters from 20 to 50 collapses the
    centroid-selection error."""
    exp = engine.app("502.gcc_r")
    z = exp.bbv_feats
    max_err = {}
    for k in (20, 50):
        km = kmeans(z, k, seed=0)
        w = np.bincount(km.labels, minlength=k) / z.shape[0]
        max_err[k] = _max_err(exp, select_centroid(km.labels, z,
                                                   km.centroids), w)
    return {"max_err": max_err}


# ------------------------------------------------ beyond the paper: §VI.C
def approx_phase1(engine: ExperimentEngine) -> dict:
    """§VI.C, proposed there and not evaluated: phase 1 on a fast
    approximate simulator, strata on its biased RFV, then the accurate
    configs on the selected regions."""
    from .simcpu.perfmodel import evaluate_regions_approx

    max_err = {}
    for name in APP_NAMES:
        exp = engine.app(name)
        stats = evaluate_regions_approx(exp.sim.pop.features, CONFIGS[0],
                                        exp.idx1)
        max_err[name] = _stratify_phase1(
            exp, np.stack([stats[k] for k in sorted(stats)], axis=1))
    return {"max_err": max_err, "worst": max(max_err.values())}


def isa_features(engine: ExperimentEngine) -> dict:
    """§VI.C: strata on microarchitecture-independent features, the
    populations' intrinsic feature vectors, known without a
    cycle-accurate run."""
    max_err = {}
    for name in APP_NAMES:
        exp = engine.app(name)
        max_err[name] = _stratify_phase1(exp, exp.sim.pop.features[exp.idx1])
    return {"max_err": max_err, "worst": max(max_err.values())}


FIGURES: dict[str, Callable[[ExperimentEngine], dict]] = {
    "fig1_cpi_distributions": cpi_distributions,
    "fig5_config_sweep": config_sweep,
    "fig7_ci_analytical": ci_analytical,
    "fig8_ci_empirical": ci_empirical,
    "fig9_ci_collapsed": ci_collapsed,
    "fig10_selection_centroid": selection_centroid,
    "fig11_selection_mean": selection_mean,
    "fig12_13_distribution_approx": distribution_approx,
    "table4_two_phase_sizing": two_phase_sizing,
    "gcc_cluster_sensitivity": gcc_cluster_sensitivity,
    "beyond_approx_phase1": approx_phase1,
    "beyond_isa_features": isa_features,
}


@dataclasses.dataclass(frozen=True)
class Claim:
    """One headline result: ``holds`` over the numbers of ``figure``;
    ``reads`` says what the reproduction measured, ``paper`` what the
    paper reports."""

    name: str
    figure: str
    holds: Callable[[dict], bool]
    reads: Callable[[dict], str]
    paper: str


CLAIMS: tuple[Claim, ...] = (
    Claim("geomean_speedup", "fig5_config_sweep",
          lambda r: 1.5 <= r["speedup"] <= 1.9,
          lambda r: f"cfg6/cfg0 geomean IPC {r['speedup']:.2f}",
          "1.68"),
    Claim("simpoint20_large_error", "fig10_selection_centroid",
          lambda r: r["worst_bbv"] >= 20.0,
          lambda r: f"worst BBV centroid error {r['worst_bbv']:.1f}%",
          "40-60% for two apps"),
    Claim("two_phase_rfv_low_error", "fig10_selection_centroid",
          lambda r: r["worst_rfv"] <= 8.0,
          lambda r: f"worst RFV error {r['worst_rfv']:.1f}%",
          "~3%"),
    # the phenomenon, not the count: BBV strata CAN give wider intervals
    # than simple random sampling (here the dominant-phase apps)
    Claim("bbv_worse_than_random", "fig7_ci_analytical",
          lambda r: r["bbv_worse"] >= 2,
          lambda r: f"{r['bbv_worse']} apps",
          "~5 of 10 apps"),
    Claim("order_of_magnitude_reduction", "table4_two_phase_sizing",
          lambda r: r["reduction_rfv"] >= 5.0,
          lambda r: f"RFV phase-2 reduction {r['reduction_rfv']:.1f}x",
          "12.6x"),
    Claim("rfv_beats_bbv_sizing", "table4_two_phase_sizing",
          lambda r: r["reduction_rfv"] > r["reduction_bbv"],
          lambda r: f"rfv {r['reduction_rfv']:.1f}x vs bbv "
                    f"{r['reduction_bbv']:.1f}x",
          "12.6x vs 3.5x"),
    Claim("gcc_k50_fixes_bbv", "gcc_cluster_sensitivity",
          lambda r: r["max_err"][50] < r["max_err"][20],
          lambda r: f"k=20: {r['max_err'][20]:.1f}% -> "
                    f"k=50: {r['max_err'][50]:.1f}%",
          "5.4% at k=50"),
)


def _rows(prefix: str, value):
    """``(key, value)`` leaves of a figure's nested numbers."""
    if isinstance(value, dict):
        for k, v in value.items():
            yield from _rows(f"{prefix}.{k}", v)
    else:
        yield prefix, value


def main() -> int:
    """Run every figure, print its numbers and the claims' verdicts;
    0 when every claim holds."""
    from .runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    engine = ExperimentEngine.auto()
    results = {}
    for name, figure in FIGURES.items():
        results[name] = figure(engine)
        for key, value in _rows(name, results[name]):
            print(f"{key},{value}", flush=True)
    failed = 0
    for claim in CLAIMS:
        r = results[claim.figure]
        held = claim.holds(r)
        failed += not held
        print(f"claim_{claim.name},{'PASS' if held else 'FAIL'},"
              f"{claim.reads(r)} (paper: {claim.paper})")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
