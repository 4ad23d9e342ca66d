"""Batched estimator engine vs the scalar loop: parity + throughput.

The tentpole claim of the array-native statistics layer is that one
batched ``StratumTables`` program over ``(A, T)`` design lanes replaces
A·T scalar ``summarize_strata`` + ``two_phase_estimate`` calls — with
identical results. This bench measures both paths on synthetic stratified
lanes and reports:

* ``estimators_scalar_us_per_lane`` / ``estimators_batched_us_per_lane``
  — wall time per design lane for each path (host CPU, float64);
* ``estimators_batched_speedup`` — scalar / batched;
* ``estimators_max_rel_err`` — worst relative deviation of the batched
  mean / two-phase variance / Satterthwaite df from the scalar reference
  across every lane. Gated in ``run.py`` claim validation at 1e-6 (the
  acceptance bar for batched == scalar).
"""

from __future__ import annotations

import dataclasses
import time
import warnings

import numpy as np

from repro.core.sampling import (WeightedPoint, critical_values,
                                 summarize_strata, two_phase_estimate)
from repro.core.sampling import plan as sampling_plan
from repro.core.sampling import tables as T

A_LANES = 4          # app-like axis
T_LANES = 250        # trial-like axis
N_SAMPLES = 200      # sampled units per lane
L_STRATA = 20
PHASE1_N = 6000

SWEEP_A = 10         # sweep-estimation shape: apps ...
SWEEP_C = 7          # ... x configs
SWEEP_A_LARGE = 2048  # service-scale rung: a coalesced tick's worth of
SWEEP_C_LARGE = 64    # stacked requests x a design-space config grid
SWEEP_REPS = 50      # timed repetitions (both paths, post-warmup)


def _rel_err(a, b):
    """Worst relative deviation; a one-sided NaN (batched NaN where the
    scalar is finite, or vice versa) counts as infinite mismatch rather
    than being silently dropped from the gate."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if not np.array_equal(np.isnan(a), np.isnan(b)):
        return float("inf")
    denom = np.maximum(np.abs(b), 1e-12)
    with np.errstate(invalid="ignore"):
        err = np.abs(a - b) / denom
    return float(np.nanmax(err)) if np.isfinite(err).any() else 0.0


def bench_estimators() -> dict:
    """CSV rows + {max_rel_err, speedup} for claim validation."""
    rng = np.random.default_rng(0)
    y = rng.normal(2.0, 1.0, (A_LANES, T_LANES, N_SAMPLES)) \
        + 0.3 * rng.integers(0, 4, (A_LANES, 1, 1))
    labels = rng.integers(0, L_STRATA, (A_LANES, T_LANES, N_SAMPLES))
    weights = np.full(L_STRATA, 1.0 / L_STRATA)
    lanes = A_LANES * T_LANES

    # scalar reference: one summarize + estimate per lane (rare degenerate
    # lanes — an n_h < 2 stratum — warn in the scalar API; the batched
    # path marks the same lanes NaN, so both stay comparable)
    t0 = time.perf_counter()
    means_s = np.empty((A_LANES, T_LANES))
    vars_s = np.empty((A_LANES, T_LANES))
    dfs_s = np.empty((A_LANES, T_LANES))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        for a in range(A_LANES):
            for t in range(T_LANES):
                summ = summarize_strata(y[a, t], labels[a, t],
                                        weights=weights)
                est = two_phase_estimate(summ, phase1_n=PHASE1_N)
                means_s[a, t] = est.mean
                vars_s[a, t] = est.variance
                dfs_s[a, t] = est.df if est.df is not None else np.inf
    scalar_s = time.perf_counter() - t0

    # batched: ONE tables build + estimator evaluation for every lane
    t0 = time.perf_counter()
    tbl = T.stratum_tables(y, labels, weights=weights,
                           num_strata=L_STRATA)
    means_b = T.stratified_mean(tbl)
    vars_b = T.two_phase_variance(tbl, PHASE1_N)
    dfs_b = T.satterthwaite_df(tbl)
    margins = critical_values(0.95, dfs_b) * np.sqrt(vars_b)
    batched_s = time.perf_counter() - t0

    err = max(_rel_err(means_b, means_s), _rel_err(vars_b, vars_s),
              _rel_err(np.where(np.isfinite(dfs_b), dfs_b, np.inf), dfs_s))
    speedup = scalar_s / max(batched_s, 1e-9)

    print(f"estimators_scalar_us_per_lane,{scalar_s / lanes * 1e6:.1f},"
          f"{lanes} lanes")
    print(f"estimators_batched_us_per_lane,{batched_s / lanes * 1e6:.1f},"
          f"one (A,T,L) tables program")
    print(f"estimators_batched_speedup,{speedup:.1f},scalar/batched")
    print(f"estimators_max_rel_err,{err:.2e},mean|variance|df vs scalar")
    print(f"estimators_mean_margin_pct,"
          f"{float(np.nanmean(100 * margins / np.abs(means_b))):.3f},"
          "sanity: eq.6 margin at these lane sizes")
    sweep = _bench_sweep_estimates()
    return {"max_rel_err": err, "speedup": speedup,
            "scalar_s": scalar_s, "batched_s": batched_s, **sweep}


def _host_sweep_reduction(cpi, valid, weights, truth):
    """The historic host-numpy sweep reduction (pre-plan ``run_sweep``):
    covered-weight-renormalized weighted mean + percent error, float64."""
    w = np.where(valid, weights, 0.0)
    covered = w.sum(axis=1)
    ests = (cpi * w[:, None, :]).sum(axis=2) / covered[:, None]
    errs = 100.0 * np.abs(ests - truth) / truth
    return ests, errs


def _sweep_rung(a_n: int, c_n: int) -> dict:
    """One (apps x configs) rung of host-numpy vs jitted on-device sweep
    estimation: returns {max_rel_err, speedup, host_s, device_s, x64}."""
    rng = np.random.default_rng(1)
    cpi = rng.normal(2.0, 0.6, (a_n, c_n, L_STRATA))
    valid = rng.random((a_n, L_STRATA)) > 0.1
    valid[:, 0] = True                        # no fully-empty app lanes
    weights = rng.random((a_n, L_STRATA))
    weights /= weights.sum(axis=1, keepdims=True)
    truth = rng.normal(2.0, 0.1, (a_n, c_n))
    est = WeightedPoint()

    est_d, err_d = est.sweep_estimates(cpi, valid, weights, truth)  # warmup
    t0 = time.perf_counter()
    for _ in range(SWEEP_REPS):
        est_d, err_d = est.sweep_estimates(cpi, valid, weights, truth)
    device_s = (time.perf_counter() - t0) / SWEEP_REPS

    est_h, err_h = _host_sweep_reduction(cpi, valid, weights, truth)
    t0 = time.perf_counter()
    for _ in range(SWEEP_REPS):
        est_h, err_h = _host_sweep_reduction(cpi, valid, weights, truth)
    host_s = (time.perf_counter() - t0) / SWEEP_REPS

    marker = sampling_plan.last_sweep_dispatch() or {}
    return {"max_rel_err": max(_rel_err(est_d, est_h),
                               _rel_err(err_d, err_h)),
            "speedup": host_s / max(device_s, 1e-12),
            "host_s": host_s, "device_s": device_s,
            "x64": bool(marker.get("x64", False))}


def _bench_sweep_estimates() -> dict:
    """Host-numpy vs jitted on-device sweep estimation (the run_sweep
    stratified path) at TWO rungs: the paper's 10x7 matrix (tiny —
    launch cost dominates, device expected <1x) and a service-scale
    512x32 batch (where the device side should win). Parity gated at
    1e-6 in run.py claim validation; both speedups recorded so the
    claim row reflects where the device program actually pays off."""
    tiny = _sweep_rung(SWEEP_A, SWEEP_C)
    large = _sweep_rung(SWEEP_A_LARGE, SWEEP_C_LARGE)

    print(f"sweep_est_host_us,{tiny['host_s'] * 1e6:.1f},"
          f"numpy reduction ({SWEEP_A}x{SWEEP_C}x{L_STRATA})")
    print(f"sweep_est_device_us,{tiny['device_s'] * 1e6:.1f},"
          f"jitted StratumTables program (x64={tiny['x64']})")
    # "staged": the estimate-stage-only dispatch of the staged pipeline —
    # expected <1x at the tiny shape (launch cost dominates); the fused
    # megaprogram's crossover is bench_fused_sweep's claim, not this one's
    print(f"staged_sweep_speedup,{tiny['speedup']:.2f},host/device at "
          f"{SWEEP_A}x{SWEEP_C} (legacy staged row; see fused_sweep for "
          "the gated crossover)")
    print(f"staged_sweep_speedup_large,{large['speedup']:.2f},"
          f"host/device at {SWEEP_A_LARGE}x{SWEEP_C_LARGE} "
          "(service-scale batch)")
    err = max(tiny["max_rel_err"], large["max_rel_err"])
    print(f"sweep_est_max_rel_err,{err:.2e},device vs host f64, "
          "both rungs")
    return {"sweep_max_rel_err": err,
            "staged_sweep_speedup": tiny["speedup"],
            "staged_sweep_speedup_large": large["speedup"],
            "sweep_host_s": tiny["host_s"],
            "sweep_device_s": tiny["device_s"],
            "sweep_x64": tiny["x64"]}


# --------------------------------------------------- fused sweep megaprogram
FUSED_LADDER = [(2, 2), (4, 4), (10, 7)]      # (apps, configs) rungs
FUSED_LADDER_QUICK = [(2, 2), (2, 7)]         # CI smoke (reduced scale)
FUSED_REPS = 10
FUSED_REPS_QUICK = 4


def _memo_snapshot(memo):
    """Copy-out of every mutable MemoBank field (arrays may GROW between
    snapshot and restore as new config columns appear; restore handles
    the leading-slice writeback)."""
    return (memo.mask.copy(), memo.cpi.copy(), memo.charges.copy(),
            list(memo.hit_count), list(memo.miss_count),
            [(l.regions_simulated, l.instructions_simulated)
             if l is not None else None for l in memo.ledgers])


def _memo_restore(memo, snap):
    """Restore a ``_memo_snapshot`` (column growth since is zeroed)."""
    mask, cpi, charges, hits, misses, leds = snap
    memo.mask[...] = False
    memo.cpi[...] = 0.0
    memo.charges[...] = 0
    s3 = tuple(slice(0, d) for d in mask.shape)
    memo.mask[s3], memo.cpi[s3] = mask, cpi
    memo.charges[tuple(slice(0, d) for d in charges.shape)] = charges
    memo.hit_count[:] = hits
    memo.miss_count[:] = misses
    for led, st in zip(memo.ledgers, leds):
        if led is not None and st is not None:
            led.regions_simulated, led.instructions_simulated = st
    memo._spill.clear()   # spilled columns belong to the discarded state
    memo._col_tick.clear()
    memo.touch()          # direct table writes: drop device-block mirrors


def _ledger_totals(memo):
    return [(l.regions_simulated, l.instructions_simulated)
            if l is not None else None for l in memo.ledgers]


def bench_fused_sweep(quick: bool = False) -> dict:
    """Fused megaprogram vs staged pipeline over an (apps x configs)
    ladder: measures the host/device crossover — the smallest sweep at
    which ONE donated-buffer device program beats the staged
    selection -> fill -> estimate chain — and gates parity (<=1e-6) and
    bitwise ledger-charge equality at every rung."""
    import jax

    from repro.core.sampling import SamplingPlan
    from repro.experiments import SweepSpec, run_sweep

    from .simcpu_common import all_apps, get_engine

    engine = get_engine()
    ladder = FUSED_LADDER_QUICK if quick else FUSED_LADDER
    reps = FUSED_REPS_QUICK if quick else FUSED_REPS
    apps_all = all_apps()
    plan = SamplingPlan.from_strings("rfv", "centroid")
    rows = []
    for a_n, c_n in ladder:
        apps = tuple(apps_all[:a_n])
        engine.build(apps)
        spec = SweepSpec(apps=apps, plan=plan,
                         config_indices=tuple(range(c_n)))
        base = _memo_snapshot(engine.memo)

        t_s = run_sweep(engine, dataclasses.replace(spec, fused=False))
        led_staged = _ledger_totals(engine.memo)
        t0 = time.perf_counter()
        for _ in range(reps):
            run_sweep(engine, dataclasses.replace(spec, fused=False))
        staged_s = (time.perf_counter() - t0) / reps
        _memo_restore(engine.memo, base)

        t_f = run_sweep(engine, spec)                 # cold: compile + fill
        led_fused = _ledger_totals(engine.memo)
        marker = sampling_plan.last_sweep_dispatch() or {}
        t0 = time.perf_counter()
        for _ in range(reps):
            run_sweep(engine, spec)
        fused_s = (time.perf_counter() - t0) / reps
        _memo_restore(engine.memo, base)

        err = _rel_err([r.estimate for r in t_f], [r.estimate for r in t_s])
        speedup = staged_s / max(fused_s, 1e-12)
        n_units = int(sum(r.n_units for r in t_f)) // c_n
        rows.append({"apps": a_n, "configs": c_n, "regions": n_units,
                     "staged_ms": staged_s * 1e3, "fused_ms": fused_s * 1e3,
                     "speedup": speedup, "max_rel_err": err,
                     "ledger_eq": led_staged == led_fused,
                     "donated": bool(marker.get("donated", False))})
        print(f"fused_sweep_{a_n}x{c_n},{speedup:.2f},staged/fused "
              f"(staged {staged_s * 1e3:.1f}ms fused {fused_s * 1e3:.1f}ms "
              f"rel_err {err:.1e} ledger_eq={led_staged == led_fused})")

    crossover = next((r for r in rows if r["speedup"] >= 1.0), None)
    print("fused_sweep_crossover,"
          + (f"{crossover['apps']}x{crossover['configs']}" if crossover
             else "none")
          + f",smallest rung where fused >= 1x staged "
          f"({len(jax.devices())} device(s))")
    return {"rows": rows, "quick": bool(quick),
            "crossover": ((crossover["apps"], crossover["configs"])
                          if crossover else None),
            "max_rung": max((r["apps"], r["configs"]) for r in rows),
            "max_rel_err": max(r["max_rel_err"] for r in rows),
            "ledger_eq": all(r["ledger_eq"] for r in rows),
            "devices": len(jax.devices())}
