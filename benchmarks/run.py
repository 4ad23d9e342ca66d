"""Benchmark harness: one function per paper table/figure.

Prints ``name,value,derived`` CSV and a final claim-validation summary,
and writes a machine-readable ``BENCH_results.json`` (per-bench timings
and results + claim outcomes) so the perf trajectory is tracked across
PRs. ``--quick`` trims Monte-Carlo trial counts (CI smoke).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

RESULTS_PATH = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_results.json"
HISTORY_PATH = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_history.jsonl"


def _force_devices(n: int) -> None:
    """Set the XLA host-device flag; must run BEFORE any jax import."""
    if n < 1:
        sys.exit(f"--devices must be >= 1, got {n}")
    if "jax" in sys.modules:
        sys.exit("--devices must take effect before jax is imported; "
                 "set XLA_FLAGS=--xla_force_host_platform_device_count="
                 f"{n} in the environment instead")
    flag = f"--xla_force_host_platform_device_count={n}"
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {flag}".strip()


def _jsonable(obj):
    """Conversion of bench results to STRICTLY valid JSON values.

    NaN/±Inf (python floats, numpy scalars, and entries inside numpy
    arrays) all become null — json.dumps would otherwise emit bare
    ``NaN``/``Infinity`` tokens that strict parsers reject, defeating
    the machine-readable ledger."""
    import math

    import numpy as np
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return _jsonable(obj.tolist())
    if isinstance(obj, np.generic):
        return _jsonable(obj.item())
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


def write_results_json(benches: dict, claims: dict, ok: bool,
                       errors: list, total_s: float,
                       path: pathlib.Path = RESULTS_PATH) -> None:
    """Dump the machine-readable run record (the cross-PR perf ledger).

    Merges into an existing ledger: a partial run (``--only``) updates
    its own bench/claim rows and leaves the rest in place, so a targeted
    rerun never erases the full-suite record. ``overall_pass`` reflects
    only the rows this run validated."""
    payload = {
        "benches": _jsonable(benches),
        "claims": _jsonable(claims),
        "overall_pass": bool(ok),
        "errors": list(errors),
        "total_seconds": round(total_s, 2),
    }
    if path.exists():
        try:
            prior = json.loads(path.read_text())
            payload["benches"] = {**prior.get("benches", {}),
                                  **payload["benches"]}
            payload["claims"] = {**prior.get("claims", {}),
                                 **payload["claims"]}
        except (json.JSONDecodeError, AttributeError):
            pass                      # corrupt ledger: rewrite from scratch
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"# results written to {path.name}")


def append_history(claims: dict, ok: bool, errors: list, total_s: float,
                   path: pathlib.Path = HISTORY_PATH) -> None:
    """Append one run record to the cross-PR perf trajectory ledger.

    ``BENCH_history.jsonl`` is append-only (one JSON object per line,
    committed to the repo, unlike the overwritten ``BENCH_results.json``
    snapshot): each CI run adds its git SHA, UTC timestamp and claim
    outcomes, so regressions are attributable to a commit by reading the
    ledger alone."""
    import datetime
    import subprocess

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=path.parent, capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"
    entry = {
        "git_sha": sha,
        "timestamp": datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
        "claims": {name: bool(c["pass"]) for name, c in claims.items()},
        "overall_pass": bool(ok),
        "errors": list(errors),
        "total_seconds": round(total_s, 2),
    }
    with path.open("a") as fh:
        fh.write(json.dumps(entry, sort_keys=True) + "\n")
    print(f"# history entry appended to {path.name} ({sha})")


def main() -> None:
    """CLI entry: run benches, validate claims, write BENCH_results.json."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    ap.add_argument("--devices", type=int, default=None,
                    help="force N XLA host devices (app-sharded sweeps); "
                    "must be set before jax initializes")
    ap.add_argument("--trials", type=int, default=None,
                    help="largest Monte-Carlo trial count for the "
                    "streaming trials bench (default 100000, or 10000 "
                    "with --quick)")
    args = ap.parse_args()

    if args.devices is not None:
        _force_devices(args.devices)

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (estimators_bench, kernels_bench, kmeans_batched_bench,
                   lint_bench, paper_figs, serving_bench, trials_bench)

    max_trials = args.trials if args.trials is not None \
        else (10_000 if args.quick else 100_000)
    benches = {
        "fig1_cpi_distributions": paper_figs.bench_cpi_distributions,
        "fig5_config_sweep": paper_figs.bench_config_sweep,
        "fig7_ci_analytical": paper_figs.bench_ci_analytical,
        "fig8_ci_empirical": (lambda: paper_figs.bench_ci_empirical(
            trials=100 if args.quick else 1000)),
        "fig9_ci_collapsed": paper_figs.bench_ci_collapsed,
        "fig10_selection_centroid": paper_figs.bench_selection_centroid,
        "fig11_selection_mean": paper_figs.bench_selection_mean,
        "fig12_13_distribution_approx": paper_figs.bench_distribution_approx,
        "table4_two_phase_sizing": paper_figs.bench_two_phase_sizing,
        "gcc_cluster_sensitivity": paper_figs.bench_gcc_cluster_sensitivity,
        "beyond_approx_phase1": paper_figs.bench_approx_phase1,
        "beyond_isa_features": paper_figs.bench_isa_features,
        "kernels": kernels_bench.bench_kernels,
        "kmeans_batched": kmeans_batched_bench.bench_kmeans_batched,
        "estimators": estimators_bench.bench_estimators,
        # registered after fig5/estimators so a combined --only run shares
        # the process-wide engine (and its MemoBank) they already built
        "fused_sweep": (lambda: estimators_bench.bench_fused_sweep(
            quick=args.quick)),
        "trials_streaming": (lambda: trials_bench.bench_trials_streaming(
            trials=max_trials, quick=args.quick)),
        "checkpoint_overhead": (
            lambda: trials_bench.bench_checkpoint_overhead(
                quick=args.quick)),
        "serving": (lambda: serving_bench.bench_serving(quick=args.quick)),
        "lint": lint_bench.bench_lint,
    }
    if args.only:
        names = args.only.split(",")
        unknown = [n for n in names if n not in benches]
        if unknown:
            sys.exit(f"unknown bench name(s): {', '.join(unknown)}; "
                     f"choose from: {', '.join(benches)}")
        benches = {k: v for k, v in benches.items() if k in names}

    t0 = time.time()
    results = {}
    bench_records = {}
    errors = []
    for name, fn in benches.items():
        print(f"# === {name} ===", flush=True)
        tb = time.time()
        try:
            results[name] = fn()
            bench_records[name] = {"seconds": round(time.time() - tb, 3),
                                   "result": results[name]}
        except Exception as e:  # noqa: BLE001
            print(f"{name},ERROR,{type(e).__name__}: {e}", flush=True)
            results[name] = None
            bench_records[name] = {"seconds": round(time.time() - tb, 3),
                                   "error": f"{type(e).__name__}: {e}"}
            errors.append(name)

    # ------------------------------------------------ claim validation
    print("# === claim validation (paper vs reproduction) ===")
    ok = True
    claims: dict[str, dict] = {}

    def check(name, cond, detail):
        nonlocal ok
        print(f"claim_{name},{'PASS' if cond else 'FAIL'},{detail}")
        claims[name] = {"pass": bool(cond), "detail": detail}
        ok = ok and cond

    r5 = results.get("fig5_config_sweep")
    if r5:
        check("geomean_speedup", 1.5 <= r5["speedup"] <= 1.9,
              f"cfg6/cfg0 {r5['speedup']:.2f} vs paper 1.68")
    r10 = results.get("fig10_selection_centroid")
    if r10:
        check("simpoint20_large_error", r10["worst_bbv"] >= 20.0,
              f"worst BBV centroid err {r10['worst_bbv']:.1f}% "
              "(paper: 40-60% for two apps)")
        check("two_phase_rfv_low_error", r10["worst_rfv"] <= 8.0,
              f"worst RFV err {r10['worst_rfv']:.1f}% (paper: ~3%)")
    r7 = results.get("fig7_ci_analytical")
    if r7:
        # qualitative phenomenon: BBV-stratified CIs CAN be worse than SRS
        # (paper: 5 of 10 apps; ours: the dominant-phase apps — see
        # EXPERIMENTS.md known deltas)
        check("bbv_worse_than_random", r7["bbv_worse"] >= 2,
              f"{r7['bbv_worse']} apps (paper: ~5)")
    rt = results.get("table4_two_phase_sizing")
    if rt:
        check("order_of_magnitude_reduction",
              rt["reduction_rfv"] >= 5.0,
              f"RFV phase-2 reduction {rt['reduction_rfv']:.1f}x "
              "(paper: 12.6x)")
        check("rfv_beats_bbv_sizing",
              rt["reduction_rfv"] > rt["reduction_bbv"],
              f"rfv {rt['reduction_rfv']:.1f}x vs bbv "
              f"{rt['reduction_bbv']:.1f}x (paper: 12.6 vs 3.5)")
    rg = results.get("gcc_cluster_sensitivity")
    if rg:
        check("gcc_k50_fixes_bbv", rg.get(50, 99) < rg.get(20, 0),
              f"k=20: {rg.get(20, 0):.1f}% -> k=50: {rg.get(50, 99):.1f}% "
              "(paper: 5.4% at k=50)")

    rb = results.get("kmeans_batched")
    if rb:
        check("batched_assign_matches_oracle", rb["worst_agree"] > 0.999,
              f"worst batched-vs-oracle agreement {rb['worst_agree']:.4f}")

    re_ = results.get("estimators")
    if re_:
        check("batched_estimators_match_scalar",
              re_["max_rel_err"] <= 1e-6,
              f"max rel err {re_['max_rel_err']:.2e} "
              f"(batched {re_['speedup']:.0f}x faster than scalar loop)")
        # f64 hosts must match to 1e-6; TPU keeps the program in f32 by
        # design (no native f64), so the gate loosens to f32 precision
        # there instead of failing by construction
        sweep_bound = 1e-6 if re_.get("sweep_x64") else 1e-4
        check("sweep_estimates_on_device_match_host",
              re_["sweep_max_rel_err"] <= sweep_bound,
              f"jitted StratumTables sweep estimation vs host numpy: "
              f"max rel err {re_['sweep_max_rel_err']:.2e} "
              f"(gate {sweep_bound:g}), "
              f"{re_['staged_sweep_speedup']:.2f}x host/device at 10x7 "
              f"(launch-bound) vs "
              f"{re_.get('staged_sweep_speedup_large', float('nan')):.2f}x "
              f"at service scale, x64={re_['sweep_x64']}")

    rf = results.get("fused_sweep")
    if rf:
        # two-part gate: parity + ledger equality at every rung, and the
        # fused megaprogram must beat the staged pipeline at (or below)
        # the largest rung tested — the full paper matrix (10 apps x 7
        # configs) on a non-quick run
        fused_bound = 1e-6
        won = rf["crossover"] is not None
        check("sweep_device_crossover",
              won and rf["max_rel_err"] <= fused_bound and rf["ledger_eq"],
              (f"fused megaprogram >= 1x staged at "
               f"{rf['crossover'][0]}x{rf['crossover'][1]} " if won
               else f"fused never beat staged up to "
               f"{rf['max_rung'][0]}x{rf['max_rung'][1]} ")
              + f"(max rel err {rf['max_rel_err']:.1e} gate "
              f"{fused_bound:g}, ledger_eq={rf['ledger_eq']}, "
              f"{rf['devices']} device(s), quick={rf['quick']})")

    rtr = results.get("trials_streaming")
    if rtr:
        check("streaming_chunked_bitwise", rtr["chunked_bitwise"],
              "chunked scan == unchunked bitwise at 1000 trials "
              "(per-block PRNG contract)")
        worst = min(rtr["coverage"].values())
        check("streaming_coverage_calibrated", worst >= 0.90,
              f"worst calibrated-scheme coverage {worst:.3f} at "
              f"{rtr['max_trials']} trials (gate 0.90, nominal 0.95, "
              "f32 accumulators)")
        scale_floor = 10_000 if rtr.get("quick") else 100_000
        top = rtr["rows"][-1]
        check("streaming_trials_scale", rtr["max_trials"] >= scale_floor,
              f"{top['trials']} trials streamed in {top['seconds']}s "
              f"({top['trials_per_sec']:,.0f} trial-lanes/s, "
              f"{top['devices']} device(s), bounded memory)")

    rco = results.get("checkpoint_overhead")
    if rco:
        check("checkpoint_overhead_small", rco["ratio"] < 0.05,
              f"{rco['snapshots_per_study']} fleet snapshots x "
              f"{rco['snapshot_seconds'] * 1e3:.1f}ms = "
              f"{100 * rco['ratio']:.2f}% of the steady-state "
              f"{rco['trials']}-trial run ({rco['run_seconds']}s, "
              f"{rco['snapshot_mb']}MB state, gate < 5%)")

    rl = results.get("lint")
    if rl:
        check("lint_clean", rl["ok"] and rl["seconds"] < 10.0,
              f"{rl['rules']} rules x {rl['files']} files: "
              f"{rl['active']} active, {rl['baselined']} baselined "
              f"({rl['baseline_entries']} justified entries), "
              f"{rl['suppressed']} suppressed, {rl['stale']} stale, "
              f"{rl['errors']} errors in {rl['seconds']:.2f}s "
              "(gate: clean and < 10s)")

    rs = results.get("serving")
    if rs:
        check("serving_coalesced_bitwise", rs["bitwise"],
              "coalesced stacked dispatches == serial run_sweep bitwise "
              "(estimates + ledger charge totals) at every K rung")
        # throughput gates the launch-bound rung (smallest apps) where
        # coalescing's launch amortization is the measured effect; quick
        # runs only smoke the machinery (2 reps, cold-heavy), so the
        # gate applies to full runs
        k8 = rs.get("speedup_k8") or 0.0
        gate = 2.0 if not rs.get("quick") else 0.5
        check("serving_coalesced_speedup", k8 >= gate,
              f"coalesced K=8 {k8:.2f}x vs serial (gate {gate:g}x, "
              f"quick={rs.get('quick')}; smallest-app launch-bound rung)")
        check("serving_eviction_bounded",
              rs["eviction_bounded"] and rs["eviction_ledger_exact"],
              f"peak resident {rs['peak_resident_cols']} <= cap "
              f"{rs['memo_cap']} ({rs['evicted_cols']} evictions), "
              f"ledger exact={rs['eviction_ledger_exact']} under spill")

    # a bench that crashed is a failure even if no claim row references it
    check("no_bench_errors", not errors,
          "errors in: " + "|".join(errors) if errors else "all benches ran")

    total_s = time.time() - t0
    print(f"benchmarks_total_s,{total_s:.1f},")
    print(f"benchmarks_overall,{'PASS' if ok else 'FAIL'},")
    write_results_json(bench_records, claims, ok, errors, total_s)
    append_history(claims, ok, errors, total_s)
    # CI contract: any FAILing claim-validation row (or bench error) must
    # make the process exit non-zero.
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
