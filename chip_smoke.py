#!/usr/bin/env python3
"""Smoke run of the study engine on a TPU at the paper's full bank size.

    python chip_smoke.py             # one chip, five phases
    python chip_smoke.py --chips 4   # four chips: the mesh paths only

One chip, all 10 apps (40k-120k regions each), 7 configs, L=20 strata:

1. build    ``ExperimentEngine.build`` of the whole bank, with the
            ``segment_stats`` kernel compiled (not interpreted) over the
            app batch and checked against its jnp oracle;
2. sweep    rfv x centroid through ``run_sweep`` (the fused
            megaprogram) against the staged reference chain;
3. trials   ``run_trials`` over the 4 canonical schemes at 10^4 trials,
            with the coverage gate of the streaming-trials tests;
4. service  ``SweepService`` on a 12-request synthetic stream, each
            coalesced result against a serial ``run_sweep``;
5. kmeans   the build's own BBV and RFV k-means fits: the compiled
            ``kmeans_assign`` kernel ran them over the app batch, every
            lane converged below the iteration cap, and the labels agree
            with the exact reference assignment to the fitted centroids.

``--chips 4`` runs the paper sweep on an ``("app",)`` mesh and the
trial study on an ``("app", "trial")`` mesh, each against the same run
on one device in the same process.

Every check that fails exits non-zero. Earlier lines report each
phase's wall and compile time and the device's peak memory; the last
line of stdout is one JSON object naming the device. Without a TPU the
script exits non-zero before any work.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

L = 20
TRIALS = 10_000
COVERAGE_GATE = 0.90        # tests/test_streaming_trials.py, on its app
COVERAGE_APP = "505.mcf_r"
SWEEP_REL_BOUND = 1e-4      # sweep_estimates_on_device_match_host on TPU
KMEANS_AGREE_GATE = 0.999   # batched_assign_matches_oracle
KMEANS_MAX_ITERS = 100      # kmeans_bank's default cap, as the build uses
SUM_REL_BOUND = 1e-5        # f32 sums of <= 120k values, reordered
LEDGER = ("ledger_regions", "ledger_instr")

_compile_s = [0.0]


def _on_event(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += secs


def check(ok: bool, what: str) -> None:
    """Exit non-zero on a failed check (after saying which)."""
    if not ok:
        print(f"FAIL: {what}", flush=True)
        sys.exit(1)


def phase(name: str, fn, *args):
    """Run one phase; report wall, compile and peak device memory."""
    import jax

    c0, t0 = _compile_s[0], time.perf_counter()
    out = fn(*args)
    wall = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    mem = "n/a" if peak is None else f"{peak / 2**30:.3f} GiB"
    print(f"phase {name}: wall {wall:.2f} s, compile "
          f"{_compile_s[0] - c0:.2f} s, run {wall - _compile_s[0] + c0:.2f} s,"
          f" peak device memory {mem}", flush=True)
    return out


def accounting(memo, keys=("mask", "charges", "hit_count", "miss_count")
               + LEDGER):
    """The memo's picks (its mask), charges, hit/miss counts and ledger
    totals, from ``MemoBank.state()``."""
    tree, _ = memo.state()
    return {k: tree[k] for k in keys}


def same_accounting(a, b) -> bool:
    return all(np.array_equal(a[k], b[k]) for k in a)


def rfv_centroid():
    from repro.core.sampling.plan import Centroid, RFVClusters, SamplingPlan
    return SamplingPlan(RFVClusters(), Centroid())


# ------------------------------------------------------------- one chip
def build(engine, apps):
    from repro.kernels.kmeans_assign import ops as km
    from repro.kernels.segment_stats import ops as seg

    seg._reset_dispatch_record()
    km._reset_dispatch_record()
    exps = engine.build(apps)
    rec = seg.last_dispatch()
    check(rec is not None and rec["interpret"] is False
          and rec["batch"] >= len(apps),
          f"segment_stats did not run compiled over the app batch: {rec}")
    km_rec = km.last_dispatch()
    check(km_rec is not None and km_rec["interpret"] is False
          and km_rec["grid"][0] >= len(apps),
          f"the build's k-means did not run the compiled kernel over the "
          f"app batch: {km_rec}")

    from repro.simcpu import stack_ragged
    labels, valid = stack_ragged([e.bbv_labels for e in exps])
    values, _ = stack_ragged([e.census_mat[0] for e in exps],
                             dtype=np.float32)
    lab = np.where(valid, labels, -1).astype(np.int32)
    s_k, _, c_k = (np.asarray(o) for o in
                   seg.segment_stats(values, lab, L, backend="pallas"))
    s_j, _, c_j = (np.asarray(o) for o in
                   seg.segment_stats(values, lab, L, backend="jnp"))
    rel = float(np.max(np.abs(s_k - s_j)) / np.max(np.abs(s_j)))
    print(f"build: {len(apps)} apps, {int(valid.sum())} regions, "
          f"segment_stats grid {(rec or {}).get('grid')}, kernel-vs-oracle sums "
          f"max rel diff {rel:.3e}")
    check(np.array_equal(c_k, c_j), "segment_stats counts != oracle")
    check(rel <= SUM_REL_BOUND, f"segment_stats sums rel diff {rel:.3e}")
    return exps


def paper_sweep(engine, apps):
    from repro.core.sampling import plan as plan_mod
    from repro.experiments import SweepSpec, run_sweep

    spec = SweepSpec(apps=tuple(apps), plan=rfv_centroid())
    engine.memo.cols_for(engine.configs)
    before = engine.memo.state()
    fused = run_sweep(engine, spec)
    check(plan_mod.last_sweep_dispatch()["fused"] is True,
          "run_sweep did not take the fused megaprogram")
    after_fused = accounting(engine.memo)
    engine.memo.load_state(*before)
    staged = run_sweep(engine, dataclasses.replace(spec, fused=False))
    after_staged = accounting(engine.memo)

    ef, es = fused.column("estimate"), staged.column("estimate")
    rel = float(np.max(np.abs(ef - es) / np.abs(es)))
    print(f"sweep: {len(fused)} rows, fused-vs-staged max rel diff "
          f"{rel:.3e}, max err_pct {np.max(fused.column('err_pct')):.4f}")
    check(len(fused) == len(apps) * len(engine.configs), "sweep row count")
    check(np.array_equal(fused.column("n_units"), staged.column("n_units")),
          "fused n_units != staged")
    check(same_accounting(after_fused, after_staged),
          "fused picks or ledger totals != staged")
    check(rel <= SWEEP_REL_BOUND, f"fused estimates rel diff {rel:.3e}")


def trial_study(engine, apps):
    from repro.experiments import TrialSpec, run_trials

    res = run_trials(engine, TrialSpec(trials=TRIALS), apps=tuple(apps))
    for s, st in res.stats.items():
        check(np.all(np.asarray(st.count) == TRIALS),
              f"{s}: trial counts {np.asarray(st.count)}")
        print(f"trials: {s} coverage per app "
              f"{np.round(res.coverage[s], 4).tolist()}")
    # the coverage gate of tests/test_streaming_trials.py, on the app
    # those tests gate
    a = apps.index(COVERAGE_APP)
    for s in ("random", "rfv"):
        check(res.coverage[s][a] >= COVERAGE_GATE,
              f"{s} coverage on {COVERAGE_APP} {res.coverage[s][a]:.4f} "
              f"< {COVERAGE_GATE}")


def service(engine, apps):
    from repro.experiments import run_sweep
    from repro.serving import SweepService
    from repro.serving.cli import synthetic_stream

    stream = synthetic_stream(12, apps=tuple(apps))
    before = engine.memo.state()
    svc = SweepService(engine)
    ids = []
    for start in range(0, len(stream), 6):
        ids += [svc.submit(spec) for spec in stream[start:start + 6]]
        svc.tick()
    served = [svc.result(i) for i in ids]
    after_service = accounting(engine.memo)
    stats = svc.stats()
    engine.memo.load_state(*before)
    serial = [run_sweep(engine, spec) for spec in stream]
    after_serial = accounting(engine.memo)
    print(f"service: {stats.completed} requests in {stats.ticks} ticks, "
          f"{stats.dispatches} dispatches, {stats.coalesced_requests} "
          f"coalesced")
    check(stats.completed == len(stream), "service left requests pending")
    check(stats.coalesced_requests > 0, "service coalesced nothing")
    for i, (a, b) in enumerate(zip(served, serial)):
        check(np.array_equal(a.column("estimate"), b.column("estimate")),
              f"request {i}: coalesced estimates != serial run_sweep")
    check(same_accounting(after_service, after_serial),
          "service accounting != serial run_sweep")


def kmeans_fits(exps):
    """The build's BBV and RFV fits against the exact reference."""
    from repro.kernels.kmeans_assign.ref import kmeans_assign_ref
    from repro.simcpu import stack_ragged

    for kind, feats in (("bbv", "bbv_feats"), ("rfv", "rfv_z")):
        z, valid = stack_ragged([getattr(e, feats) for e in exps],
                                dtype=np.float32)
        labels, _ = stack_ragged([getattr(e, f"{kind}_labels") for e in exps])
        cents = np.stack([getattr(e, f"{kind}_centroids") for e in exps])
        iters = [getattr(e, f"{kind}_iterations") for e in exps]
        ref = np.asarray(kmeans_assign_ref(z, cents.astype(np.float32))[0])
        agree = float((labels == ref)[valid].mean())
        print(f"kmeans: {kind} fit vs exact reference {agree:.6f}, "
              f"iterations {iters}")
        check(agree >= KMEANS_AGREE_GATE,
              f"{kind} k-means labels vs exact reference {agree:.6f}")
        check(max(iters) < KMEANS_MAX_ITERS,
              f"{kind} k-means lanes at the {KMEANS_MAX_ITERS}-iteration "
              f"cap: {iters}")


# ----------------------------------------------------------- four chips
def mesh_sweep(single, sharded, apps):
    from repro.experiments import SweepSpec, run_sweep

    spec = SweepSpec(apps=tuple(apps), plan=rfv_centroid())
    t1, t4 = run_sweep(single, spec), run_sweep(sharded, spec)
    e1, e4 = t1.column("estimate"), t4.column("estimate")
    print(f"mesh sweep: ('app',) mesh of {sharded.mesh.size}, max |diff| "
          f"vs one device {float(np.max(np.abs(e1 - e4))):.3e}")
    check(np.array_equal(e1, e4), "sharded sweep estimates != one device")
    check(sharded.memo.total_charges() == single.memo.total_charges(),
          "sharded memo charges != one device")
    check(same_accounting(accounting(single.memo, LEDGER),
                          accounting(sharded.memo, LEDGER)),
          "sharded ledger totals != one device")


def mesh_trials(single, sharded, apps, mesh):
    from repro.experiments import TrialSpec, run_trials

    spec = TrialSpec(trials=TRIALS, keep_trials=True)
    r1 = run_trials(single, spec, apps=tuple(apps))
    r4 = run_trials(sharded, spec, apps=tuple(apps), mesh=mesh)
    failed = []
    for s in spec.schemes:
        st1, st4 = r1.stats[s], r4.stats[s]
        for leaf in ("count", "cover", "half_n", "err_hist", "half_hist"):
            if not np.array_equal(np.asarray(getattr(st1, leaf)),
                                  np.asarray(getattr(st4, leaf))):
                failed.append(f"{s}: ('app','trial') {leaf} != one device")
        diff = {}
        for k in ("estimates", "errors", "half_widths"):
            a, b = getattr(r1, k)[s], getattr(r4, k)[s]
            diff[k] = float(np.max(np.abs(a - b)))
            if not np.array_equal(a, b):
                failed.append(f"{s}: ('app','trial') {k} != one device")
        print(f"mesh trials: {s} on {dict(mesh.shape)}, max |diff| vs one "
              f"device: {diff}")
    if not same_accounting(accounting(single.memo, LEDGER),
                           accounting(sharded.memo, LEDGER)):
        failed.append("ledger totals after the trial study differ")
    for what in failed:
        print(f"FAIL: {what}")
    check(not failed, f"{len(failed)} mesh trial check(s) failed")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the ('app',) and ('app', 'trial') "
                    "mesh paths against one device")
    args = ap.parse_args(argv)

    enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{devs[0].platform!r}")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devs)} device(s)")
    print(f"device: {devs[0].device_kind}, {len(devs)} visible, "
          f"jax {jax.__version__}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_event)

    from repro.experiments import ExperimentEngine
    from repro.kernels.backend import BackendFallbackWarning
    from repro.simcpu import APP_NAMES

    warnings.simplefilter("error", BackendFallbackWarning)
    apps = tuple(APP_NAMES)
    if args.chips == 1:
        with jax.default_device(devs[0]):
            engine = ExperimentEngine()
            exps = phase("build", build, engine, apps)
            phase("sweep", paper_sweep, engine, apps)
            phase("trials", trial_study, engine, apps)
            phase("service", service, engine, apps)
            phase("kmeans", kmeans_fits, exps)
    else:
        from repro.launch.mesh import make_app_mesh, make_app_trial_mesh

        single = ExperimentEngine()
        sharded = ExperimentEngine(mesh=make_app_mesh(devices=devs[:4]))
        phase("build (one device)", single.build, apps)
        phase("build (app mesh)", sharded.build, apps)
        phase("mesh sweep", mesh_sweep, single, sharded, apps)
        trial_mesh = make_app_trial_mesh(app_devices=2, devices=devs[:4])
        phase("mesh trials", mesh_trials, single, sharded, apps, trial_mesh)

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
