#!/usr/bin/env python3
"""One-device and mesh builds of a bank give the same bits.

    python scripts/mesh_agreement.py run --layout none --out one.json
    python scripts/mesh_agreement.py run --layout app2xtrial2 --out x4.json
    python scripts/mesh_agreement.py compare one.json x4.json

``run`` builds a benchmark configuration's bank (default
``bench/configs/spec17int_k20.json``) with ``ExperimentEngine`` on one
layout, one process per layout: ``none`` on the first device, or
``app<a>xtrial<t>`` on a ``make_app_trial_mesh(app_devices=a)`` over the
first ``a * t`` devices. It then runs ``run_trials`` at fixed study seeds,
for each trial count of ``--trials`` in turn, on one engine, and writes
a sha256 of each build output and of each statistic of each study (and
of its dense per-trial arrays, which a study keeps up to 8192 trials),
a digest of each study (its statistics in the order of
``bench/drivers/trials.py``'s digest), and the engine's study-memo hit
and miss counts where the program has them. ``compare`` lists what
differs between two such files (two layouts, or two trees on one
layout) and exits non-zero if anything does.

On a TPU the k-means kernels must run compiled: a fallback to the
oracle is an error. The persistent compile cache is on, as in a
benchmark run.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

BUILD_FIELDS = ("truth", "census_mat", "idx1", "cpi0_1", "bbv_feats",
                "bbv_labels", "bbv_centroids", "rfv_z", "rfv_labels",
                "rfv_centroids", "dg_labels")


def sha(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def run(args) -> dict:
    import jax

    from repro.experiments import ExperimentEngine, TrialSpec, run_trials
    from repro.kernels.backend import BackendFallbackWarning
    from repro.launch.mesh import make_app_trial_mesh, mesh_tag
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.simcpu import CONFIGS

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform == "tpu":
        warnings.simplefilter("error", BackendFallbackWarning)
    with open(args.config) as fh:
        cfg = json.load(fh)
    apps = tuple(cfg["apps"])
    mesh = None
    if args.layout != "none":
        app, trial = (int(x) for x in
                      args.layout.removeprefix("app").split("xtrial"))
        mesh = make_app_trial_mesh(app_devices=app,
                                   devices=devs[:app * trial])
        if mesh_tag(mesh) != args.layout:
            raise SystemExit(f"{len(devs)} devices make {mesh_tag(mesh)}, "
                             f"not {args.layout}")
    out = {"layout": mesh_tag(mesh), "device": devs[0].device_kind,
           "apps": apps, "build": {}, "studies": []}
    with jax.default_device(devs[0]):
        engine = ExperimentEngine(
            configs=[CONFIGS[i] for i in cfg["configs"]],
            num_strata=int(cfg["num_strata"]), mesh=mesh)
        t0 = time.perf_counter()
        exps = engine.build(apps)
        out["build_s"] = time.perf_counter() - t0
        for f in BUILD_FIELDS:
            out["build"][f] = sha(*(getattr(e, f) for e in exps))
        for trials in args.trials:
            for seed in args.seeds:
                spec = TrialSpec(trials=trials, seed=seed)
                t0 = time.perf_counter()
                res = run_trials(engine, spec, apps=apps)
                study = {"trials": trials, "seed": seed,
                         "s": time.perf_counter() - t0, "stats": {}}
                digest = hashlib.sha256()
                for sch in sorted(res.stats):
                    st = vars(res.stats[sch])
                    for k in sorted(st):
                        study["stats"][f"{sch}.{k}"] = sha(st[k])
                        digest.update(np.ascontiguousarray(st[k]).tobytes())
                    for field in ("estimates", "errors", "half_widths"):
                        if sch in getattr(res, field):
                            study["stats"][f"{sch}.{field}"] = sha(
                                getattr(res, field)[sch])
                study["digest"] = digest.hexdigest()
                out["studies"].append(study)
                print(f"{out['layout']} {trials} trials, seed {seed}: "
                      f"{study['s']:.3f} s, digest {study['digest']}",
                      flush=True)
        try:
            from repro.experiments.montecarlo import study_memo
            memo = study_memo(engine)
            out["study_memo"] = {"hits": memo.hits, "misses": memo.misses}
        except ImportError:
            out["study_memo"] = None
    return out


def compare(a: dict, b: dict) -> list[str]:
    diff = [f"build {f}" for f in BUILD_FIELDS
            if a["build"][f] != b["build"][f]]
    if ([(s.get("trials"), s["seed"]) for s in a["studies"]]
            != [(s.get("trials"), s["seed"]) for s in b["studies"]]):
        return diff + ["the two files ran other study seeds"]
    for sa, sb in zip(a["studies"], b["studies"]):
        diff += [f"seed {sa['seed']} {k}" for k in sa["stats"]
                 if sa["stats"][k] != sb["stats"].get(k)]
        if sa["digest"] != sb["digest"]:
            diff.append(f"seed {sa['seed']} digest")
    return diff


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("run")
    r.add_argument("--layout", default="none")
    r.add_argument("--config", default=os.path.join(
        ROOT, "bench", "configs", "spec17int_k20.json"))
    r.add_argument("--trials", type=lambda s: [int(x) for x in s.split(",")],
                   default=[100_000])
    r.add_argument("--seeds", type=lambda s: [int(x) for x in s.split(",")],
                   default=[1501, 1502, 1503])
    r.add_argument("--out", required=True)
    c = sub.add_parser("compare")
    c.add_argument("files", nargs=2)
    args = ap.parse_args(argv)
    if args.cmd == "run":
        out = run(args)
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
        return 0
    with open(args.files[0]) as fa, open(args.files[1]) as fb:
        a, b = json.load(fa), json.load(fb)
    diff = compare(a, b)
    n = sum(len(s["stats"]) + 1 for s in a["studies"]) + len(BUILD_FIELDS)
    print(f"{a['layout']} vs {b['layout']}: {n - len(diff)} of {n} equal; "
          f"study memo {a.get('study_memo')} and {b.get('study_memo')}")
    for d in diff:
        print(f"differs: {d}")
    return 1 if diff else 0


if __name__ == "__main__":
    sys.exit(main())
