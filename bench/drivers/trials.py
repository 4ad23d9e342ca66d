"""Driver of Monte-Carlo study traffic: ``run_trials`` studies back to back.

Set-up builds the configuration's app bank once with
``ExperimentEngine.build`` (census, BBV and RFV k-means strata,
Dalenius-Gurney strata, the phase-1 sample) and warms the one study
shape the cell sends. A request is one study: ``run_trials`` over every
app and scheme with ``TrialSpec(trials, schemes, config_index,
keep_trials, seed)``, its seed drawn from the run's ``--seed`` and the
request's position. Its work is trials x apps x schemes trial-lanes.

The check, after the window, holds the program to a plain reference of
the same semantics (``bench/reference``): the bank's populations and
phase-1 sample exactly, the census truth, the strata by what they
claim, and a sample of the window's studies, drawn from the seed,
statistic by statistic.
"""

from __future__ import annotations

import hashlib

import numpy as np

from bench.lib import bank
from bench.reference import trials as ref_trials

SPANS = ("setup", "setup.*", "window", "study")
WARM_SEED = 12345


def study_seed(seed: int, i: int) -> int:
    """The ``TrialSpec.seed`` of request ``i`` of a run with ``seed``."""
    return int(np.random.SeedSequence([int(seed), int(i)])
               .generate_state(1)[0] & 0x7FFFFFFF)


def _spec(params: dict, seed: int):
    from repro.experiments import TrialSpec

    return TrialSpec(trials=int(params["trials"]),
                     units_per_trial=int(params["units_per_trial"]),
                     schemes=tuple(params["schemes"]),
                     config_index=int(params["config_index"]),
                     keep_trials=params.get("keep_trials"), seed=seed)


setup = bank.build
extract = bank.extract


def warm(state: dict) -> None:
    """One study of the window's shape (the same seed every run)."""
    from repro.experiments import run_trials

    run_trials(state["engine"], _spec(state["params"], WARM_SEED),
               apps=state["apps"])


def request(state: dict, seed: int, i: int) -> dict:
    """Study ``i``: returns its work and the host copy of its output."""
    from repro.experiments import run_trials

    s = study_seed(seed, i)
    res = run_trials(state["engine"], _spec(state["params"], s),
                     apps=state["apps"])
    stats = {sch: {k: np.asarray(v) for k, v in vars(st).items()}
             for sch, st in res.stats.items()}
    out = {"seed": s, "stats": stats}
    if res.estimates:
        out["estimates"] = {k: np.asarray(v) for k, v in res.estimates.items()}
    p = state["params"]
    return {"work": p["trials"] * len(state["apps"]) * len(p["schemes"]),
            "out": out}


def digest(outputs: list) -> str:
    """sha256 over every study's statistics, in window order."""
    h = hashlib.sha256()
    for o in outputs:
        for sch in sorted(o["stats"]):
            for k in sorted(o["stats"][sch]):
                h.update(np.ascontiguousarray(o["stats"][sch][k]).tobytes())
    return h.hexdigest()


# ------------------------------------------------------------------ check
def _stack(rows, width=None, dtype=np.float64):
    width = width or max(len(r) for r in rows)
    out = np.zeros((len(rows), width), dtype)
    valid = np.zeros((len(rows), width), bool)
    for a, r in enumerate(rows):
        out[a, :len(r)] = r
        valid[a, :len(r)] = True
    return out, valid


def study_inputs(config: dict, ref: dict, prog: dict, ci: int) -> dict:
    """Inputs of the reference study: the reference's census, truth and
    value pools, with the program's strata (checked apart)."""
    census, _ = _stack([c[ci] for c in ref["census"]])
    base_all, _ = _stack([c[0] for c in ref["census"]])
    L = int(config["num_strata"])
    truth = np.asarray([c[ci].mean() for c in ref["census"]])
    pool1, v1 = _stack([c[ci][i] for c, i in zip(ref["census"], ref["idx1"])])
    base1, _ = _stack(ref["cpi0_1"])
    bbv_lab, bbv_v = _stack(prog["bbv_labels"], dtype=np.int64)
    rfv_lab, _ = _stack(prog["rfv_labels"], dtype=np.int64)
    dg_lab, _ = _stack(prog["dg_labels"], dtype=np.int64)
    return {
        "census": census, "truth": truth,
        "n_regions": np.asarray([len(c[0]) for c in ref["census"]]),
        "bbv": dict(labels=bbv_lab, valid=bbv_v, pool=census,
                    baseline=base_all, num_strata=L),
        "rfv": dict(labels=rfv_lab, valid=v1, pool=pool1, baseline=base1,
                    num_strata=L),
        "dg": dict(labels=dg_lab, valid=v1, pool=pool1, baseline=base1,
                   num_strata=L),
    }


def compare_study(prog: dict, ref: dict) -> dict:
    """Per-statistic readings of one study: program against reference."""
    g = dict(count_gap=0.0, cover_gap=0.0, moment_gap=0.0, hist_gap=0.0)
    for sch, r in ref.items():
        p = prog["stats"][sch]
        n = np.maximum(r["count"], 1)
        g["count_gap"] = max(g["count_gap"],
                             float(np.max(np.abs(p["count"] - r["count"]))),
                             float(np.max(np.abs(p["half_n"] - r["half_n"]))))
        g["cover_gap"] = max(g["cover_gap"], float(
            np.max(np.abs(p["cover"] - r["cover"]) / n)))
        for k in ("err_sum", "err_sumsq", "half_sum", "half_sumsq"):
            g["moment_gap"] = max(g["moment_gap"], bank.gap(p[k], r[k]))
        for k in ("err_hist", "half_hist"):
            moved = np.abs(np.asarray(p[k], np.float64) - r[k]).sum(-1) / 2
            g["hist_gap"] = max(g["hist_gap"], float(np.max(moved / n)))
        if "estimates" in r:
            g["estimate_gap"] = max(g.get("estimate_gap", 0.0), bank.gap(
                prog["estimates"][sch], r["estimates"]))
    return g


def sample_studies(outputs: list, seed: int, n: int) -> list[int]:
    """Positions of the window's studies the check compares, drawn from
    the seed (all of them where the window holds no more than ``n``)."""
    if len(outputs) <= n:
        return list(range(len(outputs)))
    rng = np.random.default_rng([int(seed), 7])
    return sorted(rng.choice(len(outputs), size=n, replace=False).tolist())


def readings(config: dict, params: dict, prog: dict, ref: dict,
             outputs: list, seed: int, dtype=None) -> dict:
    """Readings of a sample of the window's studies, drawn from the seed,
    against the reference study. With ``dtype`` (bfloat16) the
    reference in that precision stands in for the program: the
    control."""
    import jax.numpy as jnp

    inputs = study_inputs(config, ref, prog, int(params["config_index"]))
    keep = bool(outputs) and "estimates" in outputs[0]
    kw = dict(units_per_trial=int(params["units_per_trial"]), keep=keep)
    gaps: dict = {}
    for pos in sample_studies(outputs, seed, int(params["check_studies"])):
        o = outputs[pos]
        r = ref_trials.study(inputs, o["seed"], int(params["trials"]),
                             params["schemes"], **kw)
        if dtype is not None:
            c = ref_trials.study(inputs, o["seed"], int(params["trials"]),
                                 params["schemes"], xp=jnp, dtype=dtype,
                                 **kw)
            o = {"stats": c}
            if keep:
                o["estimates"] = {s: c[s]["estimates"] for s in c}
        for k, v in compare_study(o, r).items():
            gaps[k] = max(gaps.get(k, 0.0), v)
    return gaps


def check(config: dict, params: dict, prog: dict, outputs: list,
          seed: int) -> list:
    """[(name, reading, limit)] of every number compared."""
    return bank.check(readings, config, params, prog, outputs, seed)
