"""The app bank every driver builds on, and its check against the plain
reference.

``build`` runs ``ExperimentEngine.build`` over a configuration's apps
(census CPI for every config, BBV and RFV k-means strata, Dalenius-
Gurney strata, the phase-1 sample). ``reference_build`` makes the same
bank again from ``bench/reference`` alone, and ``build_readings``
compares the two: populations and phase-1 sample exactly, census truth
to rounding, and the strata by what they claim.
"""

from __future__ import annotations

import warnings

import numpy as np

from bench.reference import perfmodel, population, strata


def build(ctx: dict) -> dict:
    """Build the configuration's bank on the cell's device; returns the
    driver state (``engine``, ``apps``, ``params``, ``build_s``,
    ``kernels``: the shapes of the k-means kernel's calls)."""
    import jax

    from repro.experiments import ExperimentEngine
    from repro.kernels.backend import BackendFallbackWarning
    from repro.simcpu import CONFIGS

    if ctx["devices"][0].platform == "tpu":
        # on the chip every kernel runs compiled; a fallback is a fault
        warnings.simplefilter("error", BackendFallbackWarning)
    cfg = ctx["config"]
    apps = tuple(cfg["apps"])
    engine = ExperimentEngine(configs=[CONFIGS[i] for i in cfg["configs"]],
                              num_strata=int(cfg["num_strata"]))
    t0 = ctx["clock"]()
    with jax.profiler.TraceAnnotation("setup.build"):
        engine.build(apps)
    build_s = ctx["clock"]() - t0
    L = int(cfg["num_strata"])
    # the BBV fit, then the RFV fit, each one program; ``n`` is each
    # app's own count of points, without the stack's padding
    kernels = {"kmeans_assign": [
        dict(n=[int(cfg["n_regions"][a]) for a in apps], k=L,
             d=int(cfg["bbv_projection"])),
        dict(n=[int(cfg["phase1_n"][a]) for a in apps], k=L,
             d=int(cfg["rfv_metrics"]))]}
    return dict(engine=engine, apps=apps, params=ctx["params"],
                build_s=build_s, kernels=kernels)


def extract(state: dict) -> dict:
    """What the check reads of the build, as host arrays."""
    from repro.simcpu import get_population_bank

    engine, apps = state["engine"], state["apps"]
    exps = engine.build(apps)
    bank = get_population_bank(apps)
    return dict(
        apps=apps, features=np.asarray(bank.features),
        n_regions=np.asarray(bank.n_regions),
        truth=np.stack([np.asarray(e.truth, np.float64) for e in exps]),
        idx1=[np.asarray(e.idx1) for e in exps],
        bbv_labels=[np.asarray(e.bbv_labels) for e in exps],
        bbv_centroids=[np.asarray(e.bbv_centroids) for e in exps],
        rfv_labels=[np.asarray(e.rfv_labels) for e in exps],
        rfv_centroids=[np.asarray(e.rfv_centroids) for e in exps],
        dg_labels=[np.asarray(e.dg_labels) for e in exps],
        num_strata=int(exps[0].num_strata))


def reference_build(config: dict, apps) -> dict:
    """The reference's own bank: populations, census CPI per config,
    phase-1 sample, and the BBV / RFV features the strata are fit on."""
    cfgs = [perfmodel.CONFIGS[i] for i in config["configs"]]
    proj = strata.projection_matrix(population.NUM_BLOCKS,
                                    int(config["bbv_projection"]))
    out = dict(pops=[], census=[], idx1=[], bbv_z=[], rfv_z=[], cpi0_1=[])
    for name in apps:
        pop = population.generate(name, seed=int(config["population_seed"]))
        out["pops"].append(pop)
        # the bank holds the features in float32: that is the data
        xf = pop.features.astype(np.float32)
        out["census"].append(np.stack([perfmodel.model(xf, c) for c in cfgs]))
        idx1 = population.phase1_indices(
            pop.spec.n_regions, int(config["phase1_n"][name]),
            seed=int(config["phase1_seed"]))
        out["idx1"].append(idx1)
        out["cpi0_1"].append(perfmodel.model(xf[idx1], cfgs[0]))
        out["rfv_z"].append(strata.standardize(
            perfmodel.rfv(xf[idx1], cfgs[0])))
        out["bbv_z"].append(strata.project_bbvs(population.bbvs(pop), proj))
    return out


def gap(p, r) -> float:
    """Largest relative gap of ``p`` from the reference ``r``."""
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1e-300)))


def build_readings(prog: dict, ref: dict) -> dict:
    """Readings of the build against the reference."""
    feats = np.zeros_like(prog["features"])
    for a, pop in enumerate(ref["pops"]):
        feats[a, :len(pop.features)] = pop.features.astype(np.float32)
    idx_bad = sum(int(np.sum(np.asarray(p) != r)) if len(p) == len(r)
                  else max(len(p), len(r))
                  for p, r in zip(prog["idx1"], ref["idx1"]))
    truth_ref = np.stack([c.mean(axis=1) for c in ref["census"]])
    L = int(prog["num_strata"])
    read = dict(bank_mismatch=float(np.sum(feats != prog["features"])),
                phase1_mismatch=float(idx_bad),
                truth_gap=gap(prog["truth"], truth_ref))
    for kind in ("bbv", "rfv"):
        fits = list(zip(ref[f"{kind}_z"], prog[f"{kind}_labels"],
                        prog[f"{kind}_centroids"]))
        off = [strata.off_centroid_count(z, lab, c) for z, lab, c in fits]
        read[f"{kind}_off_centroid"] = (sum(b for b, _ in off)
                                        / sum(n for _, n in off))
        read[f"{kind}_centroid_gap"] = max(
            strata.centroid_gap(z, lab, c) for z, lab, c in fits)
    read["dg_gap"] = dg_gap(ref["cpi0_1"], prog["dg_labels"], L)
    return read


def dg_gap(baselines, labels, L: int) -> float:
    """Share of phase-1 units whose Dalenius-Gurney stratum is not the
    one the plain rule gives on ``baselines``."""
    bad = sum(int(np.sum(strata.dalenius_gurney(b, L) != np.asarray(lab)))
              for b, lab in zip(baselines, labels))
    return bad / sum(len(b) for b in baselines)


def control_build_readings(config: dict, prog: dict, ref: dict) -> dict:
    """The build's numbers with bfloat16 stand-ins for the program's
    census truth, k-means labels and centroids, and the baseline its
    Dalenius-Gurney strata are cut along."""
    import jax.numpy as jnp
    import ml_dtypes

    cfgs = [perfmodel.CONFIGS[i] for i in config["configs"]]
    truth16 = np.stack([
        [float(jnp.mean(perfmodel.model(jnp.asarray(p.features, jnp.float32),
                                        c, xp=jnp, dtype=jnp.bfloat16)
                        .astype(jnp.float32))) for c in cfgs]
        for p in ref["pops"]])
    truth = np.stack([c.mean(axis=1) for c in ref["census"]])

    def off(kind):
        bad = tot = 0
        for z, c in zip(ref[f"{kind}_z"], prog[f"{kind}_centroids"]):
            b, n = strata.off_centroid_count(
                z, strata.control_labels(z, c), c)
            bad, tot = bad + b, tot + n
        return bad / tot

    L = int(config["num_strata"])
    read = dict(truth_gap=gap(truth16, truth))
    for kind in ("bbv", "rfv"):
        read[f"{kind}_off_centroid"] = off(kind)
        read[f"{kind}_centroid_gap"] = max(
            strata.centroid_gap(z, lab, strata.control_centroids(z, lab, L))
            for z, lab in zip(ref[f"{kind}_z"], prog[f"{kind}_labels"]))
    b16 = [np.asarray(b, np.float64).astype(ml_dtypes.bfloat16)
           .astype(np.float64) for b in ref["cpi0_1"]]
    read["dg_gap"] = dg_gap(b16, [strata.dalenius_gurney(b, L)
                                  for b in ref["cpi0_1"]], L)
    return read


def check(readings, config: dict, params: dict, prog: dict, outputs: list,
          seed: int) -> list:
    """[(name, reading, limit)] of every number a cell compares: the
    build's readings and the driver's ``readings`` of the window, each
    beside its limit in the cell's ``params["limits"]`` (a number not
    read compares as infinite)."""
    ref = reference_build(config, prog["apps"])
    read = build_readings(prog, ref)
    read.update(readings(config, params, prog, ref, outputs, seed))
    return [(k, float(read.get(k, float("inf"))), float(v))
            for k, v in params["limits"].items()]
