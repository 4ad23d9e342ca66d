"""Driver of Monte-Carlo study traffic on an ``("app", "trial")`` mesh.

The traffic of ``drivers/trials.py`` (one ``run_trials`` study per
request) on the deployment the configuration's ``mesh`` names: set-up
builds the bank with ``ExperimentEngine(mesh=make_app_trial_mesh(...))``
over the cell's devices, so the census, the BBV and RFV k-means fits and
the phase-1 measurement run app-sharded, and every study's scan splits
its chunks over the trial axis and merges its statistics with a
``psum``. Requests, digests and the check against the plain reference
are ``drivers/trials.py``'s: the deployment's answers are one chip's.

The check adds one number: ``census_mismatch``, the census CPI values
of the mesh's build whose bits differ from the same census computed on
one device, the agreement an app-sharded build promises.
"""

from __future__ import annotations

import warnings

import numpy as np

from bench.lib import bank, registry

_trials = registry.driver("trials")

SPANS = _trials.SPANS
warm = _trials.warm
request = _trials.request
digest = _trials.digest


def setup(ctx: dict) -> dict:
    """Build the configuration's bank on a mesh over the cell's devices;
    the driver state of ``bank.build``, with ``kernels`` the k-means
    calls of one device: the apps of its app shard."""
    import jax

    from repro.experiments import ExperimentEngine
    from repro.kernels.backend import BackendFallbackWarning
    from repro.launch.mesh import make_app_trial_mesh, mesh_tag
    from repro.simcpu import CONFIGS

    if ctx["devices"][0].platform == "tpu":
        # on the chip every kernel runs compiled; a fallback is a fault
        warnings.simplefilter("error", BackendFallbackWarning)
    cfg = ctx["config"]
    shape = cfg["mesh"]
    mesh = make_app_trial_mesh(app_devices=int(shape["app"]),
                               devices=ctx["devices"])
    want = "x".join(f"{axis}{int(n)}" for axis, n in shape.items())
    if mesh_tag(mesh) != want:
        raise ValueError(f"{len(ctx['devices'])} devices make the mesh "
                         f"{mesh_tag(mesh)}, not the configuration's {want}")
    apps = tuple(cfg["apps"])
    engine = ExperimentEngine(configs=[CONFIGS[i] for i in cfg["configs"]],
                              num_strata=int(cfg["num_strata"]), mesh=mesh)
    t0 = ctx["clock"]()
    with jax.profiler.TraceAnnotation("setup.build"):
        engine.build(apps)
    build_s = ctx["clock"]() - t0
    # the first device holds the first app shard, padded like the app
    # axis of every sharded program
    per_device = -(-len(apps) // int(shape["app"]))
    own = apps[:per_device]
    L = int(cfg["num_strata"])
    kernels = {"kmeans_assign": [
        dict(n=[int(cfg["n_regions"][a]) for a in own], k=L,
             d=int(cfg["bbv_projection"])),
        dict(n=[int(cfg["phase1_n"][a]) for a in own], k=L,
             d=int(cfg["rfv_metrics"]))]}
    return dict(engine=engine, apps=apps, params=ctx["params"],
                build_s=build_s, kernels=kernels)


def extract(state: dict) -> dict:
    """``bank.extract``, with the mesh build's census CPI and the same
    census computed on one device (``census``, ``census_one``: (A, C, N)
    over each app's own regions, flattened)."""
    from repro.simcpu import config_matrix, cpi_bank, get_population_bank

    out = bank.extract(state)
    engine, apps = state["engine"], state["apps"]
    pop = get_population_bank(apps)
    one = cpi_bank(pop.features, config_matrix(engine.configs))
    out["census"] = np.concatenate([e.census_mat.ravel()
                                    for e in engine.build(apps)])
    out["census_one"] = np.concatenate([one[a, :, :n].ravel() for a, n
                                        in enumerate(pop.n_regions)])
    return out


def census_mismatch(prog: dict) -> float:
    """Census values whose bits differ between the mesh and one device."""
    a, b = prog["census"], prog["census_one"]
    if a.shape != b.shape:
        return float(max(a.size, b.size))
    return float(np.sum(a.view(np.uint32) != b.view(np.uint32)))


def readings(config: dict, params: dict, prog: dict, ref: dict,
             outputs: list, seed: int, dtype=None) -> dict:
    """``drivers/trials.py``'s readings and ``census_mismatch``; with
    ``dtype`` (the control) the reference's census in that precision
    stands in for the mesh's."""
    read = _trials.readings(config, params, prog, ref, outputs, seed,
                            dtype=dtype)
    if dtype is not None:
        import jax.numpy as jnp

        prog = dict(prog, census=np.concatenate([
            jnp.asarray(c, dtype).astype(jnp.float32).ravel()
            for c in ref["census"]]))
    read["census_mismatch"] = census_mismatch(prog)
    return read


def check(config: dict, params: dict, prog: dict, outputs: list,
          seed: int) -> list:
    """[(name, reading, limit)] of every number compared."""
    return bank.check(readings, config, params, prog, outputs, seed)
