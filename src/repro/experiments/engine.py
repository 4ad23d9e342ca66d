"""App-sharded batched experiment engine over the simulation substrate.

The engine treats the application axis as a data-parallel array dimension:
``build(names)`` stacks every requested app's population into one
``(A, N, F)`` device array (``PopulationBank``) and runs each build phase
as ONE batched-over-app program —

* census ground truth: ``cpi_bank`` vmapped over (app, config, region);
* BBV projection + k-means: ``random_project``/``kmeans_bank`` vmapped
  over the app axis with zero-weight padding rows;
* phase-1 SRS measurement: one ``rfv_bank`` dispatch for all apps'
  phase-1 samples, charged through the shared ``MemoBank``;
* RFV standardization + k-means: masked batched z-scoring + weighted
  ``kmeans_bank``.

With a 1-D ``("app",)`` mesh (``repro.launch.mesh.make_app_mesh``) each of
those programs is ``shard_map``-ped so apps run device-parallel; the
single-device path is the default and produces identical results (lanes
never communicate). Dalenius-Gurney stratification stays a host-side
scalar algorithm per app (it is an iterative boundary search on a few
thousand values, not a device program).

Per-app state is exposed exactly as before through ``AppExperiment`` — a
view slicing the stacked arrays back to one app — so figure code keeps
reading ``exp.bbv_labels`` etc. while sweeps and Monte-Carlo trials use
the stacked arrays directly.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional, Sequence

import jax
import numpy as np

from ..core.clustering import kmeans_bank, kmeans_batch, random_project
from ..core.sampling import dalenius_gurney_strata, draw_srs
from ..core.sampling import plan as sampling_plan
from ..simcpu import (APP_NAMES, CONFIGS, CachedSimulator, MemoBank,
                      config_matrix, cpi_bank, get_population_bank,
                      make_simulator, rfv_bank, stack_ragged)

NUM_STRATA = 20
PHASE1_SEED = 42

__all__ = [
    "NUM_STRATA", "PHASE1_SEED", "AppExperiment", "SweepStack",
    "ExperimentEngine", "stratum_tables",
    "plan_selection", "plan_selection_bank",
    "scheme_selection", "scheme_selection_bank",
]


@dataclasses.dataclass
class AppExperiment:
    """Per-application view shared by every figure/sweep."""

    name: str
    sim: CachedSimulator
    configs: tuple                # the sweep's config axis
    truth: np.ndarray             # (C,) census mean CPI per config
    census_mat: np.ndarray        # (C, N) census CPI (analysis-only)
    # BBV stratification (census, SimPoint-style)
    bbv_labels: np.ndarray        # (N,)
    bbv_weights: np.ndarray       # (L,)
    bbv_feats: np.ndarray         # projected (N, 15)
    bbv_centroids: np.ndarray
    # phase-1 sample + RFV stratification
    idx1: np.ndarray
    cpi0_1: np.ndarray            # baseline CPI of phase-1 units
    rfv_z: np.ndarray             # standardized RFVs of phase-1 units
    rfv_labels: np.ndarray
    rfv_weights: np.ndarray
    rfv_centroids: np.ndarray
    # Dalenius-Gurney on baseline CPI (phase-1 sample)
    dg_labels: np.ndarray
    dg_weights: np.ndarray
    num_strata: int = NUM_STRATA
    # Lloyd iterations of the BBV / RFV k-means fits (the fit's
    # max_iters means it stopped at the cap, not at convergence)
    bbv_iterations: int = 0
    rfv_iterations: int = 0

    def cpi(self, cfg_i: int, indices) -> np.ndarray:
        """(n,) CPI for one config, through the memo table."""
        return self.sim.simulate_cpi(indices, self.configs[cfg_i])

    def cpi_for(self, indices,
                config_indices: Optional[Sequence[int]] = None) -> np.ndarray:
        """(C', n) CPI for a config subset in one batched dispatch.

        Only the requested configs are simulated (and ledger-charged)."""
        cfgs = (self.configs if config_indices is None
                else tuple(self.configs[i] for i in config_indices))
        return self.sim.simulate_cpi_batch(indices, cfgs)

    def cpi_all(self, indices) -> np.ndarray:
        """(C, n) CPI across ALL configs in one batched dispatch."""
        return self.cpi_for(indices)

    def weighted_cpi_all(self, selected: Sequence[np.ndarray], weights,
                         *, config_indices: Optional[Sequence[int]] = None,
                         strict: bool = False) -> np.ndarray:
        """(C',) stratified weighted-mean CPI per config, one dispatch.

        ``selected``: per-stratum population index arrays (any count per
        stratum). Strata with no selected units renormalize the estimate
        by the covered weight — with the same warn/raise contract as
        ``weighted_point_estimate`` so the bias can't pass silently. When
        EVERY stratum is empty there is nothing to renormalize to: that
        raises under ``strict=True`` and otherwise warns and returns NaN
        estimates.
        """
        n_cfg = len(self.configs) if config_indices is None \
            else len(tuple(config_indices))
        weights = np.asarray(weights, np.float64)
        sel = [np.atleast_1d(np.asarray(s)) for s in selected]
        nonempty = [s for s in sel if s.size]
        if not nonempty:
            msg = ("every stratum selection is empty; no units to "
                   "estimate from")
            if strict:
                raise ValueError(msg)
            warnings.warn(msg, UserWarning, stacklevel=2)
            return np.full(n_cfg, np.nan)
        flat = np.concatenate(nonempty)
        seg = np.concatenate([np.full(s.size, h, np.int64)
                              for h, s in enumerate(sel) if s.size])
        counts = np.bincount(seg, minlength=len(sel))
        covered = float(weights[counts > 0].sum())
        total = float(weights.sum())
        if covered < total * (1.0 - 1e-6):
            msg = (f"selected units cover only {covered / total:.4f} of the "
                   "stratum weight; renormalizing biases the estimate "
                   "toward the covered strata")
            if strict:
                raise ValueError(msg)
            warnings.warn(msg, UserWarning, stacklevel=2)
        mat = self.cpi_for(flat, config_indices)
        w_per_unit = np.where(counts[seg] > 0,
                              weights[seg] / np.maximum(counts[seg], 1), 0.0)
        return (mat * w_per_unit[None, :]).sum(axis=1) / covered

    def census(self, cfg_i: int) -> np.ndarray:
        """(N,) census CPI of every region for config ``cfg_i``
        (analysis-only ground truth, never ledger-charged)."""
        return self.census_mat[cfg_i]


@dataclasses.dataclass(frozen=True)
class SweepStack:
    """Stacked per-app arrays backing the engine's batched dispatch paths."""

    names: tuple[str, ...]
    rows: np.ndarray            # (A,) MemoBank rows
    n_regions: np.ndarray       # (A,)
    feats: np.ndarray           # (A, N_max, F) float32 (zero-padded)
    region_mask: np.ndarray     # (A, N_max) bool
    idx1: np.ndarray            # (A, n1_max) phase-1 indices (padded)
    idx1_valid: np.ndarray      # (A, n1_max) bool

    @property
    def num_apps(self) -> int:
        """Number of apps (A) stacked in this view."""
        return len(self.names)

    def gather_feats(self, idx: np.ndarray) -> np.ndarray:
        """(A, K, F) features at per-app region indices (padding-safe)."""
        return self.feats[np.arange(len(self.names))[:, None], idx]


def _segment_sums_counts(labels: np.ndarray, valid: np.ndarray,
                         num_strata: int, values: np.ndarray,
                         precision=None) -> tuple[np.ndarray, np.ndarray]:
    """(A, L) per-stratum value sums AND counts over valid entries, from
    ONE batched ``segment_stats`` dispatch (the Pallas kernel on TPU, the
    jnp oracle elsewhere — ``repro.kernels.segment_stats``).

    This is the engine's stratum-summary hot path: every build/selection
    summarization (stratum weights, centroid targets, gather tables)
    routes through the same kernel contract the estimator tables use.
    Dtypes follow the ``PrecisionPolicy`` (``repro.core.precision``;
    default f32 trace / f64 host): the kernel computes in the trace
    dtype — counts are exact below 2^24 per stratum, and f32 value sums
    carry ~1e-7 relative rounding — so selection keys built from them
    (dg centroids, mean-policy targets, CI ordering keys) are
    trace-dtype-stable by design, not bit-equal to a float64 bincount.
    Results come home in the policy's host dtype.
    """
    from ..core.precision import resolve_precision
    from ..kernels.segment_stats.ops import segment_stats

    pp = resolve_precision(precision)
    lab = np.where(valid, labels, -1).astype(np.int32)
    with pp.x64_context():
        sums, _, counts = segment_stats(np.asarray(values, pp.trace_dtype),
                                        lab, num_strata, precision=pp)
    return (np.asarray(sums[..., 0], pp.host_dtype),
            np.asarray(counts, pp.host_dtype))


def _offset_bincount(labels: np.ndarray, valid: np.ndarray,
                     num_strata: int, weights=None) -> np.ndarray:
    """(A, L) per-app stratum counts — or weighted sums — over valid
    entries (one ``_segment_sums_counts`` dispatch)."""
    if weights is None:
        return _segment_sums_counts(labels, valid, num_strata,
                                    np.ones(labels.shape))[1]
    return _segment_sums_counts(labels, valid, num_strata, weights)[0]


def stratum_tables(labels: np.ndarray, valid: np.ndarray, num_strata: int,
                   counts: Optional[np.ndarray] = None
                   ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-stratum gather tables for an (A, n) label stack.

    Returns ``(order, offsets, counts)``: stratum ``h`` of app ``a`` owns
    positions ``order[a, offsets[a, h] : offsets[a, h] + counts[a, h]]``,
    in index order (invalid entries sort last). Shared by vectorized
    selection and the Monte-Carlo trial engine so draw indexing can never
    drift between the two. Callers that already hold the stratum counts
    from a ``_segment_sums_counts`` dispatch pass them via ``counts`` to
    avoid a second dispatch. NOTE: for trailing empty strata ``offsets``
    equals the row width — gathers must clamp (empty strata are masked
    out of every consumer anyway)."""
    if counts is None:
        counts = _offset_bincount(labels, valid, num_strata)
    counts = np.asarray(counts).astype(np.int64)
    order = np.argsort(np.where(valid, labels, num_strata), axis=1,
                       kind="stable")
    offsets = np.cumsum(counts, axis=1) - counts
    return order, offsets, counts


class ExperimentEngine:
    """Builds ``AppExperiment`` state batched over apps; runs batched sweeps.

    ``mesh``: optional ``("app",)`` mesh — every batched build/sweep
    dispatch is then ``shard_map``-ped over the app axis — or a 2-D
    ``("app", "trial")`` mesh, which additionally splits Monte-Carlo
    trial chunks across the second axis (``run_trials``; build/sweep
    dispatches treat such a mesh as app-only). ``None`` (the default)
    runs the identical programs on one device.
    """

    @classmethod
    def auto(cls, **kwargs) -> "ExperimentEngine":
        """Engine with an ``("app",)`` mesh when >1 device is present —
        the way ``repro.paper`` and the examples pick up
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
        if "mesh" not in kwargs:
            mesh = None
            if len(jax.devices()) > 1:
                from ..launch.mesh import make_app_mesh
                mesh = make_app_mesh()
            kwargs["mesh"] = mesh
        return cls(**kwargs)

    def __init__(self, *, configs: Sequence = CONFIGS,
                 num_strata: int = NUM_STRATA,
                 phase1_seed: int = PHASE1_SEED,
                 mesh=None, precision=None):
        self.configs = tuple(configs)
        self.num_strata = num_strata
        self.phase1_seed = phase1_seed
        self.mesh = mesh
        # engine-wide PrecisionPolicy override; None defers to each
        # pipeline's default (trials: DEFAULT_PRECISION, sweep estimates:
        # PrecisionPolicy.host_parity) — see repro.core.precision
        self.precision = precision
        self.memo = MemoBank()
        self._apps: dict[tuple[str, int], AppExperiment] = {}
        self._stacks: dict[tuple[tuple[str, ...], int], SweepStack] = {}

    def app(self, name: str, kmeans_seed: int = 0) -> AppExperiment:
        """The ``AppExperiment`` view for one app (built on demand)."""
        return self.build((name,), kmeans_seed)[0]

    def apps(self, names: Optional[Sequence[str]] = None
             ) -> list[AppExperiment]:
        """Views for ``names`` (default: all paper apps), built batched."""
        return self.build(tuple(names or APP_NAMES))

    def build(self, names: Sequence[str],
              kmeans_seed: int = 0) -> list[AppExperiment]:
        """Batched build: every not-yet-built app in ``names`` is
        constructed in ONE set of stacked-over-app programs."""
        names = tuple(names)
        todo = tuple(dict.fromkeys(
            n for n in names if (n, kmeans_seed) not in self._apps))
        if todo:
            self._build_stacked(todo, kmeans_seed)
        return [self._apps[(n, kmeans_seed)] for n in names]

    def stack(self, names: Sequence[str],
              kmeans_seed: int = 0) -> SweepStack:
        """Stacked view over (already built) apps for batched dispatches."""
        names = tuple(names)
        key = (names, kmeans_seed)
        if key not in self._stacks:
            exps = self.build(names, kmeans_seed)
            bank = get_population_bank(names)
            idx1, idx1_valid = stack_ragged([e.idx1 for e in exps])
            self._stacks[key] = SweepStack(
                names=names,
                rows=np.asarray([e.sim.row for e in exps], np.int64),
                n_regions=bank.n_regions, feats=bank.features,
                region_mask=bank.mask, idx1=idx1, idx1_valid=idx1_valid)
        return self._stacks[key]

    # ------------------------------------------------------------------ build
    def _build_stacked(self, names: tuple[str, ...], kmeans_seed: int) -> None:
        """Build the apps ``names`` as one stack; profiler spans
        ``build.population``, ``build.census``, ``build.bbv``,
        ``build.phase1``, ``build.rfv`` and ``build.dg`` mark its
        stages."""
        from ..simcpu import get_bbvs

        L = self.num_strata
        mesh = self.mesh
        with jax.profiler.TraceAnnotation("build.population"):
            bank = get_population_bank(names)
            a_n = bank.num_apps
            ar = np.arange(a_n)

            sims = []
            for name, pop in zip(names, bank.pops):
                base = make_simulator(name)
                row = self.memo.add_app(name, pop.n_regions, base.ledger)
                sims.append(CachedSimulator(base, bank=self.memo, row=row))

        # census ground truth for every config: one vmapped program
        # (analysis-only — free of charge, bypasses the charged memo)
        with jax.profiler.TraceAnnotation("build.census"):
            census = cpi_bank(bank.features, config_matrix(self.configs),
                              mesh=mesh)                   # (A, C, N)
            truth = np.where(bank.mask[:, None, :], census, 0.0).sum(
                axis=2, dtype=np.float64) / bank.n_regions[:, None]

        # SimPoint-style BBV stratification over the full populations
        with jax.profiler.TraceAnnotation("build.bbv"):
            bbvs, _ = stack_ragged([get_bbvs(p) for p in bank.pops],
                                   dtype=np.float32)
            z = np.asarray(_project_bank(bbvs, mesh=mesh))  # (A, N, 15)
            bbv_fit = kmeans_bank(z, L, weights=bank.mask.astype(np.float32),
                                  seed=kmeans_seed, mesh=mesh)
            bbv_counts = _offset_bincount(bbv_fit.labels, bank.mask, L)
            bbv_w = bbv_counts / bank.n_regions[:, None]

        # phase 1: SRS at the paper's Table II sizes, measured on config 0
        # as ONE stacked dispatch, charged through the shared memo bank
        with jax.profiler.TraceAnnotation("build.phase1"):
            idx1_list = [draw_srs(np.random.default_rng(self.phase1_seed),
                                  pop.n_regions, pop.spec.phase1_n)
                         for pop in bank.pops]
            idx1, idx1_valid = stack_ragged(idx1_list)
            cpi0, rfv = rfv_bank(bank.features[ar[:, None], idx1],
                                 self.configs[0], mesh=mesh)
            rows = np.asarray([s.row for s in sims], np.int64)
            self.memo.fill(rows, idx1, idx1_valid, (self.configs[0],),
                           values=cpi0[:, None, :])

        # RFV stratification: masked batched z-scoring + weighted k-means
        with jax.profiler.TraceAnnotation("build.rfv"):
            n1 = idx1_valid.sum(axis=1)                    # (A,)
            v3 = idx1_valid[:, :, None]
            mean = np.where(v3, rfv, 0.0).sum(1) / n1[:, None]
            var = np.where(v3, (rfv - mean[:, None, :]) ** 2, 0.0).sum(1) \
                / n1[:, None]
            scale = np.sqrt(var)
            scale = np.where(scale > 1e-12, scale, 1.0)
            zr = np.where(v3, (rfv - mean[:, None, :]) / scale[:, None, :],
                          0.0)
            rfv_fit = kmeans_bank(zr, L, weights=idx1_valid.astype(np.float32),
                                  seed=kmeans_seed, mesh=mesh)
            rfv_w = _offset_bincount(rfv_fit.labels, idx1_valid, L) \
                / n1[:, None]

        # Dalenius-Gurney on baseline CPI (host-side scalar refinement)
        with jax.profiler.TraceAnnotation("build.dg"):
            dg_list = [dalenius_gurney_strata(cpi0[a, :n1[a]], L)
                       for a in range(a_n)]
            dg, _ = stack_ragged(dg_list)
            dg_w = _offset_bincount(dg, idx1_valid, L) / n1[:, None]

        for a, (name, sim, pop) in enumerate(zip(names, sims, bank.pops)):
            n, n1_a = pop.n_regions, int(n1[a])
            self._apps[(name, kmeans_seed)] = AppExperiment(
                name=name, sim=sim, configs=self.configs,
                truth=truth[a], census_mat=census[a, :, :n],
                bbv_labels=bbv_fit.labels[a, :n], bbv_weights=bbv_w[a],
                bbv_feats=z[a, :n], bbv_centroids=bbv_fit.centroids[a],
                idx1=idx1_list[a], cpi0_1=cpi0[a, :n1_a],
                rfv_z=zr[a, :n1_a],
                rfv_labels=rfv_fit.labels[a, :n1_a], rfv_weights=rfv_w[a],
                rfv_centroids=rfv_fit.centroids[a],
                dg_labels=dg_list[a], dg_weights=dg_w[a], num_strata=L,
                bbv_iterations=int(bbv_fit.iterations[a]),
                rfv_iterations=int(rfv_fit.iterations[a]))

    # multi-seed stratification (paper Figs 7-8): one vmapped computation
    def rfv_stratifications(self, name: str, seeds: Sequence[int]):
        """k-means RFV fits for many clustering seeds as one batched fit."""
        exp = self.app(name)
        return kmeans_batch(exp.rfv_z, self.num_strata, seeds=list(seeds))


@functools.lru_cache(maxsize=None)
def _project_bank_fn(mesh):
    key = jax.random.PRNGKey(0)
    fn = jax.vmap(lambda b: random_project(b, 15, key=key))
    if mesh is None:
        return jax.jit(fn)
    from ..distributed.appaxis import make_app_sharded
    return make_app_sharded(fn, mesh)


def _project_bank(bbvs: np.ndarray, *, mesh=None):
    """(A, N, 256) BBVs -> (A, N, 15) projections, one batched dispatch.

    Every app uses the same JL projection matrix (same key), matching the
    historic per-app ``random_project(bbv, 15, key=PRNGKey(0))`` exactly.
    """
    return _project_bank_fn(mesh)(bbvs)


# --------------------------------------------------------------- selection
def plan_selection_bank(
    exps: Sequence[AppExperiment], plan: sampling_plan.SamplingPlan,
    seed: int = 0,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized one-unit-per-stratum selection for a stack of apps.

    THE engine's single selection dispatch site: the plan's stratifier
    resolves the engine-built artifacts into a stacked ``StratumBank``,
    ONE stratum-summary dispatch (the ``segment_stats`` kernel contract,
    via ``build_selection_context``) serves the counts, the mean-policy
    targets and any baseline-derived centroids, and the plan's policy —
    a batched callable — picks one unit per stratum. Registry plug-ins
    (new stratifiers/policies) run through here without any engine edit.

    Returns ``(picks, valid, weights)``: (A, L) population indices, an
    (A, L) validity mask (False where the stratum is empty — empty strata
    are masked out of selection entirely, they can't contribute NaN
    centroids or distances), and the (A, L) stratum weights.
    """
    bank = plan.stratifier.resolve(exps)
    ctx = sampling_plan.build_selection_context(
        bank, seed=seed, summarize=_segment_sums_counts)
    local = np.asarray(plan.policy(ctx))
    valid = ctx.counts > 0
    picks = local if bank.pool is None \
        else np.take_along_axis(bank.pool, local, axis=1)
    return np.where(valid, picks, 0), valid, bank.weights


def plan_selection(exp: AppExperiment, plan: sampling_plan.SamplingPlan,
                   seed: int = 0) -> tuple[list[np.ndarray], np.ndarray]:
    """Population indices per stratum + weights for one app's plan.

    Thin per-app wrapper over ``plan_selection_bank`` so single-app
    callers and the batched sweep driver share one code path.
    """
    picks, valid, weights = plan_selection_bank([exp], plan, seed)
    sel = [np.asarray([picks[0, h]], np.int64) if valid[0, h]
           else np.empty(0, np.int64) for h in range(exp.num_strata)]
    return sel, weights[0]


def scheme_selection_bank(
    exps: Sequence[AppExperiment], scheme: str, policy: str, seed: int = 0,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Deprecated string shim over ``plan_selection_bank``.

    Constructs ``SamplingPlan.from_strings(scheme, policy)`` through the
    registry and dispatches the plan path — identical results, one
    ``DeprecationWarning``.
    """
    sampling_plan.warn_string_dispatch(
        "scheme_selection_bank",
        "use plan_selection_bank(exps, SamplingPlan.from_strings(...))")
    return plan_selection_bank(
        exps, sampling_plan.SamplingPlan.from_strings(scheme, policy), seed)


def scheme_selection(exp: AppExperiment, scheme: str, policy: str,
                     seed: int = 0) -> tuple[list[np.ndarray], np.ndarray]:
    """Deprecated string shim over ``plan_selection`` (see
    ``scheme_selection_bank`` for the contract)."""
    sampling_plan.warn_string_dispatch(
        "scheme_selection",
        "use plan_selection(exp, SamplingPlan.from_strings(...))")
    return plan_selection(
        exp, sampling_plan.SamplingPlan.from_strings(scheme, policy), seed)
