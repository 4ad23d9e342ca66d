"""Composable sampling plans: stratifier × selection policy × estimator.

The paper's central decomposition of SimPoint — *stratification* (how
regions are grouped) is independent of *sample-unit selection* (which
region represents a stratum) and of *estimation* (how selected values
become a mean/CI) — is exactly the seam this module turns into an API.
A ``SamplingPlan`` is a pytree of three frozen dataclasses:

* a ``Stratifier`` (``BBVClusters`` / ``RFVClusters`` /
  ``DaleniusGurney``) owning its feature derivation and k-means /
  boundary-search parameters;
* a ``SelectionPolicy`` (``Centroid`` / ``StratumMean`` /
  ``RandomUnit`` / ``RankedSetUnit``) — a batched callable mapping a
  ``SelectionContext`` (per-stratum membership over a stacked app axis)
  to one pick per stratum per app;
* an ``Estimator`` (``WeightedPoint`` / ``CollapsedPairsCI`` /
  ``TwoPhaseCI``) — thin plan-level views over the batched
  ``StratumTables`` estimators in ``tables``; ``WeightedPoint`` also
  hosts the jitted on-device sweep-estimate program the sweep driver
  dispatches (``last_sweep_dispatch`` exposes the marker).

New designs plug in through the registry — ``register_stratifier`` /
``register_policy`` — without touching the engine or the sweep driver:
``repro.experiments`` dispatches on plan objects only, and
``SamplingPlan.from_strings("rfv", "ranked_set")`` resolves names
through the same registry the legacy string shims use. ``RankedSetUnit``
(order-statistic selection by phase-1 CPI rank within each stratum,
after *CPU Simulation with Ranked Set Sampling and Repeated
Subsampling*) is registered here purely through that mechanism as the
worked extensibility example.
"""

from __future__ import annotations

import dataclasses
import warnings
import zlib
from typing import Callable, ClassVar, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import tables as _tables
from .types import Estimate, critical_values

__all__ = [
    "SamplingPlan", "Stratifier", "SelectionPolicy", "Estimator",
    "BBVClusters", "RFVClusters", "DaleniusGurney",
    "Centroid", "StratumMean", "RandomUnit", "RankedSetUnit",
    "WeightedPoint", "CollapsedPairsCI", "TwoPhaseCI",
    "StratumBank", "SelectionContext", "build_selection_context",
    "register_stratifier", "register_policy",
    "registered_stratifiers", "registered_policies",
    "make_stratifier", "make_policy",
    "last_sweep_dispatch",
]


# ---------------------------------------------------------------- registry
_STRATIFIERS: dict[str, Callable] = {}
_POLICIES: dict[str, Callable] = {}
# legacy spellings resolvable by make_* but NOT listed as schemes: an
# alias must never become a second scheme name for the same design (it
# would get its own PRNG fold-in and its own row label)
_STRATIFIER_ALIASES: dict[str, str] = {}


def register_stratifier(name: str, factory: Callable, *,
                        aliases: Sequence[str] = ()) -> Callable:
    """Register a ``Stratifier`` factory under ``name`` (+ aliases).

    ``factory(**params)`` must return a ``Stratifier``; re-registering a
    name replaces the previous factory (latest wins, so downstream code
    can override the built-ins). ``aliases`` are legacy spellings that
    resolve through ``make_stratifier`` but are NOT separate scheme
    names (``registered_stratifiers`` omits them). Returns ``factory``
    so the call can be used as a decorator-style one-liner.
    """
    _STRATIFIERS[name] = factory
    for key in aliases:
        _STRATIFIER_ALIASES[key] = name
    return factory


def register_policy(name: str, factory: Callable) -> Callable:
    """Register a ``SelectionPolicy`` factory under ``name``."""
    _POLICIES[name] = factory
    return factory


def registered_stratifiers() -> tuple[str, ...]:
    """Registered stratifier scheme names (aliases omitted),
    registration order."""
    return tuple(_STRATIFIERS)


def registered_policies() -> tuple[str, ...]:
    """Registered selection-policy names, registration order."""
    return tuple(_POLICIES)


def _lookup(table: dict, kind: str, name: str) -> Callable:
    try:
        return table[name]
    except KeyError:
        raise ValueError(
            f"unknown {kind} {name!r}; registered: "
            f"{', '.join(sorted(table))}") from None


def make_stratifier(name: str, **params) -> "Stratifier":
    """Construct a registered stratifier by name (aliases resolve to
    their canonical design).

    ``params`` are filtered to the factory's dataclass fields so shims
    can pass a superset (e.g. ``kmeans_backend`` to ``DaleniusGurney``,
    which ignores it) without each factory declaring every knob.
    """
    name = _STRATIFIER_ALIASES.get(name, name)
    return _construct(_lookup(_STRATIFIERS, "stratifier", name), params)


def make_policy(name: str, **params) -> "SelectionPolicy":
    """Construct a registered selection policy by name (params filtered
    to the factory's fields, as in ``make_stratifier``)."""
    return _construct(_lookup(_POLICIES, "selection policy", name), params)


def _construct(factory: Callable, params: dict):
    if dataclasses.is_dataclass(factory):
        names = {f.name for f in dataclasses.fields(factory) if f.init}
        params = {k: v for k, v in params.items() if k in names}
    return factory(**params)


def _register_static_pytree(cls):
    """Register ``cls`` as a leafless jax pytree node (all fields static).

    Plan components are hyperparameters, not data: flattening to zero
    leaves keeps them out of tracers while letting whole plans cross
    ``jit``/``vmap`` boundaries and ``tree_map`` transparently.
    """
    jax.tree_util.register_pytree_node(
        cls, lambda t: ((), t), lambda aux, _: aux)
    return cls


# ------------------------------------------------------------ ragged stack
def _stack_ragged(arrays, *, dtype=None, fill=0):
    """(values, valid) stack of ragged-leading-length arrays.

    Local mirror of ``repro.simcpu.stack_ragged`` so the core sampling
    layer stays independent of the simulation substrate.
    """
    arrays = [np.asarray(a) for a in arrays]
    k_max = max((a.shape[0] for a in arrays), default=0)
    trail = arrays[0].shape[1:] if arrays else ()
    out = np.full((len(arrays), k_max) + trail, fill,
                  dtype=dtype or arrays[0].dtype)
    valid = np.zeros((len(arrays), k_max), bool)
    for i, a in enumerate(arrays):
        out[i, :a.shape[0]] = a
        valid[i, :a.shape[0]] = True
    return out, valid


# -------------------------------------------------------------- stratifiers
@dataclasses.dataclass(frozen=True)
class StratumBank:
    """Stacked-over-app stratification arrays a ``Stratifier`` resolves to.

    ``labels``/``valid`` are ``(A, n)`` over each app's unit pool (full
    population or phase-1 sample); ``weights`` is ``(A, L)``;
    ``baseline`` is the per-unit baseline-config CPI the selection
    policies and collapse-ordering keys read. ``feats``/``centroids``
    may be ``None`` — the selection context then derives them from the
    baseline values and the per-stratum baseline means (the
    Dalenius-Gurney convention). ``pool`` maps local unit positions to
    population indices (``None`` when labels already index the
    population directly).
    """

    labels: np.ndarray                  # (A, n) int stratum ids
    valid: np.ndarray                   # (A, n) bool
    weights: np.ndarray                 # (A, L) stratum weights W_h
    baseline: np.ndarray                # (A, n) baseline CPI per unit
    feats: Optional[np.ndarray] = None  # (A, n, F) selection features
    centroids: Optional[np.ndarray] = None   # (A, L, F)
    pool: Optional[np.ndarray] = None   # (A, n) population indices

    @property
    def num_strata(self) -> int:
        """L, the stratum-axis length."""
        return int(self.weights.shape[-1])


@dataclasses.dataclass(frozen=True)
class Stratifier:
    """Base class: how a population is grouped into strata.

    Subclasses own their feature derivation and fitting parameters and
    implement two entry points:

    * ``resolve(exps)`` — bind to engine-built artifacts: stack the
      per-app labels/weights/features this stratifier corresponds to
      into a ``StratumBank`` (``exps`` are ``AppExperiment``-shaped
      objects; duck-typed so this layer never imports the engine).
    * ``fit(baseline_y, features)`` — fit from scratch for the
      single-app ``TwoPhaseFlow`` path: returns
      ``(labels, centroids, features_used)``.

    ``pool_kind`` declares the value pool trials draw from: census pools
    are analysis-only (free); phase-1 pools are charged through the memo
    once.
    """

    name: ClassVar[str] = "?"
    pool_kind: ClassVar[str] = "phase1"        # "census" | "phase1"

    num_strata: int = 20
    seed: int = 0

    def resolve(self, exps: Sequence) -> StratumBank:
        """Stack this stratifier's engine-built artifacts over apps."""
        raise NotImplementedError

    def fit(self, baseline_y: np.ndarray,
            features: Optional[np.ndarray]):
        """Fit labels/centroids from phase-1 measurements (flow path)."""
        raise NotImplementedError


def _fit_kmeans(features, num_strata, seed, backend, restarts):
    """Standardize + k-means fit shared by the feature-space stratifiers
    (exactly the historic ``TwoPhaseFlow.stratify`` k-means branch)."""
    from ..clustering.kmeans import kmeans
    from ..clustering.standardize import Standardizer

    if features is None:
        raise ValueError("feature-space stratifiers need a feature matrix")
    _, z = Standardizer.fit_transform(features)
    z = np.asarray(z)
    km = kmeans(z, num_strata, key=jax.random.PRNGKey(seed),
                backend=backend, restarts=restarts)
    return km.labels, km.centroids, z


@_register_static_pytree
@dataclasses.dataclass(frozen=True)
class BBVClusters(Stratifier):
    """SimPoint-style stratification: k-means on projected BBVs over the
    full population (census baseline, analysis-only value pool)."""

    name: ClassVar[str] = "bbv"
    pool_kind: ClassVar[str] = "census"

    restarts: int = 3
    backend: str = "auto"

    def resolve(self, exps: Sequence) -> StratumBank:
        """Stack the engine's census-BBV artifacts over apps."""
        labels, valid = _stack_ragged([e.bbv_labels for e in exps])
        feats, _ = _stack_ragged([e.bbv_feats for e in exps])
        baseline, _ = _stack_ragged([e.census(0) for e in exps])
        return StratumBank(
            labels=labels, valid=valid,
            weights=np.stack([e.bbv_weights for e in exps]),
            baseline=baseline, feats=feats,
            centroids=np.stack([e.bbv_centroids for e in exps]), pool=None)

    def fit(self, baseline_y, features):
        """k-means on (standardized) BBV features."""
        return _fit_kmeans(features, self.num_strata, self.seed,
                           self.backend, self.restarts)


@_register_static_pytree
@dataclasses.dataclass(frozen=True)
class RFVClusters(Stratifier):
    """The paper's recommended stratification: k-means on standardized
    RFVs of the phase-1 sample (charged phase-1 value pool)."""

    name: ClassVar[str] = "rfv"
    pool_kind: ClassVar[str] = "phase1"

    restarts: int = 3
    backend: str = "auto"

    def resolve(self, exps: Sequence) -> StratumBank:
        """Stack the engine's phase-1 RFV artifacts over apps."""
        labels, valid = _stack_ragged([e.rfv_labels for e in exps])
        feats, _ = _stack_ragged([e.rfv_z for e in exps])
        baseline, _ = _stack_ragged([e.cpi0_1 for e in exps])
        pool, _ = _stack_ragged([e.idx1 for e in exps])
        return StratumBank(
            labels=labels, valid=valid,
            weights=np.stack([e.rfv_weights for e in exps]),
            baseline=baseline, feats=feats,
            centroids=np.stack([e.rfv_centroids for e in exps]), pool=pool)

    def fit(self, baseline_y, features):
        """k-means on (standardized) RFV features."""
        return _fit_kmeans(features, self.num_strata, self.seed,
                           self.backend, self.restarts)


@_register_static_pytree
@dataclasses.dataclass(frozen=True)
class DaleniusGurney(Stratifier):
    """Dalenius-Gurney boundary search on baseline CPI (paper V.B.1):
    one-dimensional strata whose "centroids" are stratum-mean CPIs."""

    name: ClassVar[str] = "dg"
    pool_kind: ClassVar[str] = "phase1"

    def resolve(self, exps: Sequence) -> StratumBank:
        """Stack the engine's DG artifacts; features/centroids are
        derived from baseline CPI by the selection context."""
        labels, valid = _stack_ragged([e.dg_labels for e in exps])
        baseline, _ = _stack_ragged([e.cpi0_1 for e in exps])
        pool, _ = _stack_ragged([e.idx1 for e in exps])
        return StratumBank(
            labels=labels, valid=valid,
            weights=np.stack([e.dg_weights for e in exps]),
            baseline=baseline, feats=None, centroids=None, pool=pool)

    def fit(self, baseline_y, features):
        """DG boundary search on baseline y; centroid = stratum mean."""
        from .dalenius import dalenius_gurney_strata

        y = np.asarray(baseline_y, np.float64)
        labels = dalenius_gurney_strata(y, self.num_strata)
        centroids = np.array([
            [y[labels == h].mean()] if (labels == h).any() else [np.nan]
            for h in range(self.num_strata)])
        return labels, centroids, y[:, None]


register_stratifier("bbv", BBVClusters)
register_stratifier("rfv", RFVClusters)
# "cpi" is the historic TwoPhaseFlow name for the same design
register_stratifier("dg", DaleniusGurney, aliases=("cpi",))


# ----------------------------------------------------------------- policies
@dataclasses.dataclass
class SelectionContext:
    """Everything a batched selection policy may read, app-stacked.

    Built once per selection (``build_selection_context``) from a
    ``StratumBank``; ``member[a, i, h]`` marks unit ``i`` of app ``a``
    as a valid member of stratum ``h``. ``order``/``offsets``/``counts``
    are the per-stratum gather tables (stratum ``h`` of app ``a`` owns
    ``order[a, offsets[a, h] : offsets[a, h] + counts[a, h]]``, in index
    order; trailing empty strata park their offset at the row width —
    gathers must clamp). ``member``/``order``/``offsets`` are lazy,
    cached on first read, so each policy materializes only the tables
    it actually dispatches on.

    The context is namespace-agnostic: fields may be numpy arrays (the
    staged host path) or jax tracers (the fused sweep megaprogram traces
    selection in-program — ``repro.experiments.fused``); the derived
    tables follow the input namespace. ``uniforms`` optionally carries
    pre-drawn ``(A, L)`` uniforms for ``RandomUnit`` so a traced context
    consumes the exact bits the host rng would have drawn.
    """

    labels: np.ndarray        # (A, n)
    valid: np.ndarray         # (A, n)
    feats: np.ndarray         # (A, n, F)
    centroids: np.ndarray     # (A, L, F)
    baseline: np.ndarray      # (A, n)
    base_means: np.ndarray    # (A, L) per-stratum mean baseline CPI
    counts: np.ndarray        # (A, L) int
    num_strata: int
    seed: int = 0
    uniforms: Optional[np.ndarray] = None    # (A, L) pre-drawn U[0,1)
    _member: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)
    _order: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)

    @property
    def member(self) -> np.ndarray:
        """(A, n, L) valid-membership mask (cached on first read)."""
        if self._member is None:
            xp = _tables._ns(self.labels, self.valid)
            self._member = (
                self.labels[:, :, None]
                == xp.arange(self.num_strata)[None, None, :]) \
                & self.valid[:, :, None]
        return self._member

    @property
    def order(self) -> np.ndarray:
        """(A, n) stratum-sorted gather table (cached on first read)."""
        if self._order is None:
            xp = _tables._ns(self.labels, self.valid)
            self._order = _tables._argsort(
                xp, xp.where(self.valid, self.labels, self.num_strata))
        return self._order

    @property
    def offsets(self) -> np.ndarray:
        """(A, L) per-stratum start positions into ``order``."""
        xp = _tables._ns(self.counts)
        return xp.cumsum(self.counts, axis=1) - self.counts


def _np_segment_sums_counts(labels, valid, num_strata, values):
    """Exact float64 host fallback for the stratum-summary dispatch
    (the engine substitutes its ``segment_stats``-kernel-backed path)."""
    lab = np.where(valid, labels, num_strata).astype(np.int64)
    a_n = lab.shape[0]
    flat = lab + (num_strata + 1) * np.arange(a_n)[:, None]
    minlength = a_n * (num_strata + 1)
    counts = np.bincount(flat.ravel(), minlength=minlength)
    sums = np.bincount(flat.ravel(),
                       weights=np.where(valid, values, 0.0).ravel(),
                       minlength=minlength)
    counts = counts.reshape(a_n, num_strata + 1)[:, :num_strata]
    sums = sums.reshape(a_n, num_strata + 1)[:, :num_strata]
    return sums.astype(np.float64), counts.astype(np.float64)


def build_selection_context(bank: StratumBank, *, seed: int = 0,
                            summarize: Optional[Callable] = None,
                            uniforms=None) -> SelectionContext:
    """Selection context for a ``StratumBank``: ONE stratum-summary
    dispatch serves the counts, the mean-policy targets AND (for
    banks without explicit centroids) the DG stratum-mean centroids.

    ``summarize(labels, valid, L, values) -> (sums, counts)`` lets the
    engine route the summary through its ``segment_stats`` kernel
    contract; the default is an exact float64 host bincount. Works on
    numpy arrays and on jax tracers alike (the fused sweep megaprogram
    builds its context in-trace, with ``uniforms`` carrying host-drawn
    random-policy draws so picks match the staged path exactly).
    """
    summarize = summarize or _np_segment_sums_counts
    L = bank.num_strata
    labels, valid = bank.labels, bank.valid
    base_sums, countsf = summarize(labels, valid, L, bank.baseline)
    xp = _tables._ns(labels, valid, countsf)
    base_means = base_sums / xp.maximum(countsf, 1)
    counts = countsf.astype(np.int64)
    feats = bank.feats if bank.feats is not None \
        else xp.asarray(bank.baseline)[:, :, None]
    # EMPTY strata get a zero derived centroid but are masked out of
    # selection entirely, so no NaN ever reaches a distance computation
    cents = bank.centroids if bank.centroids is not None \
        else base_means[:, :, None]
    return SelectionContext(
        labels=labels, valid=valid, feats=feats,
        centroids=cents, baseline=bank.baseline, base_means=base_means,
        counts=counts, num_strata=L, seed=seed, uniforms=uniforms)


@dataclasses.dataclass(frozen=True)
class SelectionPolicy:
    """Base class: which unit represents each stratum.

    A policy is a batched callable over the app stack —
    ``policy(ctx) -> (A, L)`` local unit positions, one per stratum
    (empty strata may return anything; the caller masks them with
    ``ctx.counts > 0``). ``select_local`` is the single-app
    ``TwoPhaseFlow`` entry point; the default builds a one-lane context
    and reuses the batched callable, so a plug-in policy only has to
    implement ``__call__``.

    ``uses_uniforms`` declares that the policy consumes per-(app,
    stratum) uniform draws (``SelectionContext.uniforms``): the fused
    sweep program host-draws them with the policy's exact rng sequence
    and feeds them into the trace, keeping traced picks equal to staged
    picks without string dispatch on policy names.
    """

    name: ClassVar[str] = "?"
    uses_uniforms: ClassVar[bool] = False

    def __call__(self, ctx: SelectionContext) -> np.ndarray:
        """(A, L) local pick positions for the stacked app axis."""
        raise NotImplementedError

    def select_local(self, labels, *, features, centroids, baseline,
                     num_strata: int, seed: int = 0,
                     per_stratum: Optional[int] = None) -> list[np.ndarray]:
        """Per-stratum local index arrays for one app (flow path).

        ``per_stratum=None`` defers to the policy's own configuration;
        an explicit value overrides it. The default implementation
        reuses the batched callable through a one-lane context and only
        supports one unit per stratum — multi-unit policies override.
        """
        if (per_stratum or 1) != 1:
            raise NotImplementedError(
                f"{type(self).name!r} selects one unit per stratum; "
                "override select_local for multi-unit designs")
        labels = np.asarray(labels)
        bank = StratumBank(
            labels=labels[None], valid=np.ones((1, labels.size), bool),
            weights=np.full((1, num_strata), 1.0 / max(num_strata, 1)),
            baseline=np.asarray(baseline)[None],
            feats=None if features is None
            else np.asarray(features)[None],
            centroids=None if centroids is None
            else np.asarray(centroids)[None])
        ctx = build_selection_context(bank, seed=seed)
        local = np.asarray(self(ctx))[0]
        return [np.atleast_1d(local[h]).astype(np.int64)
                if ctx.counts[0, h] > 0 else np.empty(0, np.int64)
                for h in range(num_strata)]


@_register_static_pytree
@dataclasses.dataclass(frozen=True)
class Centroid(SelectionPolicy):
    """SimPoint-style selection: the unit whose feature vector is nearest
    its stratum centroid (paper V.B, deterministic).

    ``per_stratum`` (the k nearest units) applies to the single-app flow
    path; the batched bank path picks one unit per stratum.
    """

    name: ClassVar[str] = "centroid"

    per_stratum: int = 1

    def __call__(self, ctx: SelectionContext) -> np.ndarray:
        """Argmin of squared feature distance to the centroid, per
        stratum (masked to members; empty strata are masked out)."""
        xp = _tables._ns(ctx.feats, ctx.centroids)
        # each member's distance to its OWN stratum centroid, in the direct
        # sum((x - c)^2) form: the expanded |x|^2 - 2<x,c> + |c|^2 form
        # cancels catastrophically in float32 at census scale (d2 ~ 1e-5
        # out of O(1) terms) and its matmul runs at reduced precision on
        # the TPU, enough to flip near-boundary argmins between backends.
        # Accumulate in the namespace's widest float (f64 on the host and
        # under x64; the canonical float via result_type(0.0) never warns)
        dt = xp.result_type(0.0)
        feats = xp.asarray(ctx.feats, dt)
        cents = xp.asarray(ctx.centroids, dt)
        own = xp.clip(ctx.labels, 0, ctx.num_strata - 1)
        diff = feats - cents[xp.arange(own.shape[0])[:, None], own]
        d2 = (diff ** 2).sum(axis=2)                        # (A, n)
        return xp.where(ctx.member, d2[:, :, None], xp.inf).argmin(axis=1)

    def select_local(self, labels, *, features, centroids, baseline,
                     num_strata: int, seed: int = 0,
                     per_stratum: Optional[int] = None) -> list[np.ndarray]:
        """Flow path: exactly the historic ``select_centroid``."""
        from .selection import select_centroid
        return select_centroid(np.asarray(labels), np.asarray(features),
                               np.asarray(centroids),
                               per_stratum=per_stratum or self.per_stratum)


@_register_static_pytree
@dataclasses.dataclass(frozen=True)
class StratumMean(SelectionPolicy):
    """Mean selection (paper V.B.2): the unit whose baseline CPI is
    nearest the stratum's mean baseline CPI.

    ``per_stratum`` (the k nearest units) applies to the single-app flow
    path; the batched bank path picks one unit per stratum.
    """

    name: ClassVar[str] = "mean"

    per_stratum: int = 1

    def __call__(self, ctx: SelectionContext) -> np.ndarray:
        """Argmin |baseline − stratum mean baseline| per stratum."""
        xp = _tables._ns(ctx.baseline, ctx.base_means)
        d = xp.abs(ctx.baseline[:, :, None] - ctx.base_means[:, None, :])
        return xp.where(ctx.member, d, xp.inf).argmin(axis=1)

    def select_local(self, labels, *, features, centroids, baseline,
                     num_strata: int, seed: int = 0,
                     per_stratum: Optional[int] = None) -> list[np.ndarray]:
        """Flow path: exactly the historic ``select_mean``."""
        from .selection import select_mean
        return select_mean(np.asarray(labels), np.asarray(baseline),
                           num_strata=num_strata,
                           per_stratum=per_stratum or self.per_stratum)


@_register_static_pytree
@dataclasses.dataclass(frozen=True)
class RandomUnit(SelectionPolicy):
    """Textbook stratified sampling: a uniform random unit per stratum
    (the paper's conservative-CI reference policy).

    ``per_stratum`` applies to the single-app flow path (multi-unit
    designs); the batched bank path always picks one unit per stratum.
    """

    name: ClassVar[str] = "random"
    uses_uniforms: ClassVar[bool] = True

    per_stratum: int = 1

    def __call__(self, ctx: SelectionContext) -> np.ndarray:
        """One uniform draw per (app, stratum) from the gather tables.

        ``ctx.uniforms`` (when set) substitutes for the host rng draw —
        the fused sweep program passes the SAME ``default_rng(seed)``
        bits in as an array so traced picks equal staged picks.
        """
        xp = _tables._ns(ctx.counts, ctx.uniforms)
        if ctx.uniforms is None:
            u = np.random.default_rng(ctx.seed).random(
                np.shape(ctx.counts))                       # (A, L)
        else:
            u = ctx.uniforms
        pos = ctx.offsets + xp.minimum(
            (u * ctx.counts).astype(np.int64),
            xp.maximum(ctx.counts - 1, 0))
        # trailing empty strata park offsets at the row width: clamp (the
        # pick is discarded by the caller's validity mask)
        pos = xp.minimum(pos, max(ctx.order.shape[1] - 1, 0))
        return xp.take_along_axis(ctx.order, pos, axis=1)

    def select_local(self, labels, *, features, centroids, baseline,
                     num_strata: int, seed: int = 0,
                     per_stratum: Optional[int] = None) -> list[np.ndarray]:
        """Flow path: exactly the historic ``select_random``."""
        from .selection import select_random
        return select_random(np.asarray(labels), num_strata,
                             np.random.default_rng(seed),
                             per_stratum=per_stratum or self.per_stratum)


@_register_static_pytree
@dataclasses.dataclass(frozen=True)
class RankedSetUnit(SelectionPolicy):
    """Order-statistic selection: the unit at a fixed baseline-CPI rank
    within each stratum.

    After *CPU Simulation with Ranked Set Sampling and Repeated
    Subsampling*: units are ranked by their (cheap, already-measured)
    phase-1 baseline CPI inside each stratum and the unit at rank
    fraction ``rank_fraction`` is selected — 0.5 picks the per-stratum
    median unit, 0.0/1.0 the extremes. Deterministic like ``Centroid``
    but needs only the scalar baseline, no feature geometry.

    Registered through the public registry exactly like an external
    plug-in would be — the engine and sweep driver dispatch on the plan
    object and need no edits for it.
    """

    name: ClassVar[str] = "ranked_set"

    rank_fraction: float = 0.5

    def __post_init__(self):
        if not 0.0 <= self.rank_fraction <= 1.0:
            raise ValueError(
                f"rank_fraction must be in [0, 1], got {self.rank_fraction}")

    def __call__(self, ctx: SelectionContext) -> np.ndarray:
        """Pick the unit at the configured baseline-CPI rank per stratum."""
        # within-stratum CPI order: stable sort by (stratum, baseline),
        # spelled as composed stable argsorts (== np.lexsort) so the same
        # code runs on numpy arrays and on jax tracers
        xp = _tables._ns(ctx.labels, ctx.baseline)
        primary = xp.where(ctx.valid, ctx.labels, ctx.num_strata)
        by_base = _tables._argsort(xp, ctx.baseline)
        rs_order = xp.take_along_axis(
            by_base,
            _tables._argsort(xp, xp.take_along_axis(primary, by_base,
                                                    axis=1)), axis=1)
        rank = xp.rint(self.rank_fraction
                       * xp.maximum(ctx.counts - 1, 0)).astype(np.int64)
        pos = xp.minimum(ctx.offsets + rank,
                         max(rs_order.shape[1] - 1, 0))
        return xp.take_along_axis(rs_order, pos, axis=1)


register_policy("centroid", Centroid)
register_policy("mean", StratumMean)
register_policy("random", RandomUnit)
register_policy("ranked_set", RankedSetUnit)


# --------------------------------------------------------------- estimators
# trace-/dispatch-time record of the most recent on-device sweep
# estimation (see last_sweep_dispatch)
_last_sweep_dispatch: Optional[dict] = None


def last_sweep_dispatch() -> Optional[dict]:
    """Marker describing the most recent jitted sweep-estimate dispatch.

    ``None`` until an ``Estimator.sweep_estimates`` program (or the
    fused sweep megaprogram — ``repro.experiments.fused``) ran; else a
    dict with ``batch_shape`` (the (A, C) lane axes), ``num_strata``,
    ``x64`` (whether the program ran in float64), ``backend``,
    ``fused`` (one megaprogram dispatch vs the staged estimate-only
    program), ``donated`` (whether the runtime actually consumed the
    donated memo buffers — backends without donation report False) and
    ``count`` (dispatches since the last reset, so tests can assert a
    sweep cost exactly ONE device program). Only the jitted device
    programs write it — there is no host fallback on the sweep-estimate
    path, so tests can assert estimates really came off-device.
    """
    return None if _last_sweep_dispatch is None \
        else dict(_last_sweep_dispatch)


def _record_sweep_dispatch(**fields) -> None:
    """Write the sweep-dispatch marker, accumulating ``count`` since the
    last ``_reset_sweep_dispatch`` (one fused sweep must record 1)."""
    global _last_sweep_dispatch
    prior = 0 if _last_sweep_dispatch is None \
        else _last_sweep_dispatch.get("count", 0)
    _last_sweep_dispatch = {**fields, "count": prior + 1}


def _reset_sweep_dispatch() -> None:
    """Clear the sweep-estimate dispatch marker (test helper)."""
    global _last_sweep_dispatch
    _last_sweep_dispatch = None


@jax.jit
def _weighted_point_program(cpi, valid, weights, truth):
    """Jitted ``StratumTables`` program for stratified sweep estimates.

    The staged spelling of ``Estimator.estimate_stage`` — one dispatch
    whose whole body is the fusable tables→estimates stage. Returns
    ``(estimate, err_pct)``.
    """
    return Estimator.estimate_stage(cpi, valid, weights, truth)


def _x64_sweep_programs() -> bool:
    """Whether the default sweep-estimate policy runs in float64.

    Delegates to ``PrecisionPolicy.host_parity`` — the ONE precision
    policy (``repro.core.precision``): CPU hosts trace the program under
    ``jax.enable_x64`` so on-device estimates match the
    historic float64 host reduction to rounding; TPU backends (no
    native f64) keep the default float32.
    """
    from ..precision import PrecisionPolicy

    return PrecisionPolicy.host_parity().needs_x64


@dataclasses.dataclass(frozen=True)
class Estimator:
    """Base class: how selected values become estimates.

    Every estimator shares the jitted on-device sweep-estimate program
    (``sweep_estimates``) — the weighted point estimate is the sweep's
    common denominator — and subclasses add their interval views over
    the batched ``tables`` estimators.
    """

    name: ClassVar[str] = "weighted_point"

    @staticmethod
    def estimate_stage(cpi, valid, weights, truth):
        """The fusable tables→estimates stage: traceable, no dispatch.

        Lanes are (app, config): ``sweep_point_tables`` turns the pick
        mask into one-unit-per-stratum ``StratumTables`` and
        ``stratified_mean`` reduces them to the covered-weight-
        renormalized weighted mean; ``err_pct`` follows. Shared verbatim
        by the staged jitted program (``sweep_estimates``) and the fused
        sweep megaprogram (``repro.experiments.fused``), so the two
        paths cannot drift. Returns ``(estimate, err_pct)``.
        """
        xp = _tables._ns(cpi, valid, weights, truth)
        t = _tables.sweep_point_tables(cpi, valid, weights)
        est = _tables.stratified_mean(t)
        err = 100.0 * xp.abs(est - truth) / truth
        return est, err

    def sweep_estimates(self, cpi, valid, weights, truth, *,
                        precision=None) -> tuple[np.ndarray, np.ndarray]:
        """(A, C) estimates + percent errors from one jitted dispatch.

        ``cpi``: (A, C, L) per-stratum selected-unit CPI; ``valid``:
        (A, L) pick validity; ``weights``: (A, L); ``truth``: (A, C).
        The reduction runs on device via the ``StratumTables`` program —
        no host-side weighted mean — and records the dispatch marker.
        ``precision`` overrides the default ``PrecisionPolicy``
        (``host_parity``: f64 trace off-TPU so device estimates match
        the numpy reference, f32 on TPU).
        """
        from ..precision import PrecisionPolicy

        pp = precision if precision is not None \
            else PrecisionPolicy.host_parity()
        dt = pp.trace_dtype
        args = (np.asarray(cpi, dt), np.asarray(valid, bool),
                np.asarray(weights, dt), np.asarray(truth, dt))
        with pp.x64_context():
            est, err = _weighted_point_program(*args)
        _record_sweep_dispatch(
            batch_shape=tuple(np.shape(cpi)[:-1]),
            num_strata=int(np.shape(cpi)[-1]),
            x64=pp.needs_x64, backend=jax.default_backend(),
            fused=False, donated=False)
        return np.asarray(est), np.asarray(err)


@_register_static_pytree
@dataclasses.dataclass(frozen=True)
class WeightedPoint(Estimator):
    """SimPoint-style weighted point estimate (eq. 3 mean, no interval):
    the plan-level view over ``tables.stratified_mean``."""

    name: ClassVar[str] = "weighted_point"

    def estimate(self, tables: _tables.StratumTables):
        """Lane-wise eq. (3) weighted mean (covered-weight renormalized)."""
        return _tables.stratified_mean(tables)


@_register_static_pytree
@dataclasses.dataclass(frozen=True)
class CollapsedPairsCI(Estimator):
    """One-unit-per-stratum interval via pairwise collapsed strata
    (paper eq. 4): the plan-level view over
    ``tables.collapsed_pairs_variance``."""

    name: ClassVar[str] = "collapsed_pairs"

    confidence: float = 0.95

    def interval(self, y_sorted, w_sorted, n_valid, *, num_strata: int):
        """(variance, df, half_width) lane-wise, occupied-first key order
        (see ``tables.collapsed_pairs_variance`` for the layout)."""
        var, df = _tables.collapsed_pairs_variance(
            y_sorted, w_sorted, n_valid, num_strata=num_strata)
        half = critical_values(self.confidence, np.asarray(df)) \
            * np.sqrt(np.asarray(var))
        return var, df, half

    def estimate(self, y_per_stratum, weights, *, order_by=None,
                 strict: bool = False) -> Estimate:
        """Scalar ``Estimate`` for one design (the quickstart view)."""
        from .collapsed import collapsed_strata_estimate
        return collapsed_strata_estimate(
            y_per_stratum, weights, order_by=order_by,
            confidence=self.confidence, strict=strict)


@_register_static_pytree
@dataclasses.dataclass(frozen=True)
class TwoPhaseCI(Estimator):
    """Multi-unit two-phase interval (paper eq. 5/6 + Satterthwaite):
    the plan-level view over ``tables.two_phase_variance``."""

    name: ClassVar[str] = "two_phase"

    confidence: float = 0.95
    formula: str = "phase2_only"

    def estimate(self, tables: _tables.StratumTables, phase1_n: int, *,
                 phase1_var: Optional[float] = None,
                 strict: bool = False) -> Estimate:
        """Scalar ``Estimate`` from one-lane ``StratumTables`` (the
        ``TwoPhaseFlow.ci_check`` view)."""
        from .two_phase import two_phase_estimate_tables
        return two_phase_estimate_tables(
            tables, phase1_n, phase1_var=phase1_var,
            confidence=self.confidence, formula=self.formula,
            strict=strict)


# --------------------------------------------------------------------- plan
@_register_static_pytree
@dataclasses.dataclass(frozen=True)
class SamplingPlan:
    """A complete sampling design: stratifier × policy × estimator.

    The one object the experiment engine dispatches on: see
    ``repro.experiments.plan_selection_bank`` (batched selection),
    ``SweepSpec(plan=...)`` (sweeps) and ``TwoPhaseFlow`` (single-app
    flow). ``from_strings`` resolves registry names, which is also what
    the deprecated string shims construct.
    """

    stratifier: Stratifier
    policy: SelectionPolicy = Centroid()
    estimator: Estimator = WeightedPoint()

    @classmethod
    def from_strings(cls, scheme: str, policy: str = "centroid",
                     **params) -> "SamplingPlan":
        """Resolve registered names into a plan (the compat constructor).

        ``params`` (e.g. ``num_strata``, ``seed``, ``per_stratum``) are
        filtered to each component's fields, so one kwargs dict can
        parameterize both.
        """
        return cls(stratifier=make_stratifier(scheme, **params),
                   policy=make_policy(policy, **params))

    @property
    def scheme(self) -> str:
        """The stratifier's registered name (sweep-row label)."""
        return type(self.stratifier).name

    @property
    def policy_name(self) -> str:
        """The selection policy's registered name (sweep-row label)."""
        return type(self.policy).name


def trial_scheme_index(scheme: str, canonical: Sequence[str]) -> int:
    """Stable PRNG fold-in index for a trial scheme name.

    Canonical schemes keep their historic positions (draws are
    position-based and must not change); registry plug-ins hash their
    name past the canonical range so every scheme's draws are
    independent of registration order.
    """
    canonical = tuple(canonical)
    if scheme in canonical:
        return canonical.index(scheme)
    return len(canonical) + zlib.crc32(scheme.encode()) % (2 ** 20)


def warn_string_dispatch(where: str, repl: str) -> None:
    """One ``DeprecationWarning`` per (site, replacement) pair for the
    legacy string shims (``SweepSpec(scheme=...)``,
    ``TwoPhaseFlow.stratify(scheme=...)``, ...)."""
    warnings.warn(
        f"{where} with scheme/policy strings is deprecated; {repl}",
        DeprecationWarning, stacklevel=3)
