"""End-to-end two-phase sampling flow (paper Fig. 14, Section VI.A).

Steps:
  1. Initial characterization — large SRS on the baseline configuration.
  2. Construct RFVs (and CPI distributions) from the phase-1 runs.
  3. Stratify via k-means on RFVs; pick one region per stratum (centroid).
  4. Day-to-day studies use the selected regions (4a); periodic CI checks
     sample multiple units per stratum and apply the two-phase formulas (4b).

The flow is substrate-agnostic: the caller supplies a ``measure`` callable
(indices -> per-region study values) so the same driver runs the simcpu
population, an LM sampled-eval corpus, or a step-profiling stream.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Union

import numpy as np

from . import plan as _plan
from .selection import weighted_point_estimate
from .srs import draw_srs, srs_estimate
from .types import Estimate

__all__ = ["Stratification", "TwoPhaseFlow"]


@dataclasses.dataclass
class Stratification:
    """Frozen phase-1 artifact reused across configuration studies."""

    labels: np.ndarray            # per phase-1 unit
    weights: np.ndarray           # W_h estimated from phase-1 proportions
    centroids: Optional[np.ndarray]
    features: Optional[np.ndarray]   # standardized features used to cluster
    phase1_indices: np.ndarray    # population indices of phase-1 units
    phase1_baseline_y: np.ndarray  # baseline-config y for phase-1 units
    scheme: str

    @property
    def num_strata(self) -> int:
        return int(self.weights.shape[0])

    def stratum_order_key(self) -> np.ndarray:
        """Per-stratum baseline mean CPI — the paper's collapsed-strata
        pairing key ("ordering the strata based on CPI for Config 0")."""
        out = np.zeros(self.num_strata)
        for h in range(self.num_strata):
            m = self.labels == h
            out[h] = self.phase1_baseline_y[m].mean() if m.any() else np.inf
        return out


@dataclasses.dataclass
class TwoPhaseFlow:
    """Driver for the recommended methodology.

    ``population_size``: number of regions in the application.
    ``measure_baseline``: indices -> (y_baseline, feature_matrix). The
      feature matrix is the RFV (or BBV) per region.
    """

    population_size: int
    rng: np.random.Generator

    # -- Step 1: initial characterization ------------------------------------
    def characterize(
        self,
        measure_baseline: Callable[[np.ndarray], tuple[np.ndarray, np.ndarray]],
        n_phase1: int,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, Estimate]:
        idx = draw_srs(self.rng, self.population_size, n_phase1)
        y0, feats = measure_baseline(idx)
        est = srs_estimate(y0)
        return idx, np.asarray(y0), np.asarray(feats), est

    # -- Step 3: stratify + select -------------------------------------------
    def stratify(
        self,
        phase1_indices: np.ndarray,
        phase1_baseline_y: np.ndarray,
        features: Optional[np.ndarray],
        *,
        num_strata: Optional[int] = None,
        scheme: Union[str, "_plan.Stratifier"] = "rfv",
        seed: Optional[int] = None,
        kmeans_backend: Optional[str] = None,
    ) -> Stratification:
        """Stratify the phase-1 sample under a ``Stratifier``.

        ``scheme`` is a plan-object ``Stratifier`` (``RFVClusters``,
        ``BBVClusters``, ``DaleniusGurney`` or any registry plug-in)
        owning its k-means / boundary-search parameters — the
        ``num_strata``/``seed``/``kmeans_backend`` keywords then belong
        to the object, and passing a *conflicting* value here raises
        rather than being silently ignored. Passing a string
        (``'rfv'`` | ``'bbv'`` | ``'cpi'``/``'dg'``) is deprecated: it
        resolves through the plan registry (the keywords parameterize
        the constructed object) and warns.
        """
        if isinstance(scheme, str):
            _plan.warn_string_dispatch(
                "TwoPhaseFlow.stratify(scheme=...)",
                "pass a Stratifier object (e.g. RFVClusters(num_strata=20))")
            if num_strata is None:
                raise ValueError("string schemes need num_strata")
            scheme = _plan.make_stratifier(
                scheme, num_strata=num_strata, seed=seed or 0,
                backend=kmeans_backend or "auto")
        else:
            for arg, field, val in (("num_strata", "num_strata", num_strata),
                                    ("seed", "seed", seed),
                                    ("kmeans_backend", "backend",
                                     kmeans_backend)):
                if val is not None and getattr(scheme, field, None) != val:
                    raise ValueError(
                        f"{arg}={val!r} conflicts with the Stratifier "
                        f"object ({field}="
                        f"{getattr(scheme, field, None)!r}); configure "
                        "the Stratifier instead")
        labels, centroids, feats = scheme.fit(phase1_baseline_y, features)
        num_strata = scheme.num_strata
        counts = np.bincount(labels, minlength=num_strata).astype(np.float64)
        weights = counts / counts.sum()
        return Stratification(
            labels=np.asarray(labels), weights=weights,
            centroids=np.asarray(centroids), features=np.asarray(feats),
            phase1_indices=np.asarray(phase1_indices),
            phase1_baseline_y=np.asarray(phase1_baseline_y),
            scheme=type(scheme).name)

    def select(
        self,
        strat: Stratification,
        *,
        policy: Union[str, "_plan.SelectionPolicy"] = "centroid",
        per_stratum: Optional[int] = None,
        seed: int = 0,
    ) -> list[np.ndarray]:
        """Population indices of selected regions, one array per stratum.

        ``policy`` is a plan-object ``SelectionPolicy`` (``Centroid``,
        ``StratumMean``, ``RandomUnit(per_stratum=...)``,
        ``RankedSetUnit`` or any registry plug-in); its ``select_local``
        runs against the stratification. ``per_stratum`` overrides the
        policy's own configuration when given (``None`` defers to it).
        Passing a string is deprecated and resolves through the plan
        registry — warning once per call site.
        """
        if isinstance(policy, str):
            _plan.warn_string_dispatch(
                "TwoPhaseFlow.select(policy=...)",
                "pass a SelectionPolicy object (e.g. Centroid())")
            policy = _plan.make_policy(policy,
                                       per_stratum=per_stratum or 1)
        local = policy.select_local(
            strat.labels, features=strat.features,
            centroids=strat.centroids, baseline=strat.phase1_baseline_y,
            num_strata=strat.num_strata, seed=seed,
            per_stratum=per_stratum)
        return [strat.phase1_indices[l] for l in local]

    # -- Step 4a: day-to-day point estimate ----------------------------------
    def point_estimate(
        self,
        strat: Stratification,
        selected: Sequence[np.ndarray],
        measure: Callable[[np.ndarray], np.ndarray],
    ) -> float:
        flat = np.concatenate([s for s in selected if s.size > 0])
        y = np.asarray(measure(flat))
        per_stratum: list[np.ndarray] = []
        off = 0
        for s in selected:
            per_stratum.append(np.arange(off, off + s.size))
            off += s.size
        return weighted_point_estimate(
            [np.asarray(p) for p in per_stratum], y, strat.weights)

    def collapsed_ci(
        self,
        strat: Stratification,
        selected: Sequence[np.ndarray],
        measure: Callable[[np.ndarray], np.ndarray],
        *,
        confidence: float = 0.95,
    ) -> Estimate:
        """Practical one-unit-per-stratum CI (paper V.A.3, Fig 9) — the
        plan-level ``CollapsedPairsCI`` estimator view."""
        y_h = np.array([float(measure(s)[0]) for s in selected])
        return _plan.CollapsedPairsCI(confidence=confidence).estimate(
            y_h, strat.weights, order_by=strat.stratum_order_key())

    # -- Step 4b: periodic multi-unit CI check -------------------------------
    def ci_check(
        self,
        strat: Stratification,
        measure: Callable[[np.ndarray], np.ndarray],
        *,
        per_stratum_sizes: np.ndarray,
        confidence: float = 0.95,
        seed: int = 0,
    ) -> Estimate:
        """Stratified multi-unit sample + two-phase CI (paper eq. 5/6).

        Strata whose phase-1 pool yields fewer than 2 sampled units cannot
        provide a within-stratum variance; they are collapsed into the
        neighboring stratum in baseline-CPI order (the paper fn.7 remedy)
        instead of crashing the variance formula — one-lane view over
        ``tables.collapse_small_strata``, estimated by the plan-level
        ``TwoPhaseCI`` view (the same merge + eq. 5/6 the batched
        estimators apply lane-wise).
        """
        from . import tables as _tables

        rng = np.random.default_rng(seed)
        ys: list[np.ndarray] = []
        labs: list[np.ndarray] = []
        for h in range(strat.num_strata):
            pool = strat.phase1_indices[strat.labels == h]
            k = int(min(per_stratum_sizes[h], pool.size))
            if k == 0:
                continue
            chosen = rng.choice(pool, size=k, replace=False)
            ys.append(np.asarray(measure(chosen)))
            labs.append(np.full(k, h))
        y = np.concatenate(ys) if ys else np.empty(0)
        lab = np.concatenate(labs) if labs else np.empty(0, np.int64)
        t = _tables.stratum_tables(y, lab, weights=strat.weights,
                                   num_strata=strat.num_strata)
        merged, _, n_groups = _tables.collapse_small_strata(
            t, strat.stratum_order_key())
        if int(n_groups) < 1:
            raise ValueError("ci_check needs at least 2 sampled units")
        # estimate from the merged-group lanes only (trailing slots are
        # zero-count, zero-weight: they contribute nothing)
        return _plan.TwoPhaseCI(confidence=confidence).estimate(
            merged, phase1_n=strat.phase1_indices.size)
