"""Array-native stratified statistics: the ``StratumTables`` engine.

The scalar estimators in this package (``stratified.py``, ``two_phase.py``,
``collapsed.py``, ``allocation.py``) are one-lane views over this module:
a ``StratumTables`` holds the per-stratum *sufficient statistics* —
counts, sums, sums of squares and population weights — as ``(..., L)``
arrays with arbitrary leading batch axes (apps, trials, configs, ...),
and every estimator of the paper's Appendix A maps those tables to
batched results lane-wise:

* eq. (3)  stratified mean / variance       — ``stratified_mean/variance``
* eq. (5)/(6) two-phase variance            — ``two_phase_variance``
* Satterthwaite effective df [30]           — ``satterthwaite_df``
* eq. (4)  pairwise collapsed strata        — ``collapsed_pairs_variance``
* fn. 7    small-stratum collapse           — ``collapse_small_strata``
* Cochran 5.5-5.9 allocation                — ``neyman/proportional_allocation``

All estimator functions are *namespace-agnostic*: they run on numpy
arrays (host, float64 — the exact scalar-parity path) and on jnp arrays
or tracers (device, inside ``jit`` — the Monte-Carlo hot path) with the
same code. Degenerate lanes never raise inside the batched functions —
they produce NaN lane-wise, and the scalar wrappers translate NaN into
the package's documented NaN/warn/raise ``strict=`` contract
(``docs/statistics.md``).

Construction routes through the ``segment_stats`` kernel
(``repro.kernels.segment_stats``) on device backends — one batch-native
dispatch for any leading axes — and through an exact float64 bincount on
the numpy path.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..precision import DEFAULT_PRECISION, PrecisionPolicy, resolve_precision

__all__ = [
    "StratumTables",
    "stratum_tables",
    "tables_from_summaries",
    "sweep_point_tables",
    "covered_weight",
    "total_weight",
    "stratified_mean",
    "stratified_variance",
    "satterthwaite_df",
    "two_phase_variance",
    "collapse_small_strata",
    "collapsed_pairs_variance",
    "collapsed_pairs_groups",
    "collapsed_pairs_grouped",
    "proportional_allocation",
    "neyman_allocation",
    "masked_srs_stats",
    # streaming trial statistics (the chunked Monte-Carlo accumulator)
    "TRIAL_HIST_BINS",
    "TRIAL_HIST_LO",
    "TRIAL_HIST_HI",
    "TrialStats",
    "trial_stats_init",
    "trial_stats_update",
    "trial_stats_merge",
    "log_hist_quantile",
]



def _ns(*arrays):
    """numpy or jax.numpy, picked from the argument types (tracers are
    ``jax.Array`` instances, so jitted callers get jnp)."""
    return jnp if any(isinstance(a, jax.Array) for a in arrays) else np


def _argsort(xp, a):
    """Stable argsort in either namespace (jnp's sort is always stable)."""
    return np.argsort(a, axis=-1, kind="stable") if xp is np \
        else jnp.argsort(a, axis=-1)


# --------------------------------------------------------------- the pytree
@dataclasses.dataclass(frozen=True)
class StratumTables:
    """Masked per-stratum sufficient statistics with leading batch axes.

    Every stratum leaf is ``(..., L)``; the leading axes are shared batch
    axes (one lane = one stratified design). ``counts[..., h] == 0``
    marks an empty stratum — means/variances are NaN there, and the
    estimators treat the lane according to the coverage contract.

    ``sums``/``sumsqs`` hold *shifted* moments: moments of ``y − shift``
    for a per-lane offset ``shift`` (the standard stability trick —
    variances computed from raw moments suffer catastrophic cancellation
    when ``|ȳ| ≫ s``). Constructors center on the lane sample mean;
    ``shift = 0`` recovers plain moments, so hand-built tables work
    unchanged. Registered as a jax pytree so tables can cross
    ``jit``/``vmap``/``shard_map`` boundaries.
    """

    counts: np.ndarray | jax.Array     # (..., L) units sampled per stratum
    sums: np.ndarray | jax.Array       # (..., L) sum of (y - shift)
    sumsqs: np.ndarray | jax.Array     # (..., L) sum of (y - shift)^2
    weights: np.ndarray | jax.Array    # (..., L) population weights W_h
    shift: np.ndarray | jax.Array | float = 0.0   # (...) per-lane offset

    @property
    def num_strata(self) -> int:
        """L, the trailing stratum axis length."""
        return int(self.counts.shape[-1])

    @property
    def batch_shape(self) -> tuple[int, ...]:
        """The leading batch axes (``()`` for a single design)."""
        return tuple(self.counts.shape[:-1])

    def _shift_col(self, xp):
        """The shift broadcast against the trailing stratum axis."""
        return xp.asarray(self.shift)[..., None]

    @property
    def means(self):
        """(..., L) stratum sample means ȳ_h; NaN where n_h == 0."""
        xp = _ns(self.counts, self.sums)
        safe = xp.maximum(self.counts, 1.0)
        return xp.where(self.counts > 0,
                        self._shift_col(xp) + self.sums / safe, xp.nan)

    @property
    def variances(self):
        """(..., L) within-stratum sample variances s_h² (ddof=1, eq. 2);
        NaN where n_h < 2. Shift-invariant (computed on the centered
        moments)."""
        xp = _ns(self.counts, self.sums)
        safe = xp.maximum(self.counts, 1.0)
        mean = self.sums / safe
        ss = self.sumsqs - self.counts * mean * mean
        return xp.where(self.counts > 1,
                        ss / xp.maximum(self.counts - 1.0, 1.0), xp.nan)

    def lane(self, index) -> "StratumTables":
        """The single-design view at ``index`` of the leading axes."""
        shift = self.shift[index] if np.ndim(self.shift) else self.shift
        return StratumTables(self.counts[index], self.sums[index],
                             self.sumsqs[index], self.weights[index],
                             shift)


jax.tree_util.register_pytree_node(
    StratumTables,
    lambda t: ((t.counts, t.sums, t.sumsqs, t.weights, t.shift), None),
    lambda _, leaves: StratumTables(*leaves))


# ------------------------------------------------------------- construction
def stratum_tables(
    y,
    labels,
    *,
    weights=None,
    num_strata: Optional[int] = None,
    valid=None,
    backend: str = "numpy",
    validate: bool = True,
    precision: Optional[PrecisionPolicy] = None,
) -> StratumTables:
    """Build ``StratumTables`` from samples + stratum labels, batched.

    Args:
      y: study values, ``(..., n)`` (leading axes = batch lanes).
      labels: int stratum ids aligned with ``y``; negative ids mark
        masked entries.
      weights: population stratum weights W_h — ``(L,)`` shared or
        ``(..., L)`` per-lane. Defaults to the *sample* proportions per
        lane (valid for proportional allocation / post-stratification).
      num_strata: L. Required when ``weights`` is omitted and the label
        range does not determine it; defaults to ``weights.shape[-1]``.
      valid: optional bool mask aligned with ``y`` (ANDed with
        ``labels >= 0``).
      backend: ``"numpy"`` — exact host path in the policy's host dtype
        (the scalar-parity reference); ``"auto"``/``"pallas"``/``"jnp"``
        — the ``segment_stats`` kernel contract (kernel on TPU, jnp
        oracle off-TPU) computing in the policy's trace dtype.
      validate: check label range and weight normalization (numpy path
        only; device paths are jit-safe and skip data-dependent checks).
      precision: the ``PrecisionPolicy`` governing dtypes on both paths
        (default: ``DEFAULT_PRECISION`` — f32 trace, f64 host).
    """
    pp = resolve_precision(precision)
    if backend == "numpy":
        return _stratum_tables_np(y, labels, weights=weights,
                                  num_strata=num_strata, valid=valid,
                                  validate=validate, dtype=pp.host_dtype)
    from repro.kernels.segment_stats.ops import segment_stats

    dt = pp.trace_dtype
    labels = jnp.asarray(labels, jnp.int32)
    y = jnp.asarray(y, dt)
    if valid is not None:
        labels = jnp.where(jnp.asarray(valid, bool), labels, -1)
    if num_strata is None:
        if weights is None:
            raise ValueError("device backends need num_strata (or weights) "
                             "— the label range is not traceable")
        num_strata = np.shape(weights)[-1]
    L = int(num_strata)
    # shifted moments on device too: center on the per-lane valid mean so
    # float32 sumsqs keep significant bits when |ȳ| ≫ s (the masked rows
    # carry label -1 and contribute nothing either way)
    ok = (labels >= 0) & (labels < L)
    n_ok = jnp.maximum(ok.sum(axis=-1), 1).astype(dt)
    shift = jnp.where(ok, y, 0.0).sum(axis=-1) / n_ok
    sums, sumsqs, counts = segment_stats(y - shift[..., None], labels, L,
                                         backend=backend, precision=pp)
    sums, sumsqs = sums[..., 0], sumsqs[..., 0]
    if weights is None:
        total = jnp.maximum(counts.sum(axis=-1, keepdims=True), 1.0)
        w = counts / total
    else:
        w = jnp.broadcast_to(jnp.asarray(weights, dt), counts.shape)
    return StratumTables(counts=counts, sums=sums, sumsqs=sumsqs, weights=w,
                         shift=shift)


def _stratum_tables_np(y, labels, *, weights, num_strata, valid,
                       validate, dtype=np.float64) -> StratumTables:
    """Exact host constructor (vectorized offset-bincount) in the policy's
    host dtype (float64 by default — the scalar-parity reference)."""
    yv = np.asarray(y, dtype)
    lab = np.asarray(labels)
    if yv.shape != lab.shape:
        raise ValueError(f"y shape {yv.shape} != labels shape {lab.shape}")
    ok = lab >= 0
    if valid is not None:
        ok = ok & np.asarray(valid, bool)
    if num_strata is not None:
        L = int(num_strata)
    elif weights is not None:
        L = int(np.shape(weights)[-1])
    else:
        L = int(lab[ok].max() + 1) if ok.any() else 0
    if validate and ok.any() and lab[ok].max() >= L:
        raise ValueError(f"label {int(lab[ok].max())} out of range for "
                         f"num_strata={L}")
    ok = ok & (lab < L)      # kernel semantics: out-of-range rows drop

    batch_shape = yv.shape[:-1]
    n = yv.shape[-1] if yv.ndim else 0
    b = int(np.prod(batch_shape, dtype=np.int64)) if batch_shape else 1
    lab2 = lab.reshape(b, n)
    ok2 = ok.reshape(b, n)
    # center on the per-lane sample mean (shifted moments: keeps the
    # variance free of the sumsq - n·mean² cancellation when |ȳ| ≫ s)
    n_ok = np.maximum(ok2.sum(axis=1), 1)
    shift = np.where(ok2, yv.reshape(b, n), 0.0).sum(axis=1) / n_ok
    yc = yv.reshape(b, n) - shift[:, None]
    # flat segment ids: lane i owns [i*L, (i+1)*L); invalid rows dump into
    # one trailing slot that is dropped after the bincount
    flat = np.where(ok2, lab2 + L * np.arange(b)[:, None], b * L)
    yz = np.where(ok2, yc, 0.0)
    counts = np.bincount(flat.ravel(), minlength=b * L + 1)[:-1]
    sums = np.bincount(flat.ravel(), weights=yz.ravel(),
                       minlength=b * L + 1)[:-1]
    sumsqs = np.bincount(flat.ravel(), weights=(yz * yz).ravel(),
                         minlength=b * L + 1)[:-1]
    counts = counts.astype(np.float64).reshape(*batch_shape, L)
    sums = sums.reshape(*batch_shape, L)
    sumsqs = sumsqs.reshape(*batch_shape, L)
    shift = shift.reshape(batch_shape)

    if weights is None:
        total = np.maximum(counts.sum(axis=-1, keepdims=True), 1.0)
        w = counts / total
    else:
        wa = np.asarray(weights, np.float64)
        if wa.shape[-1:] != (L,):
            raise ValueError(
                f"weights length {wa.shape[-1] if wa.ndim else 0} != "
                f"num strata {L}")
        w = np.broadcast_to(wa, counts.shape).copy()
        if validate:
            tot = w.sum(axis=-1)
            if not np.allclose(tot, 1.0, atol=1e-6):
                raise ValueError(
                    f"stratum weights sum to {np.asarray(tot).ravel()[:8]}, "
                    "expected 1")
    return StratumTables(counts=counts, sums=sums, sumsqs=sumsqs, weights=w,
                         shift=shift)


def tables_from_summaries(summaries: Sequence) -> StratumTables:
    """One-lane tables from a ``list[StratumSummary]`` (the scalar bridge).

    Inverts the mean/variance back to *shifted* sums/sums-of-squares —
    centered on the mean of the occupied stratum means — so the scalar
    wrappers can reuse the batched estimators without reintroducing the
    ``sumsq − n·mean²`` cancellation: for n ≥ 1, ``sum = n·(ȳ − c)`` and
    ``sumsq = (n−1)·s² + n·(ȳ − c)²``.
    """
    counts = np.array([s.n for s in summaries], np.float64)
    means = np.array([s.mean if s.n > 0 else 0.0 for s in summaries],
                     np.float64)
    variances = np.array(
        [s.var if s.n > 1 and np.isfinite(s.var) else 0.0 for s in summaries],
        np.float64)
    weights = np.array([s.weight for s in summaries], np.float64)
    occupied = counts > 0
    shift = float(means[occupied].mean()) if occupied.any() else 0.0
    centered = np.where(occupied, means - shift, 0.0)
    sums = counts * centered
    sumsqs = np.maximum(counts - 1.0, 0.0) * variances \
        + counts * centered ** 2
    return StratumTables(counts=counts, sums=sums, sumsqs=sumsqs,
                         weights=weights, shift=shift)


def sweep_point_tables(cpi, valid, weights) -> StratumTables:
    """``StratumTables`` for a one-unit-per-stratum sweep, lane-wise.

    ``cpi``: (A, C, L) per-stratum selected-unit CPI; ``valid``: (A, L)
    pick validity; ``weights``: (A, L) stratum weights. Lanes are
    (app, config): each occupied stratum holds exactly its one selected
    unit — counts ARE the validity mask — so ``stratified_mean`` reduces
    to the covered-weight-renormalized weighted mean the sweep reports.

    This is the sweep estimators' fusable tables stage: counts come from
    the pick mask directly, with no ``segment_stats`` dispatch (each
    stratum contributes one known unit — there is nothing to segment;
    see ``docs/kernels.md``). Namespace-agnostic: numpy in the host
    path, tracers inside the staged jitted program and the fused sweep
    megaprogram alike.
    """
    xp = _ns(cpi, valid, weights)
    counts = xp.broadcast_to(valid[:, None, :], cpi.shape).astype(cpi.dtype)
    return StratumTables(
        counts=counts, sums=xp.where(counts > 0, cpi, 0.0),
        sumsqs=xp.zeros_like(cpi),
        weights=xp.broadcast_to(weights[:, None, :], cpi.shape))


# -------------------------------------------------------------- estimators
def covered_weight(tables: StratumTables):
    """(...) total weight of strata with at least one sampled unit."""
    xp = _ns(tables.counts)
    return xp.where(tables.counts > 0, tables.weights, 0.0).sum(axis=-1)


def total_weight(tables: StratumTables):
    """(...) total stratum weight per lane (≈ 1 for normalized designs)."""
    return tables.weights.sum(axis=-1)


def stratified_mean(tables: StratumTables, *, renormalize: bool = True):
    """Batched eq. (3) point estimate ``ȳ_st = Σ_h W_h ȳ_h``, lane-wise.

    Strata with no sampled units contribute nothing. With
    ``renormalize=True`` (the coverage-contract default) the sum is
    divided by the covered weight, matching ``weighted_point_estimate``;
    with ``renormalize=False`` the lost weight simply vanishes (the
    Fig 8 Monte-Carlo estimator's semantics). Lanes with no covered
    weight at all are NaN.
    """
    xp = _ns(tables.counts, tables.sums)
    term = xp.where(tables.counts > 0,
                    tables.weights * tables.means, 0.0)
    est = term.sum(axis=-1)
    cov = covered_weight(tables)
    if renormalize:
        est = est / xp.where(cov > 0, cov, 1.0)
    return xp.where(cov > 0, est, xp.nan)


def stratified_variance(tables: StratumTables, *, renormalize: bool = True):
    """Batched eq. (3) variance ``v(ȳ_st) = Σ_h W_h² s_h² / n_h``.

    Lane-wise NaN when any stratum with positive weight and sampled
    units has n_h < 2 (s_h² is not estimable — paper fn. 7; collapse
    first). Uncovered strata (n_h = 0) are renormalized away under
    ``renormalize=True``; callers wanting the strict interpretation
    check coverage separately (see the scalar wrappers).
    """
    xp = _ns(tables.counts)
    w = tables.weights
    if renormalize:
        cov = covered_weight(tables)[..., None]
        w = xp.where(tables.counts > 0,
                     w / xp.where(cov > 0, cov, 1.0), 0.0)
    s2 = tables.variances
    occupied = tables.counts > 0
    contrib = xp.where(occupied & (w > 0),
                       (w ** 2) * s2 / xp.maximum(tables.counts, 1.0), 0.0)
    v = contrib.sum(axis=-1)
    bad = (occupied & (tables.weights > 0)
           & (tables.counts < 2)).any(axis=-1)
    return xp.where(bad | (covered_weight(tables) <= 0), xp.nan, v)


def satterthwaite_df(tables: StratumTables):
    """Batched Satterthwaite [30] effective degrees of freedom, lane-wise.

    Strata with n_h < 2 or zero weight are excluded (as in the scalar
    reference); lanes whose denominator is zero get +inf (z interval).
    The statistic is invariant to weight renormalization.
    """
    xp = _ns(tables.counts)
    usable = (tables.counts > 1) & (tables.weights > 0)
    g = xp.where(usable,
                 (tables.weights ** 2) * xp.where(usable, tables.variances,
                                                  0.0)
                 / xp.maximum(tables.counts, 1.0), 0.0)
    num = g.sum(axis=-1)
    den = xp.where(usable, g * g / xp.maximum(tables.counts - 1.0, 1.0),
                   0.0).sum(axis=-1)
    return xp.where(den > 0, num * num / xp.where(den > 0, den, 1.0), xp.inf)


def two_phase_variance(tables: StratumTables, phase1_n, *,
                       formula: str = "phase2_only", phase1_var=None,
                       renormalize: bool = True):
    """Batched two-phase variance — paper eq. (5)/(6), lane-wise.

    ``formula="with_phase1_var"`` is eq. (5): ``s²/n' + Σ W_h² s_h²/n_h``
    and needs ``phase1_var`` (broadcastable to the lane shape).
    ``formula="phase2_only"`` is eq. (6): the phase-1 term is the
    between-stratum spread ``(1/n') Σ W_h (ȳ_h − ȳ)²`` — computable
    without phase-1 y values. ``phase1_n`` may be a scalar or an array
    broadcastable to the lane shape.
    """
    xp = _ns(tables.counts)
    v2 = stratified_variance(tables, renormalize=renormalize)
    if formula == "with_phase1_var":
        if phase1_var is None:
            raise ValueError("eq. (5) needs phase1_var")
        v1 = xp.asarray(phase1_var) / phase1_n
        return v1 + v2
    if formula != "phase2_only":
        raise ValueError(f"unknown formula {formula!r}")
    mean = stratified_mean(tables, renormalize=renormalize)
    w = tables.weights
    if renormalize:
        cov = covered_weight(tables)[..., None]
        w = xp.where(tables.counts > 0,
                     w / xp.where(cov > 0, cov, 1.0), 0.0)
    dev = tables.means - mean[..., None]
    between = xp.where(tables.counts > 0, w * dev * dev, 0.0).sum(axis=-1)
    return between / phase1_n + v2


# ------------------------------------------------- collapse (fn. 7, eq. 4)
def collapse_small_strata(tables: StratumTables, order_key, *,
                          min_count: float = 2):
    """Merge under-sampled strata into their key-order neighbor, lane-wise.

    Replicates ``TwoPhaseFlow.ci_check``'s host algorithm exactly, per
    lane: strata are ordered by ``order_key`` (e.g. baseline-CPI stratum
    means); strata with zero weight and no samples are dropped; walking
    the order, each stratum either closes a group (count ≥ min_count),
    joins the still-open group, or — when undersized after a closed
    group — merges backward into it; a trailing undersized group merges
    backward too. Returns ``(merged, group_of, n_groups)``: merged
    ``StratumTables`` whose group g occupies slot g (trailing slots are
    zero), the per-stratum group assignment (−1 = dropped), and the
    per-lane group count (0 marks a degenerate lane with < min_count
    total samples — estimates there are NaN).
    """
    xp = _ns(tables.counts)
    L = tables.num_strata
    counts, weights = tables.counts, tables.weights
    active = (weights > 0) | (counts > 0)
    key = xp.where(active,
                   xp.broadcast_to(xp.asarray(order_key, counts.dtype),
                                   counts.shape), xp.inf)
    order = _argsort(xp, key)
    c_s = xp.take_along_axis(counts, order, axis=-1)
    a_s = xp.take_along_axis(active, order, axis=-1)

    batch = counts.shape[:-1]
    gid = xp.zeros(batch, dtype=int) - 1
    acc = xp.zeros(batch, dtype=counts.dtype)
    slots = []
    for p in range(L):
        act = a_s[..., p]
        c = c_s[..., p]
        no_grp = gid < 0
        open_ = acc < min_count
        start = act & ((no_grp) | (~open_ & (c >= min_count)))
        gid = xp.where(start, gid + 1, gid)
        acc = xp.where(start, c, xp.where(act, acc + c, acc))
        slots.append(xp.where(act, gid, -1))
    g_sorted = xp.stack(slots, axis=-1)
    # a group with gid > 0 only ever starts on a stratum with
    # c >= min_count, so only group 0 can end undersized — that lane is
    # degenerate (ci_check: "needs at least 2 sampled units")
    n_groups = xp.where(gid < 0, 0, gid + 1)
    n_groups = xp.where((gid == 0) & (acc < min_count), 0, n_groups)

    inv = _argsort(xp, order)
    group_of = xp.take_along_axis(g_sorted, inv, axis=-1)

    onehot = (group_of[..., :, None] == xp.arange(L)).astype(counts.dtype)
    merged = StratumTables(
        counts=(counts[..., :, None] * onehot).sum(axis=-2),
        sums=(tables.sums[..., :, None] * onehot).sum(axis=-2),
        sumsqs=(tables.sumsqs[..., :, None] * onehot).sum(axis=-2),
        weights=(weights[..., :, None] * onehot).sum(axis=-2),
        shift=tables.shift)
    return merged, group_of, n_groups


def collapsed_pairs_variance(y_sorted, w_sorted, n_valid, *,
                             num_strata: int):
    """Batched pairwise collapsed-strata variance (paper eq. 4), lane-wise.

    Args:
      y_sorted: ``(..., L)`` — the single sampled value per stratum,
        gathered into key order with the ``n_valid`` occupied strata
        first (positions ≥ n_valid are ignored).
      w_sorted: stratum weights in the same order (broadcastable).
      n_valid: (...) occupied-stratum count V per lane (broadcastable).
      num_strata: L (static).

    Groups are neighbor pairs in the sorted order; an odd V makes the
    final three strata one group whose variance is their sample variance
    (exactly the scalar ``collapsed_strata_estimate`` grouping). Per
    pair, eq. (4): ``s² = (y₁ − y₂)²/4`` entering the stratified formula
    with n_h = 1. Returns ``(variance, df)`` — both NaN for lanes with
    V < 2; ``df = V − ⌊V/2⌋`` ([18]: L − J).
    """
    return collapsed_pairs_grouped(
        y_sorted, collapsed_pairs_groups(w_sorted, n_valid,
                                         num_strata=num_strata), n_valid)


def collapsed_pairs_groups(w_sorted, n_valid, *, num_strata: int):
    """The y-free half of ``collapsed_pairs_variance``: per neighbor
    group ``j`` of the sorted order, ``(wsq, in_grp, has3)`` — each
    ``(..., J)`` with ``J = L // 2`` — the group's summed squared
    weights, whether it is one of the lane's ``⌊V/2⌋`` groups, and
    whether it is the final triple of an odd V.

    The Monte-Carlo engine computes these once per app on the host: the
    same weight arithmetic inside every trial program would be fused
    (and rounded) differently per program shape.
    """
    xp = _ns(w_sorted, n_valid)
    L = int(num_strata)
    v_cnt = xp.asarray(n_valid)
    n_groups = v_cnt // 2
    odd = (v_cnt % 2) == 1
    shape = xp.broadcast_shapes(xp.shape(w_sorted)[:-1], xp.shape(v_cnt))
    wsq, in_grp, has3 = [], [], []
    for j in range(L // 2):
        p1, p2, p3 = 2 * j, 2 * j + 1, min(2 * j + 2, L - 1)
        tri = odd & (j == n_groups - 1)
        w1, w2, w3 = (w_sorted[..., p] for p in (p1, p2, p3))
        wsq.append(xp.broadcast_to(
            w1 ** 2 + w2 ** 2 + xp.where(tri, w3 ** 2, 0.0), shape))
        in_grp.append(xp.broadcast_to(j < n_groups, shape))
        has3.append(xp.broadcast_to(tri, shape))
    if not wsq:                               # L < 2: no groups
        return (xp.zeros(shape + (0,), xp.asarray(w_sorted).dtype),
                xp.zeros(shape + (0,), bool), xp.zeros(shape + (0,), bool))
    return tuple(xp.stack(g, axis=-1) for g in (wsq, in_grp, has3))


def collapsed_pairs_grouped(y_sorted, groups, n_valid):
    """``collapsed_pairs_variance`` from precomputed
    ``collapsed_pairs_groups``; ``y_sorted``: ``(..., L)``, groups and
    ``n_valid`` broadcastable against its leading axes."""
    wsq_g, in_grp_g, has3_g = groups
    xp = _ns(y_sorted, wsq_g, n_valid)
    L = xp.shape(y_sorted)[-1]
    v_cnt = xp.asarray(n_valid)
    var = xp.zeros(xp.broadcast_shapes(
        xp.shape(y_sorted)[:-1], xp.shape(wsq_g)[:-1],
        xp.shape(v_cnt)), dtype=xp.asarray(y_sorted).dtype)
    for j in range(xp.shape(wsq_g)[-1]):
        p1, p2, p3 = 2 * j, 2 * j + 1, min(2 * j + 2, L - 1)
        y1, y2, y3 = (y_sorted[..., p] for p in (p1, p2, p3))
        s2_pair = (y1 - y2) ** 2 / 4.0
        m3 = (y1 + y2 + y3) / 3.0
        s2_tri = ((y1 - m3) ** 2 + (y2 - m3) ** 2 + (y3 - m3) ** 2) / 2.0
        s2 = xp.where(has3_g[..., j], s2_tri, s2_pair)
        var = var + xp.where(in_grp_g[..., j], wsq_g[..., j] * s2, 0.0)
    bad = v_cnt < 2
    n_groups = v_cnt // 2
    var = xp.where(bad, xp.nan, var)
    df = xp.where(bad, xp.nan, (v_cnt - n_groups).astype(var.dtype))
    return var, df


# ------------------------------------------------------------- allocation
def proportional_allocation(weights, n_total, *, min_per_stratum: int = 2):
    """Batched proportional allocation: n_h ∝ W_h, each ≥ min_per_stratum.

    ``weights``: ``(..., L)``; ``n_total`` scalar or ``(...)``. Returns
    int allocations ``(..., L)`` using the same largest-remainder fixup
    as the scalar reference (overshoot accepted when minima force it).
    """
    xp = _ns(weights)
    # host lanes promote to f64 (the exact reference); device lanes keep
    # the caller's trace dtype (f32 default, f64 under an x64 policy)
    w = xp.asarray(weights, np.float64) if xp is np else xp.asarray(weights)
    nt = xp.asarray(n_total)
    raw = w * (nt[..., None] if nt.ndim else nt)
    n_h = xp.maximum(xp.floor(raw).astype(int), min_per_stratum)
    return _largest_remainder_fixup(n_h, raw, n_total)


def neyman_allocation(weights, stds, n_total, *, min_per_stratum: int = 2):
    """Batched Neyman allocation: n_h ∝ W_h·S_h (optimal for fixed n).

    Lanes whose W·S products are all zero fall back to proportional
    allocation (mirroring the scalar reference), lane-wise.
    """
    xp = _ns(weights, stds)
    w = xp.asarray(weights)
    s = xp.maximum(xp.asarray(stds), 0.0)
    prod = w * s
    tot = prod.sum(axis=-1, keepdims=True)
    zero = tot <= 0
    share = prod / xp.where(zero, 1.0, tot)
    nt = xp.asarray(n_total)
    raw = share * (nt[..., None] if nt.ndim else nt)
    n_h = xp.maximum(xp.floor(raw).astype(int), min_per_stratum)
    ney = _largest_remainder_fixup(n_h, raw, n_total)
    prop = proportional_allocation(w, n_total,
                                   min_per_stratum=min_per_stratum)
    return xp.where(zero, prop, ney)


def _largest_remainder_fixup(n_h, raw, n_total):
    """Lane-wise largest-remainder rounding to hit the n_total budget.

    Exactly the scalar rule: distribute the deficit one unit at a time
    in descending fractional-remainder order, wrapping around; a
    negative deficit (minima overshoot) is accepted.
    """
    xp = _ns(n_h, raw)
    L = n_h.shape[-1]
    deficit = (xp.asarray(n_total) - n_h.sum(axis=-1)).astype(int)
    deficit = xp.maximum(deficit, 0)
    frac = raw - xp.floor(raw)
    # rank 0 = largest remainder (stable, matching argsort of -frac)
    order = _argsort(xp, -frac)
    rank = _argsort(xp, order)
    extra = deficit[..., None] // L + (
        rank < (deficit[..., None] % L)).astype(int)
    return n_h + extra


# ------------------------------------------------------------- SRS helper
def masked_srs_stats(x, valid):
    """Lane-wise SRS sample mean and variance-of-the-mean (paper eq. 2).

    ``x``: ``(..., n)`` values; ``valid``: broadcastable bool mask.
    Returns ``(mean, v_mean, n)`` with ``v_mean = s²/n`` (ddof=1); lanes
    with n < 2 get NaN variance, n = 0 NaN mean.
    """
    xp = _ns(x)
    v = xp.broadcast_to(xp.asarray(valid, bool), xp.shape(x))
    n = v.sum(axis=-1).astype(xp.asarray(x).dtype)
    safe_n = xp.maximum(n, 1.0)
    mean = xp.where(v, x, 0.0).sum(axis=-1) / safe_n
    ss = xp.where(v, (x - mean[..., None]) ** 2, 0.0).sum(axis=-1)
    s2 = xp.where(n > 1, ss / xp.maximum(n - 1.0, 1.0), xp.nan)
    mean = xp.where(n > 0, mean, xp.nan)
    return mean, s2 / safe_n, n


# ----------------------------------------------- streaming trial statistics
# Log-histogram sketch grid shared by every TrialStats: 4096 bins over
# [1e-6, 1e6) gives ~0.68% relative resolution — far below the Monte-Carlo
# noise of any quantile read from it. Percent errors and absolute CI
# half-widths both live comfortably inside this range; out-of-range values
# clip into the edge bins.
TRIAL_HIST_BINS = 4096
TRIAL_HIST_LO = 1e-6
TRIAL_HIST_HI = 1e6
_HIST_LOG_LO = float(np.log(TRIAL_HIST_LO))
_HIST_LOG_SPAN = float(np.log(TRIAL_HIST_HI) - np.log(TRIAL_HIST_LO))


@dataclasses.dataclass(frozen=True)
class TrialStats:
    """Streaming-accumulable Monte-Carlo trial statistics, batched.

    Every leaf is *additive*: chunk updates, cross-chunk scan carries and
    cross-device ``psum`` merges are all elementwise sums, so any
    chunking or sharding of the trial axis accumulates to the same
    totals — bitwise for the integer leaves (trial counts, coverage
    counts, histogram sketches) and up to float summation order for the
    moment sums (the streaming trial program pins that order:
    ``repro.experiments.montecarlo._block_moments``). Leading axes (``...``) are batch lanes (apps); per-trial
    ``T``-axis arrays never materialize.

    ``err_hist``/``half_hist`` are log-spaced histogram sketches over
    ``[TRIAL_HIST_LO, TRIAL_HIST_HI)``; quantile readouts (the Fig 8
    p95) come from ``log_hist_quantile``. Registered as a jax pytree so
    the stats ride a ``lax.scan`` carry and cross ``shard_map``
    boundaries.
    """

    count: np.ndarray | jax.Array      # (...,) valid trials accumulated
    cover: np.ndarray | jax.Array      # (...,) trials whose CI covered truth
    err_sum: np.ndarray | jax.Array    # (...,) Σ pct |error|   (accum dtype)
    err_sumsq: np.ndarray | jax.Array  # (...,) Σ pct |error|²
    half_n: np.ndarray | jax.Array     # (...,) trials with finite half-width
    half_sum: np.ndarray | jax.Array   # (...,) Σ CI half-width
    half_sumsq: np.ndarray | jax.Array  # (...,) Σ half-width²
    err_hist: np.ndarray | jax.Array   # (..., B) log-bucketed error counts
    half_hist: np.ndarray | jax.Array  # (..., B) log-bucketed half counts

    # host-side readouts -----------------------------------------------
    @property
    def coverage(self):
        """(...) empirical coverage: covered / valid trials (NaN if 0)."""
        xp = _ns(self.count)
        denom = xp.maximum(self.count, 1).astype(np.float64)
        return xp.where(self.count > 0, self.cover / denom, xp.nan)

    @property
    def err_mean(self):
        """(...) mean percent |error| over trials with finite error."""
        xp = _ns(self.count)
        n = self.err_hist.sum(axis=-1)
        return xp.where(n > 0, self.err_sum / xp.maximum(n, 1), xp.nan)

    @property
    def half_mean(self):
        """(...) mean CI half-width over trials with a finite interval
        (the streamed analogue of ``nanmean`` over per-trial widths)."""
        xp = _ns(self.count)
        return xp.where(self.half_n > 0,
                        self.half_sum / xp.maximum(self.half_n, 1), xp.nan)

    def err_quantile(self, q: float):
        """(...) q-quantile of percent |error| from the sketch (host)."""
        return log_hist_quantile(self.err_hist, q)

    def half_quantile(self, q: float):
        """(...) q-quantile of the CI half-width from the sketch (host)."""
        return log_hist_quantile(self.half_hist, q)


jax.tree_util.register_pytree_node(
    TrialStats,
    lambda s: ((s.count, s.cover, s.err_sum, s.err_sumsq, s.half_n,
                s.half_sum, s.half_sumsq, s.err_hist, s.half_hist), None),
    lambda _, leaves: TrialStats(*leaves))


def trial_stats_init(batch_shape, *, bins: int = TRIAL_HIST_BINS,
                     accum_dtype=None, xp=np) -> TrialStats:
    """Zeroed accumulator for ``batch_shape`` lanes (the scan carry init).

    ``accum_dtype`` is the float-moment dtype, defaulting to the
    policy's ``PrecisionPolicy.accum``; the counters and sketches are
    int32 regardless — they are exact in any policy.
    """
    if accum_dtype is None:
        accum_dtype = DEFAULT_PRECISION.accum_dtype
    bs = tuple(batch_shape)
    zi = xp.zeros(bs, np.int32)
    zf = xp.zeros(bs, accum_dtype)
    zh = xp.zeros(bs + (int(bins),), np.int32)
    return TrialStats(count=zi, cover=zi, err_sum=zf, err_sumsq=zf,
                      half_n=zi, half_sum=zf, half_sumsq=zf,
                      err_hist=zh, half_hist=zh)


def _log_bucket(x, xp, bins: int):
    """Histogram bin index of ``x`` on the shared log grid (clipped)."""
    pos = xp.isfinite(x) & (x > 0)
    safe = xp.where(pos, x, TRIAL_HIST_LO)
    b = xp.floor((xp.log(safe) - _HIST_LOG_LO) * (bins / _HIST_LOG_SPAN))
    return xp.clip(b, 0, bins - 1).astype(np.int32)


def _hist_add(hist, values, mask, xp):
    """``hist + histogram(values[mask])`` lane-wise, namespace-agnostic.

    Lanes are flattened into one offset-bincount / scatter-add so a whole
    chunk folds in with a single dispatch (mirrors the flat-segment trick
    of ``_stratum_tables_np``).
    """
    bins = hist.shape[-1]
    lead = hist.shape[:-1]
    lanes = int(np.prod(lead, dtype=np.int64)) if lead else 1
    t = values.shape[-1]
    idx = _log_bucket(values, xp, bins).reshape(lanes, t)
    flat = (idx + bins * xp.arange(lanes, dtype=np.int32)[:, None]).reshape(-1)
    w = xp.broadcast_to(mask, values.shape).reshape(-1).astype(np.int32)
    if xp is np:
        add = np.bincount(flat, weights=w,
                          minlength=lanes * bins).astype(np.int32)
    else:
        add = jnp.zeros(lanes * bins, jnp.int32).at[flat].add(w)
    return hist + add.reshape(hist.shape)


def trial_stats_update(stats: TrialStats, err, half, covered,
                       valid) -> TrialStats:
    """Fold one chunk of per-trial outcomes into the running statistics.

    ``err``/``half`` are ``(..., Tc)`` per-trial chunk outcomes,
    ``covered`` the per-trial CI-covers-truth booleans, and ``valid``
    a broadcastable mask dropping padding trials (the chunk grid rounds
    the trial count up). Float moments are cast to the accumulator dtype
    *before* summing; counters stay int32 (exact, order-independent —
    the bitwise half of the chunked == unchunked contract).
    """
    xp = _ns(stats.count, err)
    acc = stats.err_sum.dtype

    def moments(x, m):
        xc = xp.where(m, x, 0).astype(acc)
        return xc.sum(axis=-1), (xc * xc).sum(axis=-1)

    # named scopes label the device time of a traced trial scan
    with jax.named_scope("trials.fold"):
        v = xp.broadcast_to(xp.asarray(valid, bool), err.shape)
        err_ok = v & xp.isfinite(err)
        half_ok = v & xp.isfinite(half)
        err_s, err_ss = moments(err, err_ok)
        half_s, half_ss = moments(half, half_ok)
        folded = dict(
            count=stats.count + v.sum(axis=-1).astype(np.int32),
            cover=stats.cover + (v & covered).sum(axis=-1).astype(np.int32),
            err_sum=stats.err_sum + err_s,
            err_sumsq=stats.err_sumsq + err_ss,
            half_n=stats.half_n + half_ok.sum(axis=-1).astype(np.int32),
            half_sum=stats.half_sum + half_s,
            half_sumsq=stats.half_sumsq + half_ss)
    with jax.named_scope("trials.hist"):
        return TrialStats(
            **folded,
            err_hist=_hist_add(stats.err_hist, err, err_ok, xp),
            half_hist=_hist_add(stats.half_hist, half, half_ok, xp))


def trial_stats_merge(a: TrialStats, b: TrialStats) -> TrialStats:
    """Merge two partial accumulations (host-side analogue of the
    in-program ``psum`` over the trial mesh axis)."""
    return jax.tree.map(lambda x, y: x + y, a, b)


def log_hist_quantile(hist, q: float):
    """(...) quantile readout from a log-histogram sketch (host, numpy).

    Returns the geometric center of the bin holding the q-th order
    statistic; NaN for empty lanes. Accurate to one bin width (~0.68%
    relative at the default grid) plus the gap between neighboring order
    statistics — the parity test vs ``np.percentile`` on the dense path
    bounds both.
    """
    h = np.asarray(hist, np.float64)
    bins = h.shape[-1]
    tot = h.sum(axis=-1)
    cum = np.cumsum(h, axis=-1)
    idx = np.argmax(cum >= q * tot[..., None], axis=-1)
    centers = np.exp(_HIST_LOG_LO
                     + (np.arange(bins) + 0.5) * (_HIST_LOG_SPAN / bins))
    return np.where(tot > 0, centers[idx], np.nan)
