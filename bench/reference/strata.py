"""Checks of a build's strata by what they say, from the reference's own
features.

A k-means fit answers with centroids and labels: every unit's label
names the centroid nearest to it, and every centroid is the mean of its
members (the fixed point Lloyd's iteration stops at). Both are checked
in float64 from features the reference generated itself. A Dalenius-
Gurney partition is recomputed by the plain rule from the reference's
own baseline. ``control_*`` gives what a fit one precision step below
the program's float32 would hold: bfloat16 assignment and centroids.
"""

from __future__ import annotations

import functools

import numpy as np


def nearest(z: np.ndarray, centroids: np.ndarray,
            block: int = 65536) -> np.ndarray:
    """Nearest centroid of every row, by the direct squared distance in
    float64 (ties to the lower index)."""
    out = np.empty(len(z), np.int64)
    for s in range(0, len(z), block):
        d2 = ((z[s:s + block, None, :] - centroids[None]) ** 2).sum(-1)
        out[s:s + block] = d2.argmin(1)
    return out


def off_centroid_count(z, labels, centroids) -> tuple[int, int]:
    """(units whose label is not their nearest centroid, units)."""
    near = nearest(np.asarray(z, np.float64),
                   np.asarray(centroids, np.float64))
    return int((near != np.asarray(labels)).sum()), len(near)


def control_labels(z, centroids) -> np.ndarray:
    """Labels a bfloat16 nearest-centroid assignment gives (jax on the
    default device), for the control reading."""
    import jax.numpy as jnp

    zb = jnp.asarray(z, jnp.bfloat16)
    cb = jnp.asarray(centroids, jnp.bfloat16)
    out = []
    for s in range(0, len(z), 65536):
        d2 = ((zb[s:s + 65536, None, :] - cb[None]) ** 2).sum(-1)
        out.append(np.asarray(d2.argmin(1)))
    return np.concatenate(out)


def member_means(z, labels, num_strata: int):
    """(means (L, d), counts (L,)) of the rows of ``z`` per label, in
    float64; an empty stratum's mean is 0."""
    z = np.asarray(z, np.float64)
    lab = np.asarray(labels, np.int64)
    counts = np.bincount(lab, minlength=num_strata)[:num_strata]
    sums = np.stack([np.bincount(lab, weights=z[:, j],
                                 minlength=num_strata)[:num_strata]
                     for j in range(z.shape[1])], 1)
    return sums / np.maximum(counts, 1)[:, None], counts


def centroid_gap(z, labels, centroids) -> float:
    """How far a fit's centroids lie from the mean of their members, the
    fixed point of Lloyd's iteration: the largest distance over the
    occupied strata, in units of the fit's RMS distance of a unit from
    its stratum mean (both from the reference's features).

    A column that is constant over the units (a counter that no region
    changes, such as a stall bin the configuration pins) adds the same
    amount to every unit's distance from a centroid: a centroid's value
    there moves no label and no nearest unit, and any value is a fixed
    point. Such columns are left out."""
    z = np.asarray(z, np.float64)
    live = np.ptp(z, axis=0) > 0
    z = z[:, live]
    c = np.asarray(centroids, np.float64)[:, live]
    means, counts = member_means(z, labels, len(c))
    radius = np.sqrt(np.mean(
        ((z - means[np.asarray(labels, np.int64)]) ** 2).sum(1)))
    dist = np.sqrt(((c - means) ** 2).sum(1))[counts > 0]
    return float(dist.max() / max(radius, 1e-300))


def control_centroids(z, labels, num_strata: int) -> np.ndarray:
    """The members' means held in bfloat16: the centroids a fit one
    precision step below float32 would keep."""
    import ml_dtypes

    means, _ = member_means(z, labels, num_strata)
    return means.astype(ml_dtypes.bfloat16).astype(np.float64)


def dalenius_gurney(x, num_strata: int, max_iters: int = 200,
                    tol: float = 1e-3) -> np.ndarray:
    """Dalenius-Gurney strata of a scalar (the paper's eq. 7, Appendix
    A.E): cut the sorted values into ``num_strata`` equal-count
    intervals, then move each interior cut one step (1/16 of the
    neighbouring stratum, at least one unit) towards the side whose
    W_h s_h is smaller, boundary by boundary in order, until the
    products agree to ``tol`` of their mean, no cut moves, or
    ``max_iters`` passes are made. Ties in x keep index order. Returns
    the label of every unit."""
    x = np.asarray(x, np.float64).reshape(-1)
    n, L = len(x), int(num_strata)
    order = np.argsort(x, kind="stable")
    xs = x[order]
    cuts = np.linspace(0, n, L + 1).round().astype(int)

    @functools.lru_cache(maxsize=None)
    def product(lo, hi):
        seg = xs[lo:hi]
        return seg.size / n * (seg.std(ddof=1) if seg.size > 1 else 0.0)

    def products():
        return np.array([product(int(cuts[h]), int(cuts[h + 1]))
                         for h in range(L)])

    for _ in range(max_iters):
        p = products()
        if p.mean() > 0 and (p.max() - p.min()) / p.mean() < tol:
            break
        moved = False
        for b in range(1, L):
            if p[b - 1] > p[b] and cuts[b] - cuts[b - 1] > 1:
                cuts[b] -= max(1, (cuts[b] - cuts[b - 1]) // 16)
                moved = True
            elif p[b] > p[b - 1] and cuts[b + 1] - cuts[b] > 1:
                cuts[b] += max(1, (cuts[b + 1] - cuts[b]) // 16)
                moved = True
            if moved:
                p = products()
        if not moved:
            break
    labels = np.empty(n, np.int64)
    labels[order] = np.repeat(np.arange(L), np.diff(cuts))
    return labels


def standardize(x: np.ndarray) -> np.ndarray:
    """Columns to zero mean and unit population variance (a constant
    column keeps scale 1), as the RFV stratification defines them."""
    x = np.asarray(x, np.float64)
    mean = x.mean(0)
    scale = np.sqrt(((x - mean) ** 2).mean(0))
    scale = np.where(scale > 1e-12, scale, 1.0)
    return (x - mean) / scale


def project_bbvs(bbv: np.ndarray, proj: np.ndarray) -> np.ndarray:
    """L1-normalized BBV rows times the projection, in float64."""
    x = np.asarray(bbv, np.float64)
    x = x / np.maximum(np.abs(x).sum(1, keepdims=True), 1e-12)
    return x @ np.asarray(proj, np.float64)


def projection_matrix(d_in: int, d_out: int, seed: int = 0) -> np.ndarray:
    """The BBV projection: standard normals from ``PRNGKey(seed)`` over
    ``sqrt(d_out)``."""
    import jax

    g = np.asarray(jax.random.normal(jax.random.PRNGKey(seed),
                                     (d_in, d_out), np.float32))
    return g.astype(np.float64) / np.sqrt(float(d_out))
