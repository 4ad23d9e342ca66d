"""Plain one-unit-per-stratum selection and the sweep's weighted estimate.

For each app and stratum the policies pick, among the stratum's members:

* ``centroid``: the member nearest its stratum's centroid (squared
  distance in the stratifier's features; a one-dimensional stratifier
  uses the baseline CPI and the stratum's mean baseline);
* ``mean``: the member whose baseline CPI is nearest the stratum's mean
  baseline;
* ``ranked_set``: the member at rank ``rint(0.5 (count - 1))`` of the
  stratum in baseline order;
* ``random``: member ``floor(u * count)`` in index order, ``u`` the
  selection seed's ``default_rng(seed).random((A, L))`` draw, the
  product rounded to float32.

Ties go to the lower index; members whose key lies within ``TIE`` of the
pick's (relative to the key's scale; ``TIE_MEAN`` where the key is a
distance from the stratum's mean baseline) are returned as near-ties,
which the program's float32 may resolve the other way. The estimate of
an (app, config) is the stratum-weighted mean of the picks' CPI over the
occupied strata, with
weights count / pool size. ``dtype`` float64 is the reference; an
``ml_dtypes.bfloat16`` dtype computes distances, means and the estimate
in bfloat16 (each operation rounded), the control.
"""

from __future__ import annotations

import numpy as np


TIE = 2e-6   # relative key distance under which float32 rounding decides
# The program's stratum mean is a float32 sum: products accumulated on
# the MXU over 1,024-row tiles, then tile after tile (up to 118 tiles),
# good to a few 1e-6 of the mean; a distance from it is known to no better
TIE_MEAN = 1e-5


def _f64(x):
    return np.asarray(x).astype(np.float64)


def stratum_order(labels, valid, L: int):
    """Per app: (counts (L,), offsets (L,)) and the members sorted by
    stratum then index."""
    lab = np.where(valid, labels, L)
    counts = np.bincount(lab, minlength=L + 1)[:L]
    order = np.argsort(lab, kind="stable")
    return counts, np.cumsum(counts) - counts, order


def pick_app(policy: str, labels, valid, baseline, feats, centroids,
             L: int, u=None, dtype=np.float64):
    """(L,) local positions picked in one app, (L,) occupied flags, and
    per stratum the local positions of its near-ties: members whose key
    lies so close to the pick's that float32 rounding could have picked
    them instead (``TIE`` of the key's scale; none for ``random``).

    ``labels``/``valid``/``baseline``: (n,); ``feats``: (n, d) or None
    (then the baseline is the feature and the stratum mean baseline the
    centroid); ``centroids``: (L, d) or None; ``u``: (L,) uniforms for
    ``random``."""
    labels = np.asarray(labels, np.int64)
    valid = np.asarray(valid, bool)
    counts, offsets, order = stratum_order(labels, valid, L)
    occupied = counts > 0
    n = len(labels)
    idx = np.arange(n)
    lab = np.where(valid, labels, L)
    none = [np.empty(0, np.int64)] * L
    if policy == "random":
        prod = (np.asarray(u, np.float32).astype(dtype)
                * counts.astype(dtype)).astype(np.float32)
        pos = offsets + np.minimum(prod.astype(np.int64),
                                   np.maximum(counts - 1, 0))
        return order[np.minimum(pos, n - 1)], occupied, none
    b = np.asarray(baseline).astype(dtype)
    sums = np.bincount(lab, weights=_f64(b), minlength=L + 1)[:L]
    base_mean = (sums / np.maximum(counts, 1)).astype(dtype)
    own = np.clip(lab, 0, L - 1)
    if policy == "ranked_set":
        key = _f64(b)
        rs = np.lexsort((idx, key, lab))
        rank = np.rint(0.5 * np.maximum(counts - 1, 0)).astype(np.int64)
        pick = rs[np.minimum(offsets + rank, n - 1)]
        # a member whose baseline rounds level with the pick's can swap
        # ranks with it
        d = np.abs(key - key[pick][own])
        tol = TIE * np.abs(key[pick])
    elif policy == "mean" or (policy == "centroid" and feats is None):
        d = np.abs(b - base_mean[own])
        if policy == "centroid":
            d = d * d
        tol = TIE_MEAN * np.abs(_f64(base_mean))
        if policy == "centroid":
            tol = tol * tol + 2 * tol * np.abs(_f64(base_mean))
    elif policy == "centroid":
        z = np.asarray(feats).astype(dtype)
        c = np.asarray(centroids).astype(dtype)
        diff = z - c[own]
        d = (diff * diff).sum(axis=1, dtype=dtype)
        cn = np.sqrt((_f64(c) ** 2).sum(1))
        tol = None
    else:
        raise KeyError(f"unknown policy {policy!r}")
    d = _f64(d)
    best = np.lexsort((idx, d, lab))
    pick = best[np.minimum(offsets, n - 1)]
    if policy == "centroid" and feats is not None:
        dmin = d[pick]
        tol = TIE * (cn * np.sqrt(dmin) + dmin)
    if policy != "ranked_set":
        d = d - d[pick][own]
    near = valid & (lab < L) & (d <= tol[own]) & (idx != pick[own])
    alts = [np.flatnonzero(near & (lab == h)) for h in range(L)]
    return pick, occupied, alts


def estimate(cpi_sel, weights, occupied, truth, dtype=np.float64):
    """(C,) weighted estimate and percent error from (C, L) picked CPI."""
    w = np.where(occupied, weights, 0.0).astype(dtype)
    cpi = np.asarray(cpi_sel).astype(dtype)
    num = (cpi * w[None, :]).sum(axis=1, dtype=dtype)
    est = _f64(num / w.sum(dtype=dtype))
    return est, 100.0 * np.abs(est - truth) / truth
