"""Plain Monte-Carlo study: the statistics ``run_trials`` streams, computed
trial block by trial block with no scan, chunking or kernel.

Semantics (the paper's Fig. 8 study at one config):

* ``random``: each trial draws ``n`` regions uniformly from the census,
  estimates the mean CPI, and builds the eq. (2) t-interval;
* a stratified scheme: each trial draws one unit uniformly from every
  non-empty stratum, weights it by the stratum weight (count / pool
  size), and builds the eq. (4) collapsed-pairs interval over the
  occupied strata in the order of their mean baseline CPI;
* a trial's percent error is ``100 |est - truth| / truth``; its interval
  covers when ``|est - truth| <= half-width``.

Draw contract: trial block ``b`` (256 trials) of app ``a`` uses
``uniform(fold_in(fold_in(fold_in(PRNGKey(seed), s), b), a))`` in
float32, ``s`` the scheme's position in (random, bbv, rfv, dg); a uniform
``u`` picks position ``floor(u * count)`` of the stratum (or census) in
index order, with the product rounded to float32.

Everything after the pick is float64 in numpy for the reference. With
``xp=jax.numpy`` and ``dtype=bfloat16`` the same code is the control.
"""

from __future__ import annotations

import functools

import numpy as np
from scipy import stats as sps

TRIAL_BLOCK = 256
BLOCKS_PER_STEP = 16       # trial blocks the reference evaluates at once
SCHEMES = ("random", "bbv", "rfv", "dg")
HIST_BINS = 4096
HIST_LO, HIST_HI = 1e-6, 1e6
_LOG_LO = float(np.log(HIST_LO))
_LOG_SPAN = float(np.log(HIST_HI) - np.log(HIST_LO))


@functools.lru_cache(maxsize=None)
def _uniform_fn(draws: int):
    import jax

    def blocks(key, b0, nb, n_apps):
        def one(b):
            bk = jax.random.fold_in(key, b)
            return jax.vmap(lambda a: jax.random.uniform(
                jax.random.fold_in(bk, a), (TRIAL_BLOCK, draws),
                np.float32))(jax.numpy.arange(n_apps))
        u = jax.vmap(one)(b0 + jax.numpy.arange(nb))   # (nb, A, 256, D)
        return u.transpose(1, 0, 2, 3).reshape(n_apps, nb * TRIAL_BLOCK,
                                               draws)
    return jax.jit(blocks, static_argnums=(2, 3))


def uniforms(seed: int, scheme: str, b0: int, nb: int, n_apps: int,
             draws: int) -> np.ndarray:
    """(A, nb * 256, draws) float32 uniforms of blocks b0 .. b0+nb-1."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             SCHEMES.index(scheme))
    return np.asarray(_uniform_fn(draws)(key, b0, nb, n_apps))


def critical(dfs, confidence: float = 0.95) -> np.ndarray:
    d = np.asarray(dfs, np.float64)
    q = 1.0 - (1.0 - confidence) / 2.0
    use_z = ~np.isfinite(d) | (d >= 1e6)
    return np.where(use_z, sps.norm.ppf(q),
                    sps.t.ppf(q, np.maximum(np.where(use_z, 1.0, d), 1.0)))


def stratified_setup(labels, valid, pool, baseline, num_strata: int):
    """Per-app gather tables, weights and collapsed-pairs geometry of one
    stratification over its pool: labels/valid/pool/baseline are (A, n)."""
    L = num_strata
    a_n = labels.shape[0]
    lab = np.where(valid, labels, L)
    counts = np.stack([np.bincount(lab[a], minlength=L + 1)[:L]
                       for a in range(a_n)])
    n_pool = valid.sum(1)
    weights = counts / n_pool[:, None]
    order = np.argsort(lab, axis=1, kind="stable")
    offsets = np.cumsum(counts, 1) - counts
    sorted_vals = np.take_along_axis(np.asarray(pool, np.float64), order, 1)
    base = np.where(valid, np.asarray(baseline, np.float64), 0.0)
    sums = np.stack([np.bincount(lab[a], weights=base[a],
                                 minlength=L + 1)[:L] for a in range(a_n)])
    key = np.where(counts > 0, sums / np.maximum(counts, 1), np.inf)
    key_order = np.argsort(key, axis=1, kind="stable")
    n_occ = (counts > 0).sum(1)
    w_sorted = np.take_along_axis(weights, key_order, 1)
    n_groups = n_occ // 2
    odd = n_occ % 2 == 1
    wsq, in_grp, has3 = [], [], []
    for j in range(L // 2):
        p1, p2, p3 = 2 * j, 2 * j + 1, min(2 * j + 2, L - 1)
        tri = odd & (j == n_groups - 1)
        wsq.append(w_sorted[:, p1] ** 2 + w_sorted[:, p2] ** 2
                   + np.where(tri, w_sorted[:, p3] ** 2, 0.0))
        in_grp.append(j < n_groups)
        has3.append(tri)
    crit = critical(np.maximum(n_occ - n_groups, 1).astype(np.float64))
    return dict(counts=counts, offsets=offsets, sorted_vals=sorted_vals,
                weights=weights, key_order=key_order, n_occ=n_occ,
                wsq=np.stack(wsq, 1), in_grp=np.stack(in_grp, 1),
                has3=np.stack(has3, 1), crit=crit)


def _index(u, counts, xp, idx_dtype):
    """floor(u * count) clamped to the last member, product rounded to
    ``idx_dtype``."""
    prod = xp.asarray(u, idx_dtype) * xp.asarray(counts, idx_dtype)
    idx = prod.astype(np.int32)
    return xp.minimum(idx, xp.maximum(xp.asarray(counts, np.int32) - 1, 0))


def stratified_block(u, setup, truth, xp=np, dtype=np.float64):
    """Per-trial (est, err, half, covered) for (A, T, L) uniforms."""
    idx_dt = np.float32 if np.dtype(dtype).itemsize >= 4 else dtype
    c = setup["counts"][:, None, :]
    pick = xp.asarray(setup["offsets"][:, None, :]) + _index(u, c, xp, idx_dt)
    pick = xp.minimum(pick, setup["sorted_vals"].shape[1] - 1)
    vals = xp.take_along_axis(
        xp.asarray(setup["sorted_vals"], dtype)[:, None, :], pick, axis=2)
    w = xp.asarray(np.where(setup["counts"] > 0, setup["weights"], 0.0),
                   dtype)[:, None, :]
    est = (vals * w).sum(-1)
    t = xp.asarray(truth, dtype)[:, None]
    err = xp.asarray(100.0, dtype) * xp.abs(est - t) / t
    ko = np.broadcast_to(setup["key_order"][:, None, :], vals.shape)
    y = xp.take_along_axis(vals, xp.asarray(ko), axis=2)
    L = y.shape[-1]
    var = xp.zeros(est.shape, dtype)
    for j in range(setup["wsq"].shape[1]):
        p1, p2, p3 = 2 * j, 2 * j + 1, min(2 * j + 2, L - 1)
        y1, y2, y3 = y[..., p1], y[..., p2], y[..., p3]
        s2_pair = (y1 - y2) ** 2 / 4.0
        m3 = (y1 + y2 + y3) / 3.0
        s2_tri = ((y1 - m3) ** 2 + (y2 - m3) ** 2 + (y3 - m3) ** 2) / 2.0
        s2 = xp.where(xp.asarray(setup["has3"][:, j:j + 1]), s2_tri, s2_pair)
        wsq = xp.asarray(setup["wsq"][:, j:j + 1], dtype)
        var = var + xp.where(xp.asarray(setup["in_grp"][:, j:j + 1]),
                             wsq * s2, 0.0)
    var = xp.where(xp.asarray(setup["n_occ"][:, None] < 2), np.nan, var)
    half = xp.asarray(setup["crit"], dtype)[:, None] * xp.sqrt(var)
    covered = xp.abs(est - t) <= half
    return est, err, half, covered


def srs_block(u, census, n_valid, truth, crit, xp=np, dtype=np.float64):
    """Per-trial (est, err, half, covered) of n-unit uniform draws."""
    idx_dt = np.float32 if np.dtype(dtype).itemsize >= 4 else dtype
    n = u.shape[2]
    idx = _index(u, n_valid[:, None, None], xp, idx_dt)
    vals = xp.take_along_axis(xp.asarray(census, dtype)[:, None, :], idx,
                              axis=2)
    est = vals.sum(-1) / n
    t = xp.asarray(truth, dtype)[:, None]
    err = xp.asarray(100.0, dtype) * xp.abs(est - t) / t
    ss = ((vals - est[..., None]) ** 2).sum(-1)
    half = xp.asarray(crit, dtype)[:, None] * xp.sqrt(ss / (n - 1) / n)
    covered = xp.abs(est - t) <= half
    return est, err, half, covered


def log_bucket(x) -> np.ndarray:
    x = np.asarray(x)
    pos = np.isfinite(x) & (x > 0)
    safe = np.where(pos, x, np.asarray(HIST_LO, x.dtype))
    b = np.floor((np.log(safe) - _LOG_LO) * (HIST_BINS / _LOG_SPAN))
    return np.clip(b, 0, HIST_BINS - 1).astype(np.int64)


def empty_stats(a_n: int) -> dict:
    z = np.zeros(a_n)
    return dict(count=z.copy(), cover=z.copy(), err_sum=z.copy(),
                err_sumsq=z.copy(), half_n=z.copy(), half_sum=z.copy(),
                half_sumsq=z.copy(),
                err_hist=np.zeros((a_n, HIST_BINS)),
                half_hist=np.zeros((a_n, HIST_BINS)))


def fold(stats: dict, err, half, covered, valid) -> None:
    """Add one block's per-trial outcomes (host arrays) to ``stats``."""
    err = np.asarray(err, np.float64)
    half = np.asarray(half, np.float64)
    covered = np.asarray(covered)
    v = np.broadcast_to(valid, err.shape)
    eok = v & np.isfinite(err)
    hok = v & np.isfinite(half)
    e64 = np.where(eok, err, 0.0)
    h64 = np.where(hok, half, 0.0)
    stats["count"] += v.sum(1)
    stats["cover"] += (v & covered).sum(1)
    stats["err_sum"] += e64.sum(1)
    stats["err_sumsq"] += (e64 * e64).sum(1)
    stats["half_n"] += hok.sum(1)
    stats["half_sum"] += h64.sum(1)
    stats["half_sumsq"] += (h64 * h64).sum(1)
    for name, x, ok in (("err_hist", err, eok), ("half_hist", half, hok)):
        b = log_bucket(x)
        for a in range(x.shape[0]):
            stats[name][a] += np.bincount(b[a][ok[a]], minlength=HIST_BINS)


def study(inputs: dict, seed: int, trials: int, schemes, *,
          units_per_trial: int = 20, keep: bool = False,
          xp=np, dtype=np.float64) -> dict:
    """Per-scheme statistics (and, with ``keep``, the per-trial
    estimates) of one study. ``inputs``: ``census`` (A, N) and
    ``n_regions`` (A,), ``truth`` (A,), and per stratified scheme a dict
    of ``labels``/``valid``/``pool``/``baseline`` (A, n) plus
    ``num_strata``."""
    a_n = len(inputs["truth"])
    n_blocks = -(-trials // TRIAL_BLOCK)
    out = {}
    for scheme in schemes:
        st = empty_stats(a_n)
        ests = []
        if scheme == "random":
            draws = units_per_trial
            crit = critical(np.full(a_n, float(draws - 1) if draws < 30
                                    else np.inf))
            blk = functools.partial(
                srs_block, census=inputs["census"],
                n_valid=np.asarray(inputs["n_regions"]),
                truth=inputs["truth"], crit=crit, xp=xp, dtype=dtype)
        else:
            s = inputs[scheme]
            setup = stratified_setup(s["labels"], s["valid"], s["pool"],
                                     s["baseline"], s["num_strata"])
            draws = s["num_strata"]
            blk = functools.partial(stratified_block, setup=setup,
                                    truth=inputs["truth"], xp=xp,
                                    dtype=dtype)
        for b0 in range(0, n_blocks, BLOCKS_PER_STEP):
            nb = min(BLOCKS_PER_STEP, n_blocks - b0)
            u = uniforms(seed, scheme, b0, nb, a_n, draws)
            est, err, half, cov = blk(u)
            t_idx = b0 * TRIAL_BLOCK + np.arange(nb * TRIAL_BLOCK)
            fold(st, err, half, cov, (t_idx < trials)[None, :])
            if keep:
                ests.append(np.asarray(est, np.float64))
        if keep:
            st["estimates"] = np.concatenate(ests, 1)[:, :trials]
        out[scheme] = st
    return out
