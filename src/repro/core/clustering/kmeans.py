"""k-means clustering in JAX (SimPoint's stratification step).

Design notes
------------
* kmeans++ initialization, Lloyd iterations inside ``lax.while_loop`` —
  the whole fit is one jitted computation.
* Pluggable assignment backend: ``"jnp"`` (pure jnp, the oracle),
  ``"pallas"`` (the batch-native tiled TPU kernel in
  ``repro.kernels.kmeans_assign``) or ``"auto"``, the default: the kernel
  on TPU, the oracle elsewhere. (On a TPU the oracle's XLA einsum runs
  below f32 precision and its Lloyd loop does not converge on the BBV
  bank, so fits on the chip take the kernel.) Requesting ``"pallas"``
  off-TPU falls back with a one-time ``BackendFallbackWarning`` naming
  the reason (platform → interpret mode, import failure → jnp oracle);
  the backend that actually ran is recorded on every fit result.
* Empty clusters are re-seeded to the point farthest from its centroid —
  standard practice; keeps L strata non-empty, which the stratified
  estimators require.
* The paper repeats clustering with 10 seeds for the stochastic schemes
  (Fig 7); ``kmeans_multi_seed`` supports that and best-of-N selection.
* ALL fits route through ONE natively-stacked Lloyd loop
  (``_kmeans_fit_stacked``): the key/restart axis of ``kmeans_batch`` and
  the app axis of ``kmeans_bank`` are a real leading array axis of every
  step — assignment is one batched kernel dispatch over a ``(batch,
  tile)`` grid, never a vmap of ``pallas_call``. Only the pure-jnp
  seeding/update steps are vmapped (array ops, free to batch). Converged
  lanes are frozen with per-lane masks, reproducing exactly what
  ``vmap(while_loop)`` used to do, so per-lane results match an unbatched
  fit with the same key.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


# Backend policy is shared across kernels (repro.kernels.backend); the
# historic import sites (`from repro.core.clustering.kmeans import
# BackendFallbackWarning, resolve_backend, _reset_backend_warnings`)
# keep working through these aliases.
from repro.kernels.backend import (BackendFallbackWarning,  # noqa: F401
                                   ResolvedBackend)
from repro.kernels.backend import \
    reset_backend_warnings as _reset_backend_warnings  # noqa: F401
from repro.kernels.backend import resolve_backend as _resolve_shared


def _probe_kmeans_kernel() -> None:
    from repro.kernels.kmeans_assign import ops as _ops  # noqa: F401


def resolve_backend(requested: str) -> ResolvedBackend:
    """Map a requested assignment backend to the one that can run here.

    ``"jnp"`` always resolves to itself. ``"pallas"`` resolves to
    ``"pallas"`` on TPU, to ``"pallas_interpret"`` (same kernel, Pallas
    interpreter — correctness validation, not speed) on other platforms,
    and to ``"jnp"`` when the kernel package cannot be imported off-TPU.
    ``"auto"`` resolves to ``"pallas"`` on TPU and to ``"jnp"`` elsewhere.
    Any fallback emits a one-time ``BackendFallbackWarning`` naming the
    reason (shared policy: ``repro.kernels.backend``).
    """
    if requested not in ("jnp", "pallas", "auto"):
        raise ValueError(f"unknown backend {requested!r}; "
                         "expected 'jnp', 'pallas' or 'auto'")
    return _resolve_shared(requested, kernel="k-means assignment",
                           import_probe=_probe_kmeans_kernel)


@dataclasses.dataclass(frozen=True)
class KMeansResult:
    """One fitted stratification.

    ``backend`` records the assignment backend that actually ran
    (``resolve_backend``'s ``active`` value), so tests can assert which
    path produced the fit.
    """

    centroids: np.ndarray   # (k, d)
    labels: np.ndarray      # (n,)
    inertia: float          # sum of squared distances to assigned centroid
    iterations: int
    backend: str = "jnp"    # active assignment backend ("jnp" | "pallas*")


def _assign_jnp_stacked(x: jax.Array, centroids: jax.Array
                        ) -> tuple[jax.Array, jax.Array]:
    """Batched oracle assignment: (B, n, d) x (B, k, d) -> (B, n) pairs."""
    x2 = jnp.sum(x * x, axis=2, keepdims=True)           # (B, n, 1)
    c2 = jnp.sum(centroids * centroids, axis=2)          # (B, k)
    # dist2 = |x|^2 - 2 x.c^T + |c|^2 : the x.c^T matmul is the MXU hot spot.
    xc = jnp.einsum("bnd,bkd->bnk", x, centroids)
    d2 = x2 - 2.0 * xc + c2[:, None, :]
    labels = jnp.argmin(d2, axis=2)
    return labels, jnp.maximum(jnp.min(d2, axis=2), 0.0)


def _assign_pallas_stacked(x: jax.Array, centroids: jax.Array
                           ) -> tuple[jax.Array, jax.Array]:
    """Batched kernel assignment: ONE (batch, tile)-grid Pallas dispatch."""
    from repro.kernels.kmeans_assign import ops as _ops
    return _ops.kmeans_assign(x, centroids)


# active-backend name -> stacked assignment fn ((B,n,d),(B,k,d)) -> (B,n) x2
_ASSIGN = {
    "jnp": _assign_jnp_stacked,
    "pallas": _assign_pallas_stacked,
    "pallas_interpret": _assign_pallas_stacked,
}


def _update_centroids(x: jax.Array, labels: jax.Array, k: int,
                      old: jax.Array, w=None) -> jax.Array:
    """(Weighted) mean of assigned points; empty clusters keep their old
    centroid. ``w=None`` is the exact historic unweighted path."""
    xw = x if w is None else x * w[:, None]
    ones = jnp.ones((x.shape[0],), x.dtype) if w is None else w
    sums = jax.ops.segment_sum(xw, labels, num_segments=k)
    counts = jax.ops.segment_sum(ones, labels, num_segments=k)
    safe = jnp.maximum(counts, 1.0)
    means = sums / safe[:, None]
    return jnp.where((counts > 0)[:, None], means, old)


def _kmeanspp_init(key: jax.Array, x: jax.Array, k: int, w=None) -> jax.Array:
    """kmeans++ seeding (jit-friendly, O(k) passes).

    With point weights, selection probabilities are scaled by ``w`` so
    zero-weight (padded) rows are never chosen as seeds.
    """
    n = x.shape[0]

    def body(carry, i):
        key, centroids, min_d2 = carry
        key, sub = jax.random.split(key)
        scaled = min_d2 if w is None else min_d2 * w
        probs = scaled / jnp.maximum(scaled.sum(), 1e-30)
        idx = jax.random.choice(sub, n, p=probs)
        c_new = x[idx]
        centroids = centroids.at[i].set(c_new)
        d2_new = jnp.sum((x - c_new[None, :]) ** 2, axis=1)
        return (key, centroids, jnp.minimum(min_d2, d2_new)), None

    key, sub = jax.random.split(key)
    if w is None:
        first = x[jax.random.randint(sub, (), 0, n)]
    else:
        first = x[jax.random.choice(sub, n,
                                    p=w / jnp.maximum(w.sum(), 1e-30))]
    centroids = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(first)
    min_d2 = jnp.sum((x - first[None, :]) ** 2, axis=1)
    (key, centroids, _), _ = jax.lax.scan(
        body, (key, centroids, min_d2), jnp.arange(1, k))
    return centroids


@functools.partial(jax.jit, static_argnames=("k", "max_iters", "backend",
                                             "tol"))
def _kmeans_fit_stacked(keys: jax.Array, x: jax.Array, k: int,
                        max_iters: int, backend: str, tol: float, w=None):
    """THE Lloyd loop: every lane of a (B, n, d) stack fit in one program.

    ``keys``: (B, ...) PRNG keys (one per lane); ``x``: (B, n, d) points —
    or (n, d) shared by all lanes, broadcast INSIDE the jitted program so
    callers never materialize B host-side copies; ``w``: optional (B, n)
    point weights. ``backend`` must be an ACTIVE
    backend name (see ``resolve_backend``). Assignment for all B lanes is
    one batched dispatch per Lloyd step — on the pallas backends that is a
    single ``(batch, tile)``-grid kernel launch, NOT a vmap of per-lane
    ``pallas_call``s. Per-lane ``active`` masks freeze converged lanes
    (state held, iteration counter stopped), replicating
    ``vmap(while_loop)`` semantics exactly: lane ``b``'s result is
    identical to an unbatched fit with ``keys[b]``.

    Returns ``(centroids (B, k, d), labels (B, n), inertia (B,),
    iterations (B,))``.
    """
    assign = _ASSIGN[backend]
    b = keys.shape[0]
    if x.ndim == 2:
        x = jnp.broadcast_to(x, (b,) + x.shape)

    if w is None:
        init = jax.vmap(
            lambda kk, xx: _kmeanspp_init(kk, xx, k))(keys, x)
    else:
        init = jax.vmap(
            lambda kk, xx, ww: _kmeanspp_init(kk, xx, k, ww))(keys, x, w)

    update = jax.vmap(
        lambda xx, ll, old, ww: _update_centroids(xx, ll, k, old, ww),
        in_axes=(0, 0, 0, None if w is None else 0))

    def cond(state):
        _, _, it, shift = state
        return jnp.any(jnp.logical_and(it < max_iters, shift > tol))

    def body(state):
        centroids, labels, it, shift = state
        active = jnp.logical_and(it < max_iters, shift > tol)   # (B,)
        new_labels, _ = assign(x, centroids)
        new_c = update(x, new_labels, centroids, w)
        new_shift = jnp.max(jnp.sum((new_c - centroids) ** 2, axis=2),
                            axis=1)
        centroids = jnp.where(active[:, None, None], new_c, centroids)
        labels = jnp.where(active[:, None], new_labels, labels)
        shift = jnp.where(active, new_shift, shift)
        it = it + active.astype(it.dtype)
        return centroids, labels, it, shift

    labels0, _ = assign(x, init)
    state = (init, labels0, jnp.zeros((b,), jnp.int32),
             jnp.full((b,), jnp.inf, x.dtype))
    centroids, labels, iters, _ = jax.lax.while_loop(cond, body, state)
    labels, min_d2 = assign(x, centroids)
    inertia = min_d2.sum(axis=1) if w is None else (min_d2 * w).sum(axis=1)
    return centroids, labels, inertia, iters


@functools.partial(jax.jit, static_argnames=("k", "max_iters", "backend",
                                             "tol"))
def _kmeans_fit(key: jax.Array, x: jax.Array, k: int, max_iters: int,
                backend: str, tol: float, w=None):
    """Single (n, d) fit: lane 0 of the stacked loop with B=1."""
    out = _kmeans_fit_stacked(key[None], x[None], k, max_iters, backend,
                              tol, None if w is None else w[None])
    return jax.tree.map(lambda o: o[0], out)


def _as_key_batch(keys, seeds) -> jax.Array:
    if (keys is None) == (seeds is None):
        raise ValueError("pass exactly one of keys= or seeds=")
    if keys is None:
        keys = [jax.random.PRNGKey(int(s)) for s in seeds]
    if not isinstance(keys, jax.Array):
        keys = jnp.stack(list(keys))
    if keys.ndim == 1:
        keys = keys[None, :]
    return keys


def kmeans_batch(
    features,
    k: int,
    *,
    keys=None,
    seeds=None,
    max_iters: int = 100,
    backend: str = "auto",
    tol: float = 1e-8,
) -> list[KMeansResult]:
    """Batched k-means: one fit per key/seed as a single stacked program.

    Equivalent to ``[kmeans(features, k, key=key) for key in keys]`` but
    compiled and dispatched once (the paper's 10-seed repetitions for
    Figs 7-8 and best-of-N restarts): the key axis is a native leading
    batch axis of the Lloyd loop, so assignment runs the batch-grid
    kernel (backend ``"pallas"``) or one batched einsum (``"jnp"``).
    Returns one ``KMeansResult`` per key, in key order, each carrying the
    ``backend`` that actually ran.
    """
    x = jnp.asarray(features, dtype=jnp.float32)
    if x.ndim != 2:
        raise ValueError(f"expected (n, d), got {x.shape}")
    if k < 1 or k > x.shape[0]:
        raise ValueError(f"k={k} invalid for n={x.shape[0]}")
    kb = _as_key_batch(keys, seeds)
    resolved = resolve_backend(backend)
    centroids, labels, inertia, iters = _kmeans_fit_stacked(
        kb, x, k, max_iters, resolved.active, tol)
    centroids, labels = np.asarray(centroids), np.asarray(labels)
    return [
        KMeansResult(centroids=centroids[i], labels=labels[i],
                     inertia=float(inertia[i]), iterations=int(iters[i]),
                     backend=resolved.active)
        for i in range(kb.shape[0])
    ]


def kmeans(
    features,
    k: int,
    *,
    key: Optional[jax.Array] = None,
    seed: int = 0,
    max_iters: int = 100,
    backend: str = "auto",
    tol: float = 1e-8,
    restarts: int = 1,
) -> KMeansResult:
    """Fit k-means; returns numpy-backed result (host-side strata labels).

    ``restarts`` > 1 runs several kmeans++ initializations and keeps the
    lowest-inertia fit (Lloyd can land in local minima even on perfectly
    separated data). ``result.backend`` records the active assignment
    backend after ``resolve_backend`` (a requested ``"pallas"`` may fall
    back off-TPU, with a one-time ``BackendFallbackWarning``).
    """
    x = jnp.asarray(features, dtype=jnp.float32)
    if x.ndim != 2:
        raise ValueError(f"expected (n, d), got {x.shape}")
    n = x.shape[0]
    if k < 1 or k > n:
        raise ValueError(f"k={k} invalid for n={n}")
    if key is None:
        key = jax.random.PRNGKey(seed)
    if restarts <= 1:
        # restarts=1 consumes the caller's key directly (stable results for
        # seeded single-fit callers); multi-restart splits per attempt.
        resolved = resolve_backend(backend)
        centroids, labels, inertia, iters = _kmeans_fit(
            key, x, k, max_iters, resolved.active, tol)
        return KMeansResult(
            centroids=np.asarray(centroids),
            labels=np.asarray(labels),
            inertia=float(inertia),
            iterations=int(iters),
            backend=resolved.active,
        )
    subs = []
    for _ in range(restarts):
        key, sub = jax.random.split(key)
        subs.append(sub)
    return best_of(kmeans_batch(x, k, keys=jnp.stack(subs),
                                max_iters=max_iters, backend=backend,
                                tol=tol))


def kmeans_multi_seed(
    features,
    k: int,
    *,
    seeds,
    max_iters: int = 100,
    backend: str = "auto",
) -> list[KMeansResult]:
    """One fit per seed (the paper's 10-seed repetitions for Figs 7-8),
    batched into a single stacked computation."""
    return kmeans_batch(features, k, seeds=list(seeds), max_iters=max_iters,
                        backend=backend)


def best_of(results: list[KMeansResult]) -> KMeansResult:
    """The lowest-inertia fit of a batch."""
    return min(results, key=lambda r: r.inertia)


@dataclasses.dataclass(frozen=True)
class KMeansBank:
    """Stacked per-app fits: one lane per dataset of an (A, n, d) stack.

    ``backend`` is the active assignment backend the whole bank ran on.
    """

    centroids: np.ndarray   # (A, k, d)
    labels: np.ndarray      # (A, n)
    inertia: np.ndarray     # (A,)
    iterations: np.ndarray  # (A,)
    backend: str = "jnp"    # active assignment backend ("jnp" | "pallas*")

    def __len__(self) -> int:
        return int(self.labels.shape[0])

    def lane(self, a: int, n_valid: Optional[int] = None) -> KMeansResult:
        """Lane ``a`` as a single ``KMeansResult`` (labels cut to
        ``n_valid`` when the lane was padded)."""
        end = self.labels.shape[1] if n_valid is None else int(n_valid)
        return KMeansResult(centroids=self.centroids[a],
                            labels=self.labels[a, :end],
                            inertia=float(self.inertia[a]),
                            iterations=int(self.iterations[a]),
                            backend=self.backend)


def kmeans_bank(
    features,
    k: int,
    *,
    weights=None,
    key: Optional[jax.Array] = None,
    seed: int = 0,
    max_iters: int = 100,
    backend: str = "auto",
    tol: float = 1e-8,
    mesh=None,
) -> KMeansBank:
    """One k-means fit per DATASET lane of an ``(A, n, d)`` stack.

    This is the app-axis companion of ``kmeans_batch`` (which stacks over
    seeds for one dataset): every lane fits its own point set with its own
    point ``weights`` (weight 0 = padded row, never seeds a centroid and
    never moves one — how ragged per-app populations share one stack).
    All lanes share the same PRNG ``key``/``seed`` so lane ``a`` matches a
    single-dataset weighted fit with that key. The app axis is a native
    batch axis of the Lloyd loop — with ``backend="pallas"`` every
    assignment step is ONE ``(batch, tile)``-grid kernel launch for all
    lanes. With ``mesh`` (a 1-D ``("app",)`` mesh) lanes run
    device-parallel; per-lane results are identical to the single-device
    run because lanes never interact.
    """
    x = jnp.asarray(features, jnp.float32)
    if x.ndim != 3:
        raise ValueError(f"expected (A, n, d), got {x.shape}")
    if k < 1 or k > x.shape[1]:
        raise ValueError(f"k={k} invalid for n={x.shape[1]}")
    w = jnp.ones(x.shape[:2], x.dtype) if weights is None else \
        jnp.asarray(weights, jnp.float32)
    if key is None:
        key = jax.random.PRNGKey(seed)

    resolved = resolve_backend(backend)
    fit = _bank_fit_fn(k, max_iters, resolved.active, tol)
    if mesh is None:
        out = fit(key, x, w)
    else:
        from ...distributed.appaxis import app_sharded_cached
        out = app_sharded_cached(fit, mesh, (0,))(key, x, w)
    centroids, labels, inertia, iters = (np.asarray(o) for o in out)
    return KMeansBank(centroids=centroids, labels=labels, inertia=inertia,
                      iterations=iters, backend=resolved.active)


@functools.lru_cache(maxsize=None)
def _bank_fit_fn(k: int, max_iters: int, backend: str, tol: float):
    """Stable (cacheable) stacked bank fit: one compile per parameter set,
    shared by the single-device and shard_map paths. The shared key is
    broadcast to one key per lane; the lane axis is the stacked loop's
    native batch axis (``backend`` must already be resolved/active)."""
    def fit(key, xa, wa):
        keys = jnp.broadcast_to(key, (xa.shape[0],) + key.shape)
        return _kmeans_fit_stacked(keys, xa, k, max_iters, backend, tol, wa)
    return fit
