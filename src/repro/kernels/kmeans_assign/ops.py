"""Jitted public wrapper for the batch-native k-means assignment kernel.

ONE dispatch path for every input rank: ``(n, d)`` single problems,
``(B, n, d)`` key/restart batches and ``(A, R, n, d)``-style bank shapes
all flatten their leading axes into the kernel's batch grid dimension —
no vmap-of-``pallas_call`` anywhere. Handles padding to hardware-aligned
shapes and falls back to interpret mode off-TPU (this container validates
the kernel body on CPU; TPU is the compile target).

``last_dispatch()`` exposes a trace-time marker describing the most
recent kernel dispatch (batch size, grid, block shape, interpret flag) so
tests can assert the batch-native path was taken rather than a
lifted/vmapped one.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .kmeans_assign import BLOCK_N, kmeans_assign_padded

# trace-time record of the most recent kernel dispatch (see last_dispatch)
_last_dispatch: Optional[dict] = None


def last_dispatch() -> Optional[dict]:
    """Snapshot of the most recent ``kmeans_assign`` kernel dispatch.

    Returns ``None`` if the kernel was never dispatched, else a dict with
    ``batch`` (flattened leading-axes size fed to the batch grid axis),
    ``batch_shape`` (the caller's leading axes, ``()`` for 2-D input),
    ``n``/``k``/``d`` (logical problem shape), ``grid``/``block_n``
    (kernel launch geometry) and ``interpret``. The record is written at
    trace time: jit-cached re-executions of an already-traced fit do not
    refresh it, so tests should use fresh shapes to force a trace.
    """
    return None if _last_dispatch is None else dict(_last_dispatch)


def _reset_dispatch_record() -> None:
    """Clear the dispatch marker (test helper)."""
    global _last_dispatch
    _last_dispatch = None


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kmeans_assign(x: jax.Array, centroids: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """Nearest-centroid assignment via the batch-native Pallas kernel.

    Args:
      x: points — ``(n, d)``, ``(B, n, d)`` or any higher-rank stack such
        as a ``(A, R, n, d)`` bank; every axis before the trailing two is
        treated as batch.
      centroids: ``(..., k, d)`` with leading axes matching ``x`` exactly
        (one centroid block per batch element).

    Returns:
      ``(labels, min_d2)`` with shapes ``(..., n)`` — int32 labels and
      float32 squared distance to the winning centroid.

    All batch elements share one ``(batch, n_tiles)`` kernel grid: leading
    axes are flattened into the batch grid axis, n is padded to the point
    tile, k and d to multiples of 128. Padded centroids get +inf ``|c|²``
    so they can never win the argmin; padded d columns are zero in both
    operands so distances are unchanged; padded n rows are computed then
    sliced off — assignment of every valid row is invariant to padding.
    """
    x = jnp.asarray(x, jnp.float32)
    c = jnp.asarray(centroids, jnp.float32)
    if x.ndim < 2 or c.ndim != x.ndim:
        raise ValueError(
            f"rank mismatch: x {x.shape} vs centroids {c.shape} "
            "(need matching leading axes plus trailing (n|k, d))")
    if x.shape[:-2] != c.shape[:-2]:
        raise ValueError(
            f"batch mismatch: x {x.shape} vs centroids {c.shape}")
    if c.shape[-1] != x.shape[-1]:
        raise ValueError(f"dim mismatch: x {x.shape} vs centroids {c.shape}")

    batch_shape = x.shape[:-2]
    n, d = x.shape[-2:]
    k = c.shape[-2]
    b = math.prod(batch_shape) if batch_shape else 1

    # hardware-aligned padding, shared by every batch element
    d_p = _round_up(max(d, 1), 128)
    k_p = _round_up(max(k, 1), 128)
    block_n = min(BLOCK_N, _round_up(max(n, 1), 128))
    n_p = _round_up(max(n, 1), block_n)

    xb = x.reshape(b, n, d)
    cb = c.reshape(b, k, d)
    x_p = jnp.zeros((b, n_p, d_p), jnp.float32).at[:, :n, :d].set(xb)
    c_p = jnp.zeros((b, k_p, d_p), jnp.float32).at[:, :k, :d].set(cb)
    c2 = jnp.full((b, 1, k_p), jnp.inf, jnp.float32).at[:, 0, :k].set(
        jnp.sum(cb * cb, axis=2))

    interpret = not _on_tpu()
    global _last_dispatch
    _last_dispatch = {
        "batch": b, "batch_shape": batch_shape, "n": n, "k": k, "d": d,
        "grid": (b, n_p // block_n), "block_n": block_n,
        "interpret": interpret,
    }
    labels, mind2 = kmeans_assign_padded(x_p, c_p, c2, block_n=block_n,
                                         interpret=interpret)
    labels = labels[:, 0, :n].reshape(*batch_shape, n)
    mind2 = mind2[:, 0, :n].reshape(*batch_shape, n)
    return labels, mind2


def kmeans_assign_np(x: np.ndarray, centroids: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray]:
    """``kmeans_assign`` with numpy in/out (host-side callers)."""
    labels, mind2 = kmeans_assign(x, centroids)
    return np.asarray(labels), np.asarray(mind2)
