"""Random projection for BBV dimensionality reduction (SimPoint step 2).

SimPoint projects the (very high-dimensional, sparse) basic block vectors
down to ~15 dimensions before k-means. We use a dense Gaussian projection
scaled by 1/sqrt(d_out) (Johnson-Lindenstrauss); the paper notes RFVs are
low-dimensional enough (38) that projection is skipped for them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def projection_matrix(key: jax.Array, d_in: int, d_out: int,
                      dtype=jnp.float32) -> jax.Array:
    return jax.random.normal(key, (d_in, d_out), dtype) / jnp.sqrt(
        jnp.asarray(d_out, dtype))


def random_project(
    features: jax.Array,
    d_out: int,
    *,
    key: jax.Array,
    normalize_rows: bool = True,
) -> jax.Array:
    """Project (n, d_in) -> (n, d_out).

    ``normalize_rows`` first L1-normalizes each BBV (SimPoint treats BBVs as
    frequency distributions so region length doesn't dominate distances).
    """
    x = jnp.asarray(features)
    if x.ndim != 2:
        raise ValueError(f"expected (n, d) matrix, got {x.shape}")
    if normalize_rows:
        norm = jnp.maximum(jnp.abs(x).sum(axis=1, keepdims=True), 1e-12)
        x = x / norm
    proj = projection_matrix(key, x.shape[1], d_out, x.dtype)
    # full f32: the TPU's default matmul precision would give the chip
    # different BBV features (and strata) than every other backend
    return jnp.matmul(x, proj, precision=jax.lax.Precision.HIGHEST)
