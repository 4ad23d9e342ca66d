"""Pallas TPU kernel: batch-native tiled nearest-centroid assignment.

The clustering hot spot at fleet scale (paper §VII.B: clustering ≥100 k
BBVs) is the (n, d) × (d, k) distance matmul, repeated across a leading
batch of independent problems — the flattened key × restart × app axes of
``kmeans_batch`` / ``kmeans_bank``. TPU adaptation:

* the squared distance is expanded to |x|² − 2·x·cᵀ + |c|², so the inner
  loop is a plain matmul that maps onto the 128×128 MXU;
* the grid is ``(batch, n_tiles)`` with the tile axis innermost: batch
  element ``b`` keeps its centroid block resident in VMEM while its point
  tiles stream through — no vmap-of-``pallas_call`` lifting, every batch
  element is a first-class grid coordinate with its own centroid block
  selected by the ``BlockSpec`` index maps;
* points are tiled along n with ``block_n`` rows resident in VMEM; the
  per-batch centroid block (k ≤ ~1024, d ≤ ~512 after projection and
  standardization) also lives in VMEM — k·d·4 B ≈ 2 MB worst case, well
  under the ~16 MB v5e VMEM budget together with a 512×512 x-tile (1 MB);
* the argmin over k runs on the VPU on the (block_n, k) distance tile.

Padding rules (handled by ops.py, identical for every batch element):
n → multiple of ``block_n``, k → multiple of 128 with +inf ``|c|²``
sentinel entries, d → multiple of 128 with zero columns. Padded point
rows are all-zero tiles whose outputs are sliced off by the wrapper;
padded centroids can never win the argmin; padded feature columns are
zero in both operands so distances are unchanged — the same
padding-invariance contract the unbatched kernel had.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 512


def _assign_kernel(x_ref, c_ref, c2_ref, labels_ref, mind2_ref):
    """One (batch element, point tile) grid step.

    Block shapes: x (1, block_n, d), c (1, k, d), c2 (1, 1, k) — the
    leading 1 is the batch block; outputs (1, 1, block_n).
    """
    x = x_ref[0].astype(jnp.float32)            # (block_n, d)
    c = c_ref[0].astype(jnp.float32)            # (k, d)
    c2 = c2_ref[0]                              # (1, k) — +inf on pad rows
    x2 = jnp.sum(x * x, axis=1, keepdims=True)  # (block_n, 1)
    # MXU: (block_n, d) @ (d, k), in full f32: at the default precision
    # the near-tie argmins flip and the Lloyd loop never converges
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    d2 = x2 - 2.0 * xc + c2                     # (block_n, k)
    labels_ref[0, 0, :] = jnp.argmin(d2, axis=1).astype(jnp.int32)
    mind2_ref[0, 0, :] = jnp.maximum(jnp.min(d2, axis=1), 0.0)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign_padded(x: jax.Array, c: jax.Array, c2: jax.Array,
                         *, block_n: int = BLOCK_N, interpret: bool = False
                         ) -> tuple[jax.Array, jax.Array]:
    """Batch-native assignment on pre-padded operands.

    Args:
      x: ``(B, n, d)`` points, ``n % block_n == 0``.
      c: ``(B, k, d)`` centroids (one block per batch element).
      c2: ``(B, 1, k)`` squared centroid norms, ``+inf`` on padded rows.
      block_n: point-tile rows resident in VMEM per grid step.
      interpret: run the Pallas interpreter (CPU validation) instead of
        compiling for TPU.

    Returns:
      ``(labels (B, 1, n) int32, min_d2 (B, 1, n) float32)``. The unit
      second-minor axis keeps each ``(1, 1, block_n)`` output block equal
      to the array in its last-but-one dimension, which the TPU lowering
      requires once ``B > 1``.
    """
    b, n, d = x.shape
    k = c.shape[1]
    grid = (b, n // block_n)                    # tile axis innermost:
    # the (k, d) centroid block is re-fetched only when b advances
    return pl.pallas_call(
        _assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, d), lambda b, i: (b, i, 0)),  # x tile
            pl.BlockSpec((1, k, d), lambda b, i: (b, 0, 0)),        # centroids
            pl.BlockSpec((1, 1, k), lambda b, i: (b, 0, 0)),        # |c|^2 row
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_n), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_n), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, n), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, c, c2)
