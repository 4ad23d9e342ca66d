"""Pallas TPU kernel: per-stratum sufficient statistics, batch-native.

TPU adaptation of the centroid-update / stratified-moment scatter: a scatter
by stratum label is hostile to the TPU memory system, so it is recast as a
one-hot matmul — ``onehot(labels)ᵀ @ x`` — which runs on the MXU.

The grid is ``(batch, n_tiles)`` with the tile axis innermost (the same
layout as ``kmeans_assign``): batch element ``b`` keeps its ``(k, d)``
output blocks resident while its row tiles stream through. Outputs map
every tile step of a batch element to the same block (revisited
accumulation): zero-initialized at tile 0, accumulated thereafter. Labels
arrive as a ``(batch, n, 1)`` int32 column so the one-hot compare
vectorizes over lanes; label ``-1`` (padding / masked rows) matches no
segment and contributes nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 1024


def _segment_kernel(x_ref, lab_ref, sums_ref, sumsq_ref, counts_ref):
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        sumsq_ref[...] = jnp.zeros_like(sumsq_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    x = x_ref[0].astype(jnp.float32)                   # (BLOCK_N, d)
    labels = lab_ref[0]                                # (BLOCK_N, 1)
    k = sums_ref.shape[1]
    seg_ids = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_N, k), 1)
    onehot = (labels == seg_ids).astype(jnp.float32)   # (BLOCK_N, k)
    # MXU: (k, BLOCK_N) @ (BLOCK_N, d), in full f32 (the f32 contract)
    sums_ref[0] += jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    sumsq_ref[0] += jax.lax.dot_general(
        onehot, x * x, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    counts_ref[0] += jnp.sum(onehot, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def segment_stats_padded(x: jax.Array, labels: jax.Array, num_segments: int,
                         *, interpret: bool = False):
    """x: (b, n, d), n % BLOCK_N == 0; labels: (b, n, 1) int32 (pad = -1).

    Returns per-batch-element ``(sums (b, k, d), sumsq (b, k, d),
    counts (b, 1, k))`` over the ``(batch, n_tiles)`` kernel grid. The
    unit second-minor axis of ``counts`` keeps its ``(1, 1, k)`` block
    equal to the array in its last two dimensions, which the TPU lowering
    requires once ``b > 1``.
    """
    b, n, d = x.shape
    grid = (b, n // BLOCK_N)
    return pl.pallas_call(
        _segment_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BLOCK_N, d), lambda bi, i: (bi, i, 0)),
            pl.BlockSpec((1, BLOCK_N, 1), lambda bi, i: (bi, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, num_segments, d), lambda bi, i: (bi, 0, 0)),
            pl.BlockSpec((1, num_segments, d), lambda bi, i: (bi, 0, 0)),
            pl.BlockSpec((1, 1, num_segments), lambda bi, i: (bi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, num_segments, d), jnp.float32),
            jax.ShapeDtypeStruct((b, num_segments, d), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, num_segments), jnp.float32),
        ],
        interpret=interpret,
    )(x, labels)
