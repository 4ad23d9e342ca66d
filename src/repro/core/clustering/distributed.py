"""Distributed k-means via shard_map (multi-pod stratification).

The paper's §VII.B scalability argument: instead of clustering BBVs for the
*entire* application, cluster a large (≈100 k) phase-1 random sample. At
fleet scale even that benefits from data-parallel clustering: points are
sharded across the ("pod", "data") mesh axes, every device computes local
assignments and local per-cluster (sum, count, sumsq) statistics, and a
single ``psum`` per Lloyd iteration reduces them — the classic
communication-optimal distributed k-means: collective bytes per iteration
are O(k·d), independent of n.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .kmeans import _assign_jnp


def _local_stats(x, centroids, k):
    labels, min_d2 = _assign_jnp(x, centroids)
    ones = jnp.ones((x.shape[0],), x.dtype)
    sums = jax.ops.segment_sum(x, labels, num_segments=k)
    counts = jax.ops.segment_sum(ones, labels, num_segments=k)
    return labels, sums, counts, min_d2.sum()


def make_distributed_kmeans_step(mesh: Mesh, data_axes: Sequence[str], k: int):
    """Build a jitted one-Lloyd-iteration function over a sharded point set.

    Inputs: x sharded (n/devices, d) along ``data_axes``; centroids
    replicated (k, d). Output: new centroids (replicated), global inertia.
    """
    axes = tuple(data_axes)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes), P()),
        out_specs=(P(), P()),
    )
    def step(x_local, centroids):
        _, sums, counts, inertia = _local_stats(x_local, centroids, k)
        sums = jax.lax.psum(sums, axes)          # (k, d) — O(k d) bytes
        counts = jax.lax.psum(counts, axes)      # (k,)
        inertia = jax.lax.psum(inertia, axes)
        safe = jnp.maximum(counts, 1.0)
        new_c = jnp.where((counts > 0)[:, None], sums / safe[:, None], centroids)
        return new_c, inertia

    return jax.jit(step)


def make_distributed_assign(mesh: Mesh, data_axes: Sequence[str]):
    """Sharded final assignment: labels stay sharded with their points."""
    axes = tuple(data_axes)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes), P()),
        out_specs=P(axes),
    )
    def assign(x_local, centroids):
        labels, _ = _assign_jnp(x_local, centroids)
        return labels

    return jax.jit(assign)


def distributed_kmeans(
    x,
    k: int,
    mesh: Mesh,
    *,
    data_axes: Sequence[str] = ("data",),
    iters: int = 25,
    seed: int = 0,
):
    """Convenience driver: shard x, init from first k points of a shuffled
    copy (cheap deterministic init; kmeans++ is host-side in kmeans.py),
    run ``iters`` Lloyd steps, return (centroids, labels, inertia)."""
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    sharding = NamedSharding(mesh, P(tuple(data_axes)))
    # kmeans++ init on a host subsample (cheap), refined distributed
    from .kmeans import kmeans as _kmeans
    sub = np.asarray(x[:min(n, 8192)])
    centroids = jnp.asarray(_kmeans(sub, k, seed=seed, max_iters=1,
                                    restarts=2).centroids)
    x = jax.device_put(x, sharding)

    step = make_distributed_kmeans_step(mesh, data_axes, k)
    inertia = jnp.inf
    for _ in range(iters):
        centroids, inertia = step(x, centroids)
    assign = make_distributed_assign(mesh, data_axes)
    labels = assign(x, centroids)
    return centroids, labels, float(inertia)
