"""Operations and bytes of the kernels the benchmark reads, from shapes.

``kmeans_assign`` assigns each of ``n[b]`` points in ``d`` dimensions to
the nearest of ``k`` centroids, for each problem ``b`` of a batch. Counted
as the algorithm needs it, whatever the kernel does inside:

* operations of the direct distance form: per (point, centroid) pair,
  ``d`` subtractions, ``d`` multiplications and ``d`` additions;
* bytes: the points and centroids read once in float32, and the int32
  label and float32 squared distance written per point. The padding that
  stacks the problems to one length, and the kernel's tile padding, are
  not counted.
"""

from __future__ import annotations


def kmeans_assign_counts(n, k: int, d: int) -> tuple[float, float]:
    """(operations, bytes) of one kmeans_assign call over problems of
    ``n`` (a list, one count per problem) points each."""
    points = float(sum(n))
    ops = 3.0 * points * k * d
    bytes_ = 4.0 * (points * d + len(n) * k * d + 2 * points)
    return ops, bytes_


def least_time(ops: float, bytes_: float, peak_flops: float,
               peak_bytes_per_s: float) -> tuple[float, str]:
    """(seconds, bound): the larger of compute and memory time."""
    t_ops, t_bytes = ops / peak_flops, bytes_ / peak_bytes_per_s
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
