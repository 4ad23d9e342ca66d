"""The k-means fixed-point reading of ``bench/reference/strata``."""

from __future__ import annotations

import numpy as np
import pytest

from bench.reference import strata


def fixed_point(seed=0, n=400, d=5, L=4):
    """Points in ``L`` separated clusters, their labels and the exact
    member means as centroids."""
    rng = np.random.default_rng(seed)
    labels = np.arange(n) % L
    z = rng.normal(size=(n, d)) + 6.0 * np.eye(L, d)[labels]
    means, _ = strata.member_means(z, labels, L)
    return z, labels, means


def test_fixed_point_reads_zero():
    z, labels, c = fixed_point()
    assert strata.centroid_gap(z, labels, c) < 1e-12


@pytest.mark.parametrize("offset", [-1.0, 0.5, 1e3])
def test_constant_column_at_any_value_is_a_fixed_point(offset):
    """A column the units share adds one amount to every distance: the
    fit's value there (a z-score of a pinned counter, say) moves nothing."""
    z, labels, c = fixed_point()
    z = np.concatenate([z, np.full((len(z), 1), 4e-17)], axis=1)
    c = np.concatenate([c, np.full((len(c), 1), offset)], axis=1)
    assert strata.centroid_gap(z, labels, c) < 1e-12
    assert strata.off_centroid_count(z, labels, c)[0] == 0


def test_centroid_moved_in_a_live_column_is_read():
    z, labels, c = fixed_point()
    c = c.copy()
    c[2, 0] += 0.1
    radius = np.sqrt(np.mean(((z - c[labels]) ** 2).sum(1)))
    assert strata.centroid_gap(z, labels, c) > 0.05 / radius
