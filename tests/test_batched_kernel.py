"""Batch-native k-means assignment kernel: oracle equivalence, batch-axis
invariances, dispatch-path regression, backend fallback contract."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.clustering import kmeans, kmeans_bank, kmeans_batch
from repro.core.clustering.kmeans import (BackendFallbackWarning,
                                          _reset_backend_warnings,
                                          resolve_backend)
from repro.kernels.kmeans_assign import ops as assign_ops
from repro.kernels.kmeans_assign.ops import kmeans_assign, last_dispatch
from repro.kernels.kmeans_assign.ref import kmeans_assign_ref

RNG = np.random.default_rng(7)


def _problem(shape_x, shape_c):
    x = RNG.normal(size=shape_x).astype(np.float32)
    c = RNG.normal(size=shape_c).astype(np.float32)
    return x, c


# ---------------------------------------------------------- oracle equivalence
@pytest.mark.parametrize("b,n,k,d", [
    (3, 513, 7, 5),       # odd n remainder, odd k
    (2, 129, 130, 3),     # n just past one 128 sub-tile, k just past one pad
    (4, 100, 20, 15),     # paper-like shapes
    (1, 64, 3, 1),        # degenerate d
    (5, 511, 129, 33),    # both n and k one short of an alignment boundary
])
def test_batched_matches_oracle_odd_remainders(b, n, k, d):
    x, c = _problem((b, n, d), (b, k, d))
    l1, d1 = kmeans_assign(x, c)
    l2, d2 = kmeans_assign_ref(jnp.asarray(x), jnp.asarray(c))
    assert l1.shape == (b, n)
    assert (np.asarray(l1) == np.asarray(l2)).mean() > 0.999
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=3e-4, atol=3e-4)


def test_bank_rank4_matches_oracle():
    x, c = _problem((2, 3, 140, 6), (2, 3, 9, 6))
    l1, d1 = kmeans_assign(x, c)
    l2, d2 = kmeans_assign_ref(jnp.asarray(x), jnp.asarray(c))
    assert l1.shape == (2, 3, 140)
    assert (np.asarray(l1) == np.asarray(l2)).all()
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=3e-4, atol=3e-4)


# ------------------------------------------------------- batch-axis invariance
def test_batch_axis_permutation_invariance():
    b = 6
    x, c = _problem((b, 257, 11), (b, 13, 11))
    perm = RNG.permutation(b)
    l_base, d_base = (np.asarray(o) for o in kmeans_assign(x, c))
    l_perm, d_perm = (np.asarray(o) for o in kmeans_assign(x[perm], c[perm]))
    np.testing.assert_array_equal(l_perm, l_base[perm])
    np.testing.assert_allclose(d_perm, d_base[perm], rtol=1e-6, atol=1e-6)


def test_batched_lane_equals_unbatched_call():
    """Each lane of a batched dispatch matches its own 2-D dispatch —
    batching (and the padding it shares) cannot leak across lanes."""
    b = 4
    x, c = _problem((b, 200, 8), (b, 10, 8))
    lb, db = (np.asarray(o) for o in kmeans_assign(x, c))
    for i in range(b):
        li, di = (np.asarray(o) for o in kmeans_assign(x[i], c[i]))
        np.testing.assert_array_equal(lb[i], li)
        np.testing.assert_allclose(db[i], di, rtol=1e-6, atol=1e-6)


# --------------------------------------------------- dispatch-path regression
def test_kmeans_bank_uses_batch_native_grid():
    """Regression: the bank fit must feed its app axis to the kernel's
    batch grid axis natively. A vmap-of-pallas_call would strip the axis
    before ``ops.kmeans_assign`` ran, recording batch_shape == ()."""
    a, n, d = 3, 142, 6                      # fresh shape -> forces a trace
    x = RNG.normal(size=(a, n, d)).astype(np.float32)
    assign_ops._reset_dispatch_record()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", BackendFallbackWarning)
        bank = kmeans_bank(x, 4, seed=3, backend="pallas")
    rec = last_dispatch()
    assert rec is not None, "pallas kernel never dispatched"
    assert rec["batch"] == a
    assert rec["batch_shape"] == (a,)
    assert rec["grid"][0] == a
    assert bank.backend == resolve_backend("pallas").active


def test_kmeans_batch_uses_batch_native_grid():
    n_seeds, n, d = 4, 151, 5                # fresh shape -> forces a trace
    x = RNG.normal(size=(n, d)).astype(np.float32)
    assign_ops._reset_dispatch_record()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", BackendFallbackWarning)
        fits = kmeans_batch(x, 3, seeds=range(n_seeds), backend="pallas")
    rec = last_dispatch()
    assert rec is not None
    assert rec["batch"] == n_seeds
    assert rec["batch_shape"] == (n_seeds,)
    assert all(f.backend == resolve_backend("pallas").active for f in fits)


def test_bank_pallas_matches_jnp_backend():
    """The batched kernel path and the jnp oracle path agree lane-by-lane
    on a weighted (padded) bank fit."""
    a, n, d = 3, 120, 5
    x = RNG.normal(size=(a, n, d)).astype(np.float32)
    w = np.ones((a, n), np.float32)
    w[:, 100:] = 0.0                         # padded tail rows
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", BackendFallbackWarning)
        bp = kmeans_bank(x, 4, weights=w, seed=1, backend="pallas")
    bj = kmeans_bank(x, 4, weights=w, seed=1, backend="jnp")
    assert (bp.labels == bj.labels).mean() > 0.99
    np.testing.assert_allclose(bp.inertia, bj.inertia, rtol=1e-4)


# ------------------------------------------------------------- backend policy
def test_pallas_fallback_warns_once_with_reason():
    _reset_backend_warnings()
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        assert resolve_backend("pallas").active == "pallas"
        return
    with pytest.warns(BackendFallbackWarning, match="platform="):
        resolved = resolve_backend("pallas")
    assert resolved.requested == "pallas"
    assert resolved.active == "pallas_interpret"
    assert "interpret" in resolved.reason
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # second resolve must be silent
        again = resolve_backend("pallas")
    assert again == resolved


def test_kernel_import_failure_raises_on_tpu(monkeypatch):
    """Off-TPU a broken kernel import degrades to the oracle (warning
    once); on TPU it raises, so the device path never hides behind it."""
    from repro.kernels import backend

    def broken():
        raise ImportError("kernel package missing")

    _reset_backend_warnings()
    monkeypatch.setattr(backend.jax, "default_backend", lambda: "tpu")
    for requested in ("pallas", "auto"):
        with pytest.raises(ImportError, match="kernel package missing"):
            backend.resolve_backend(requested, kernel="probe",
                                    import_probe=broken)
    monkeypatch.setattr(backend.jax, "default_backend", lambda: "cpu")
    with pytest.warns(BackendFallbackWarning, match="import of the probe"):
        resolved = backend.resolve_backend("pallas", kernel="probe",
                                           import_probe=broken)
    assert resolved.active == "jnp"


def test_kmeans_auto_backend_is_kernel_on_tpu_oracle_elsewhere(monkeypatch):
    """k-means defaults to ``"auto"``: the compiled kernel on a TPU, the
    jnp oracle (with a one-time warning) anywhere else — so CPU fits keep
    their bits and chip fits never take the oracle's einsum."""
    from repro.kernels import backend

    _reset_backend_warnings()
    with pytest.warns(BackendFallbackWarning, match="has no TPU"):
        assert resolve_backend("auto").active == "jnp"
    x = RNG.normal(size=(80, 4)).astype(np.float32)
    assert kmeans(x, 3, seed=0).backend == "jnp"
    monkeypatch.setattr(backend.jax, "default_backend", lambda: "tpu")
    assert resolve_backend("auto").active == "pallas"


def test_jnp_backend_never_warns_and_is_recorded():
    _reset_backend_warnings()
    x = RNG.normal(size=(80, 4)).astype(np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("error", BackendFallbackWarning)
        fit = kmeans(x, 3, seed=0, backend="jnp")
    assert fit.backend == "jnp"


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("cuda")


# ------------------------------------------------------------ shape contracts
def test_rank_and_batch_mismatches_rejected():
    x = np.zeros((2, 10, 3), np.float32)
    with pytest.raises(ValueError, match="rank mismatch"):
        kmeans_assign(x, np.zeros((4, 3), np.float32))
    with pytest.raises(ValueError, match="batch mismatch"):
        kmeans_assign(x, np.zeros((3, 4, 3), np.float32))
    with pytest.raises(ValueError, match="dim mismatch"):
        kmeans_assign(x, np.zeros((2, 4, 5), np.float32))
