"""Checkpoint / elastic / health runtime tests."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.sampling import tables as sampling_tables
from repro.runtime.checkpoint import (latest_step, restore_checkpoint,
                                      restore_memobank, save_checkpoint,
                                      save_memobank)
from repro.runtime.elastic import (build_mesh, plan_app_mesh,
                                   plan_app_trial_mesh, plan_mesh, reshard)
from repro.runtime.health import (QuantumHealth, StepTimer,
                                  StragglerDetector,
                                  one_per_stratum_steptime_ci,
                                  stratified_steptime_estimate)


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
        "nested": {"b": jnp.asarray(rng.integers(0, 9, (3,)), jnp.int32)},
    }


def test_checkpoint_roundtrip(tmp_path):
    tree = _tree()
    save_checkpoint(tmp_path, 7, tree, extra={"step": 7})
    restored, extra = restore_checkpoint(tmp_path, tree)
    assert extra["step"] == 7
    np.testing.assert_array_equal(np.asarray(tree["a"]),
                                  np.asarray(restored["a"]))
    np.testing.assert_array_equal(np.asarray(tree["nested"]["b"]),
                                  np.asarray(restored["nested"]["b"]))


def test_checkpoint_retention_and_latest(tmp_path):
    tree = _tree()
    for step in (1, 2, 3, 4, 5):
        save_checkpoint(tmp_path, step, tree, keep=3)
    assert latest_step(tmp_path) == 5
    kept = sorted(int(p.name.split("_")[1])
                  for p in tmp_path.glob("step_*"))
    assert kept == [3, 4, 5]


def test_checkpoint_shape_mismatch_detected(tmp_path):
    save_checkpoint(tmp_path, 0, _tree())
    bad = {"a": jnp.zeros((2, 2)), "nested": {"b": jnp.zeros(3, jnp.int32)}}
    with pytest.raises(ValueError):
        restore_checkpoint(tmp_path, bad)


def test_checkpoint_sharding_aware_restore(tmp_path):
    """``shardings=`` places restored leaves on devices with the given
    sharding (the elastic supervisor restores onto the NEW mesh)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    tree = _tree()
    save_checkpoint(tmp_path, 0, tree)
    mesh = build_mesh(plan_app_mesh(len(jax.devices())))
    sh = {"a": NamedSharding(mesh, P()),
          "nested": {"b": NamedSharding(mesh, P())}}
    restored, _ = restore_checkpoint(tmp_path, tree, shardings=sh)
    assert restored["a"].sharding == sh["a"]
    assert restored["a"].dtype == tree["a"].dtype
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))


# ---------------------------------------------------------------- MemoBank
def _toy_bank(register):
    """A two-app bank with ledgers, filled through the memoized path.

    ``register`` pre-registers config columns in the given order, so a
    restore target can hold a PERMUTED (or empty) column layout relative
    to the snapshot source.
    """
    from repro.simcpu.cache import MemoBank
    from repro.simcpu.simulator import Ledger
    from repro.simcpu.uarch import UarchConfig

    c0, c1 = UarchConfig(name="cfg-a"), UarchConfig(name="cfg-b")
    bank = MemoBank()
    bank.add_app("alpha", 6, Ledger())
    bank.add_app("beta", 5, Ledger())
    bank.cols_for([(c0, c1), (c1, c0), ()][register])
    return bank, (c0, c1)


def _fill_toy(bank, cfgs, *, seed=0):
    rng = np.random.default_rng(seed)
    idx = np.asarray([[0, 2, 4], [1, 3, 3]])
    vals = rng.uniform(0.5, 3.0, size=(2, 2, 3)).astype(np.float32)
    return bank.fill([0, 1], idx, None, cfgs, values=vals)


def test_memobank_checkpoint_roundtrip_permuted_columns(tmp_path):
    """A bank snapshot restores into a fresh bank whose config columns
    were registered in a different order: dtypes/shapes/version survive,
    accounting is replaced exactly, and the restored memo serves the
    original fills as pure hits with identical CPI."""
    src, cfgs = _toy_bank(0)
    cpi_src, _ = _fill_toy(src, cfgs)
    save_memobank(tmp_path, 0, src, extra={"tag": "t"})

    for register in (1, 2):                    # permuted / unregistered
        dst, _ = _toy_bank(register)
        extra = restore_memobank(tmp_path, dst, universe=cfgs)
        assert extra["tag"] == "t"
        assert dst.mask.dtype == np.bool_ and dst.cpi.dtype == np.float32
        assert dst.version == src.version
        assert dst.hit_count == src.hit_count
        assert dst.miss_count == src.miss_count
        assert [l.regions_simulated for l in dst.ledgers] == \
               [l.regions_simulated for l in src.ledgers]
        cpi_dst, n_miss = _fill_toy(dst, cfgs)
        assert not n_miss.any()                # fully memoized after restore
        np.testing.assert_array_equal(cpi_dst, cpi_src)
        assert np.asarray(dst.charges).sum() == np.asarray(src.charges).sum()


def test_memobank_restore_refuses_identity_drift(tmp_path):
    from repro.simcpu.cache import MemoBank
    from repro.simcpu.simulator import Ledger

    src, cfgs = _toy_bank(0)
    _fill_toy(src, cfgs)
    save_memobank(tmp_path, 0, src)
    other = MemoBank()
    other.add_app("gamma", 6, Ledger())
    other.add_app("beta", 5, Ledger())
    with pytest.raises(ValueError, match="apps"):
        restore_memobank(tmp_path, other, universe=cfgs)
    fresh, _ = _toy_bank(2)
    with pytest.raises(ValueError, match="not resolvable"):
        restore_memobank(tmp_path, fresh, universe=())


def test_memobank_version_never_rolls_back(tmp_path):
    """Restoring an older snapshot onto a bank that already advanced past
    it must move ``version`` forward (stale device-resident mirrors keyed
    on the saved version would otherwise revalidate)."""
    src, cfgs = _toy_bank(0)
    _fill_toy(src, cfgs)
    save_memobank(tmp_path, 0, src)
    dst, _ = _toy_bank(0)
    for _ in range(src.version + 3):
        dst.touch()
    before = dst.version
    restore_memobank(tmp_path, dst, universe=cfgs)
    assert dst.version > before >= src.version


def test_trial_stats_checkpoint_roundtrip(tmp_path):
    """TrialStats (a registered pytree) checkpoints leaf-for-leaf: dtypes,
    shapes and exact bit patterns survive the round-trip."""
    rng = np.random.default_rng(3)
    st = sampling_tables.trial_stats_update(
        sampling_tables.trial_stats_init((2,)),
        rng.uniform(0.1, 20.0, (2, 32)), rng.uniform(0.01, 1.0, (2, 32)),
        rng.random((2, 32)) < 0.9, np.ones((2, 32), bool))
    save_checkpoint(tmp_path, 0, {"stats": st})
    restored, _ = restore_checkpoint(
        tmp_path, {"stats": sampling_tables.trial_stats_init((2,))})
    got = jax.tree_util.tree_leaves(restored["stats"])
    want = jax.tree_util.tree_leaves(st)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins untouched; otherwise the cache
    goes to the same fixed directory of the checkout on every call.
    Importing the library never turns the cache on."""
    import pathlib

    from repro.runtime import compile_cache

    before = jax.config.jax_compilation_cache_dir
    assert before == (os.environ.get(compile_cache.CACHE_ENV) or None)
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(compile_cache.CACHE_ENV)
    try:
        path = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert pathlib.Path(path) == root / ".jax_cache"


def test_elastic_mesh_plans():
    p = plan_mesh(256, model_parallel=16)
    assert p.shape == (16, 16)
    p = plan_mesh(240, model_parallel=16)    # lost a node's chips
    assert p.shape == (15, 16)
    p = plan_mesh(8, model_parallel=16)      # degrade TP
    assert p.shape[0] * p.shape[1] <= 8
    with pytest.raises(ValueError):
        plan_mesh(0)


def test_elastic_app_mesh_plans():
    assert plan_app_mesh(5).shape == (5,)
    assert plan_app_mesh(5).axes == ("app",)
    p = plan_app_trial_mesh(8, app_devices=2)
    assert p.shape == (2, 4) and p.axes == ("app", "trial")
    # app degree clamps to the pool; leftover devices idle off-rectangle
    assert plan_app_trial_mesh(3, app_devices=8).shape == (3, 1)
    with pytest.raises(ValueError):
        plan_app_trial_mesh(0)


def test_quantum_health_trace():
    h = QuantumHealth()
    h.detector.min_samples = 4
    for q in range(8):
        assert not h.record(q, 0.1)
    assert h.record(8, 5.0)                    # obvious straggler
    assert h.summary()["quanta"] == 9
    assert h.summary()["stragglers"] == 1
    assert h.stragglers[0][0] == 8


def test_elastic_reshard_on_host():
    plan = plan_mesh(len(jax.devices()), model_parallel=1)
    mesh = build_mesh(plan)
    from jax.sharding import NamedSharding, PartitionSpec as P
    tree = _tree()
    sh = {"a": NamedSharding(mesh, P()), "nested": {
        "b": NamedSharding(mesh, P())}}
    out = reshard(tree, sh)
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(tree["a"]))


def test_straggler_detector():
    det = StragglerDetector(k=3.0, min_samples=10)
    times = np.full(100, 0.1) + np.random.default_rng(0).normal(0, 0.002, 100)
    assert not det.is_straggler(times, 0.105)
    assert det.is_straggler(times, 0.5)


def test_step_timer_window():
    t = StepTimer(window=5)
    for i in range(10):
        t.record(float(i))
    assert t.times.size == 5
    assert t.times[-1] == 9.0


def test_stratified_steptime_cis():
    rng = np.random.default_rng(1)
    # two regimes: fast data shapes and slow ones
    labels = rng.integers(0, 2, 200)
    times = np.where(labels == 0, 0.1, 0.3) + rng.normal(0, 0.01, 200)
    est = stratified_steptime_estimate(times, labels, num_strata=2)
    assert abs(est.mean - times.mean()) < 0.02
    est1 = one_per_stratum_steptime_ci([0.1, 0.12, 0.3, 0.29],
                                       [0.25, 0.25, 0.25, 0.25])
    assert np.isfinite(est1.margin)
