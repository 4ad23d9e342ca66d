"""Streaming Monte-Carlo trial engine tests (chunked scan + precision).

Covers the streaming-reduction contracts:

* chunked == unchunked bitwise at matching seeds (the per-block PRNG
  fold-in contract),
* streamed ``TrialStats`` == dense per-trial reductions (coverage exact,
  sketch quantiles within grid resolution),
* sharded ``("app", "trial")`` totals == single-device totals (needs
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, as in
  ``scripts/ci.sh``),
* the 10^5-trial coverage-calibration gate: empirical coverage of the
  calibrated/conservative schemes stays >= 90% at nominal 95% while the
  f32 accumulator policy streams every chunk,
* ``PrecisionPolicy`` plumbing and the jitted Table IV sizing program.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core.precision import PrecisionPolicy, resolve_precision
from repro.core.sampling import tables as sampling_tables
from repro.core.sampling.two_phase import phase2_sizes_for_margin
from repro.experiments import ExperimentEngine, TrialSpec, run_trials
from repro.experiments.montecarlo import TRIAL_BLOCK, trial_uniforms

APP = "505.mcf_r"
APPS2 = ("505.mcf_r", "520.omnetpp_r")

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def engine():
    return ExperimentEngine()


# ------------------------------------------------ chunked == unchunked
def test_chunked_equals_unchunked_bitwise(engine):
    """Any chunking of the scan consumes identical per-block draws, so
    per-trial outputs are bitwise equal and integer stats exact."""
    spec = TrialSpec(trials=1000, schemes=("random", "dg"),
                     keep_trials=True)
    res1 = run_trials(engine, spec, apps=(APP,))                # 1 chunk
    res2 = run_trials(engine, dataclasses.replace(
        spec, chunk_size=TRIAL_BLOCK), apps=(APP,))             # 4 chunks
    for s in spec.schemes:
        np.testing.assert_array_equal(res1.estimates[s], res2.estimates[s])
        np.testing.assert_array_equal(res1.errors[s], res2.errors[s])
        np.testing.assert_array_equal(res1.half_widths[s],
                                      res2.half_widths[s])
        st1, st2 = res1.stats[s], res2.stats[s]
        np.testing.assert_array_equal(st1.count, st2.count)
        np.testing.assert_array_equal(st1.cover, st2.cover)
        np.testing.assert_array_equal(st1.err_hist, st2.err_hist)
        np.testing.assert_array_equal(st1.half_hist, st2.half_hist)
        # the float moments sum per PRNG block, then over the blocks in
        # an order fixed by the trial count, not by the chunking
        for leaf in ("err_sum", "err_sumsq", "half_sum", "half_sumsq"):
            np.testing.assert_array_equal(_bits(getattr(st1, leaf)),
                                          _bits(getattr(st2, leaf)))


@pytest.mark.parametrize("draws", [2, 20, 1000])
def test_sum_draws_is_left_to_right_at_any_length(draws):
    """The trial chunks' draw sum adds left to right (numpy's sequential
    ``add.accumulate``, bit for bit) with a program whose size does not
    grow with the draw count."""
    from repro.experiments.montecarlo import _sum_draws

    x = np.random.default_rng(draws).lognormal(size=(3, 5, draws)).astype(
        np.float32)
    np.testing.assert_array_equal(np.asarray(_sum_draws(x)),
                                  np.add.accumulate(x, axis=-1)[..., -1])
    eqns = len(jax.make_jaxpr(_sum_draws)(x).jaxpr.eqns)
    assert eqns == len(jax.make_jaxpr(_sum_draws)(x[..., :2]).jaxpr.eqns)


def _key_order_gather(vals, key_order):
    """The collapsed-pairs key order as a gather: the reference that the
    key-order select must equal bit for bit."""
    import jax.numpy as jnp

    return jnp.take_along_axis(
        vals, jnp.broadcast_to(key_order[:, None, :], vals.shape), axis=2)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.dtype(f"u{x.dtype.itemsize}"))


@pytest.mark.parametrize("num_strata", [1, 2, 3, 20, 50])
def test_key_order_select_equals_gather_bitwise(num_strata):
    """Each trial's stratum draws reach key order unchanged bit for bit,
    with trailing empty strata (key +inf, count 0) ordered last and
    values a gather passes through untouched: signed zeros, infinities,
    subnormals, a NaN's payload. The select lowers to no gather."""
    from repro.experiments.montecarlo import _key_order_select

    rng = np.random.default_rng(num_strata)
    a, t = 4, 9
    counts = rng.integers(1, 50, (a, num_strata))
    for i in range(a):                       # app i: i trailing empties
        counts[i, max(num_strata - i, 1):] = 0
    key = np.where(counts > 0, rng.random((a, num_strata)), np.inf)
    key_order = np.argsort(key, axis=1, kind="stable").astype(np.int32)
    vals = rng.lognormal(size=(a, t, num_strata)).astype(np.float32)
    special = np.array([-0.0, 0.0, np.inf, -np.inf, 1e-45, -3.5,
                        np.uint32(0x7FC00123).view(np.float32)], np.float32)
    vals.reshape(-1)[:special.size] = special[:vals.size]

    got = _key_order_select(vals, key_order)
    np.testing.assert_array_equal(_bits(got),
                                  _bits(_key_order_gather(vals, key_order)))
    lowered = jax.jit(_key_order_select).lower(vals, key_order).as_text()
    assert "gather" not in lowered


def test_run_trials_equals_key_order_gather_bitwise(engine, monkeypatch):
    """A whole study with the key-order select gives the per-trial
    estimates, errors, half-widths and every ``TrialStats`` leaf of the
    same study with the key-order gather, bit for bit."""
    from repro.experiments import montecarlo

    spec = TrialSpec(trials=600, keep_trials=True)       # every scheme
    montecarlo._streaming_program.cache_clear()
    new = run_trials(engine, spec, apps=APPS2)
    monkeypatch.setattr(montecarlo, "_key_order_select", _key_order_gather)
    montecarlo._streaming_program.cache_clear()
    try:
        old = run_trials(engine, spec, apps=APPS2)
    finally:
        montecarlo._streaming_program.cache_clear()
    for s in spec.schemes:
        for field in ("estimates", "errors", "half_widths"):
            np.testing.assert_array_equal(
                _bits(getattr(new, field)[s]), _bits(getattr(old, field)[s]))
        for leaf in dataclasses.fields(new.stats[s]):
            np.testing.assert_array_equal(
                _bits(getattr(new.stats[s], leaf.name)),
                _bits(getattr(old.stats[s], leaf.name)), err_msg=leaf.name)


def test_many_units_per_trial_chunked_equals_unchunked(engine):
    """A realistic SRS sample size (10^3 units per trial) streams, and
    any chunking still gives bitwise-equal per-trial outcomes."""
    spec = TrialSpec(trials=512, schemes=("random",), units_per_trial=1000,
                     keep_trials=True)
    res1 = run_trials(engine, spec, apps=(APP,))
    res2 = run_trials(engine, dataclasses.replace(
        spec, chunk_size=TRIAL_BLOCK), apps=(APP,))
    for field in ("estimates", "errors", "half_widths"):
        np.testing.assert_array_equal(getattr(res1, field)["random"],
                                      getattr(res2, field)["random"])
    np.testing.assert_array_equal(res1.stats["random"].cover,
                                  res2.stats["random"].cover)
    assert 0.85 <= float(res1.coverage["random"][0]) <= 1.0


def test_trial_uniforms_matches_block_contract(engine):
    """The dense reference helper reproduces the exact draws the chunked
    scan consumes — trial t at offset t % TRIAL_BLOCK of block
    t // TRIAL_BLOCK, regardless of the requested trial count."""
    spec = TrialSpec(trials=600, schemes=("random",))
    u_all = trial_uniforms(spec, "random", 2, 5)
    assert u_all.shape == (2, 600, 5)
    u_short = trial_uniforms(dataclasses.replace(spec, trials=100),
                             "random", 2, 5)
    np.testing.assert_array_equal(u_all[:, :100], u_short)


def test_chunk_size_must_align_to_block():
    with pytest.raises(ValueError, match="multiple of TRIAL_BLOCK"):
        TrialSpec(chunk_size=100)


@pytest.mark.parametrize("trials", [1_000, 10_000, 100_000, 1_000_000])
@pytest.mark.parametrize("ntd,devices", [(1, 1), (1, 2), (2, 4), (4, 8)])
def test_default_chunk_is_one_devices_per_device(trials, ntd, devices):
    """The default geometry covers the trials with whole blocks per
    trial device, holds per device at most one device's default
    trial-lanes (app shards of 10 apps), and pads less than a chunk
    per trial device; an explicit chunk size is kept."""
    from repro.experiments.montecarlo import _DEFAULT_CHUNK, _chunk_blocks

    blocks = -(-trials // TRIAL_BLOCK)
    kb, n_chunks = _chunk_blocks(TrialSpec(trials=trials), ntd, devices)
    assert kb % ntd == 0 and kb * n_chunks >= blocks
    assert kb * n_chunks - blocks < n_chunks * ntd
    apps_per_device = -(-10 // (devices // ntd))
    assert (kb // ntd * TRIAL_BLOCK * apps_per_device
            <= _DEFAULT_CHUNK * 10 + ntd * TRIAL_BLOCK * apps_per_device)
    if devices == 1:
        assert n_chunks == -(-blocks // (_DEFAULT_CHUNK // TRIAL_BLOCK))
    spec = TrialSpec(trials=trials, chunk_size=2048)
    assert _chunk_blocks(spec, ntd, devices)[0] == -(
        -min(8, blocks) // ntd) * ntd


# ------------------------------------------------ streamed vs dense parity
def test_streamed_stats_match_dense_reductions(engine):
    """TrialStats totals agree with dense per-trial reductions: counts
    exactly, moments to rounding, sketch quantiles to grid resolution
    (the satellite parity test for p95/half_width_pct at 1000 trials)."""
    spec = TrialSpec(trials=1000, keep_trials=True)
    res = run_trials(engine, spec, apps=APPS2)
    truth = np.stack(
        [e.truth[spec.config_index] for e in engine.build(APPS2)])
    for s in spec.schemes:
        st = res.stats[s]
        est, half = res.estimates[s], res.half_widths[s]
        err = res.errors[s]
        assert st.count.tolist() == [spec.trials, spec.trials]
        # coverage counts vs the dense |est - truth| <= half definition
        # (NaN half-widths never cover); same-op f32 host recomputation
        dense_cover = np.where(
            np.isnan(half), False,
            np.abs(est - truth[:, None].astype(est.dtype))
            <= np.nan_to_num(half)).mean(axis=1)
        np.testing.assert_allclose(res.coverage[s], dense_cover,
                                   atol=2.0 / spec.trials)
        # p95 from the sketch vs np.percentile on the dense errors
        np.testing.assert_allclose(res.p95(s),
                                   np.percentile(err, 95, axis=1),
                                   rtol=0.03)
        # streamed mean half-width == nanmean of dense half-widths
        # (f32 accumulation vs f64 host sum)
        np.testing.assert_allclose(np.asarray(st.half_mean),
                                   np.nanmean(half, axis=1), rtol=1e-4)
        # streamed error moments == dense sums (accumulated in f32)
        np.testing.assert_allclose(np.asarray(st.err_sum),
                                   err.sum(axis=1), rtol=1e-4)


def test_half_width_pct_streams(engine):
    """half_width_pct works off accumulated moments — identical with and
    without dense per-trial arrays materialized."""
    spec = TrialSpec(trials=512, schemes=("dg",))
    truth = np.asarray([1.0])
    r_keep = run_trials(engine, dataclasses.replace(spec, keep_trials=True),
                        apps=(APP,))
    r_stream = run_trials(engine,
                          dataclasses.replace(spec, keep_trials=False),
                          apps=(APP,))
    assert not r_stream.estimates and not r_stream.half_widths
    np.testing.assert_allclose(r_keep.half_width_pct("dg", truth),
                               r_stream.half_width_pct("dg", truth),
                               rtol=1e-6)
    dense = 100.0 * np.nanmean(r_keep.half_widths["dg"], axis=1)
    np.testing.assert_allclose(r_keep.half_width_pct("dg", truth), dense,
                               rtol=1e-4)


def test_run_trials_warm_call_does_not_recompile(engine, compile_counter):
    """A second identical ``run_trials`` hits the compiled chunk scan.

    Same spec, apps, chunking — the trial program must come back from
    the jit cache; a retrace here means the chunk scan's shapes or
    static args are derived from something unstable (recompile guard
    teeth on the streaming hot path)."""
    spec = TrialSpec(trials=TRIAL_BLOCK * 2, schemes=("random",))
    run_trials(engine, spec, apps=(APP,))         # warm: trace + compile
    with compile_counter.no_recompile("second identical run_trials"):
        run_trials(engine, spec, apps=(APP,))


# ------------------------------------------------ scale + calibration gate
def test_100k_trials_stream_with_calibrated_coverage(engine):
    """10^5 trials run through the chunked scan in bounded memory (no
    dense per-trial arrays) and the f32 accumulator policy keeps the
    calibrated/conservative schemes' empirical coverage >= 90% at
    nominal 95% — the gate proving streaming + f32 accumulation does not
    silently degrade calibration at scale."""
    spec = TrialSpec(trials=100_000, schemes=("random", "rfv"))
    res = run_trials(engine, spec, apps=(APP,))
    assert not res.estimates            # > keep threshold: streamed only
    for s in spec.schemes:
        st = res.stats[s]
        assert int(st.count[0]) == spec.trials
        assert float(res.coverage[s][0]) >= 0.90, (
            f"{s} coverage degraded: {res.coverage[s]}")
    # the quantile sketch is populated and readable at scale
    assert np.isfinite(res.p95("random")).all()


# ------------------------------------------------ sharded (app x trial)
@needs_devices
def test_app_trial_mesh_totals_match_single_device(engine):
    """(app x trial) sharded totals == single-device: integer leaves,
    float moments and dense per-trial arrays all bitwise (the same PRNG
    blocks are evaluated, merely on different devices)."""
    from repro.launch.mesh import make_app_trial_mesh

    spec = TrialSpec(trials=1000, keep_trials=True)
    single = run_trials(engine, spec, apps=APPS2, mesh=None)
    mesh = make_app_trial_mesh(app_devices=2)           # 2 apps x 4 trial
    eng2 = ExperimentEngine(mesh=mesh)
    sharded = run_trials(eng2, spec, apps=APPS2)
    for s in spec.schemes:
        st1, st2 = single.stats[s], sharded.stats[s]
        np.testing.assert_array_equal(st1.count, st2.count)
        np.testing.assert_array_equal(st1.cover, st2.cover)
        np.testing.assert_array_equal(st1.err_hist, st2.err_hist)
        np.testing.assert_array_equal(_bits(st1.err_sum), _bits(st2.err_sum))
        np.testing.assert_array_equal(single.estimates[s],
                                      sharded.estimates[s])
        np.testing.assert_array_equal(single.half_widths[s],
                                      sharded.half_widths[s])


@needs_devices
def test_trial_axis_splits_chunks():
    """The trial mesh axis actually divides each chunk's blocks."""
    from repro.distributed.appaxis import app_trial_axes
    from repro.launch.mesh import make_app_trial_mesh

    mesh = make_app_trial_mesh(app_devices=2)
    app_axis, trial_axis = app_trial_axes(mesh)
    assert (app_axis, trial_axis) == ("app", "trial")
    assert mesh.shape["app"] == 2 and mesh.shape["trial"] == 4


_MESH_ENGINE_CHILD = r"""
import dataclasses

import numpy as np

from repro.experiments import ExperimentEngine, TrialSpec, run_trials
from repro.launch.mesh import make_app_trial_mesh

def bits(x):
    return np.ascontiguousarray(x).view(np.uint8)

apps = ("505.mcf_r", "520.omnetpp_r")
mesh = make_app_trial_mesh(app_devices=2)
assert dict(mesh.shape) == {"app": 2, "trial": 2}, mesh.shape
one, sharded = ExperimentEngine(), ExperimentEngine(mesh=mesh)
for e1, e4 in zip(one.build(apps), sharded.build(apps)):
    for f in ("truth", "census_mat", "idx1", "cpi0_1", "bbv_feats",
              "bbv_labels", "bbv_centroids", "rfv_z", "rfv_labels",
              "rfv_centroids", "dg_labels"):
        np.testing.assert_array_equal(bits(getattr(e1, f)),
                                      bits(getattr(e4, f)), err_msg=f)
spec = TrialSpec(trials=1000, keep_trials=True)
r1, r4 = run_trials(one, spec, apps=apps), run_trials(sharded, spec, apps=apps)
for s in spec.schemes:
    for leaf in dataclasses.fields(r1.stats[s]):
        np.testing.assert_array_equal(
            bits(getattr(r1.stats[s], leaf.name)),
            bits(getattr(r4.stats[s], leaf.name)), err_msg=f"{s} {leaf.name}")
    for field in ("estimates", "half_widths"):
        np.testing.assert_array_equal(bits(getattr(r1, field)[s]),
                                      bits(getattr(r4, field)[s]),
                                      err_msg=f"{s} {field}")
print("mesh engine ok")
"""


def test_app_trial_mesh_engine_on_four_cpu_devices():
    """An engine built on a 2 x 2 ``("app", "trial")`` mesh against a
    one-device engine, in a child with four CPU devices: the census
    truth, the census pool, the phase-1 sample, the features, z-scores,
    centroids and labels of the strata, then every ``TrialStats`` leaf,
    the estimates and the half-widths of a study, all bit for bit. One
    app per device of the app axis, the split at which the perf model's
    bits once depended on the mesh."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   p for p in ("src", os.environ.get("PYTHONPATH")) if p))
    out = subprocess.run([sys.executable, "-c", _MESH_ENGINE_CHILD],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "mesh engine ok" in out.stdout


# ------------------------------------------------ precision policy
def test_precision_policy_contract():
    pp = PrecisionPolicy()
    assert (pp.trace, pp.accum, pp.host) == ("float32", "float32",
                                             "float64")
    assert not pp.needs_x64
    assert PrecisionPolicy(trace="float64").needs_x64
    assert PrecisionPolicy(trace=np.float64).trace == "float64"
    with pytest.raises(ValueError, match="must be one of"):
        PrecisionPolicy(trace="float16")
    # hashable + value equality (lru_cache / jit static keys)
    assert PrecisionPolicy() == PrecisionPolicy(trace=np.float32)
    assert len({PrecisionPolicy(), PrecisionPolicy.default()}) == 1
    assert resolve_precision(None, None) == PrecisionPolicy()
    assert resolve_precision(None, pp) is pp


def test_x64_context_keeps_float64():
    """An f64 policy's context computes in float64 on the installed JAX
    (the one place a JAX upgrade that moves ``enable_x64`` fails)."""
    import jax.numpy as jnp

    tiny = 2.0 ** -40                  # lost to rounding in float32
    with PrecisionPolicy(trace="float64").x64_context():
        x = jnp.asarray(1.0 + tiny, jnp.float64)
        assert x.dtype == jnp.float64
        assert float(x - 1.0) == tiny
    assert jnp.asarray(1.0).dtype == jnp.float32
    with PrecisionPolicy().x64_context():
        assert jnp.asarray(1.0).dtype == jnp.float32


def test_trials_under_x64_policy_agree_with_f32(engine):
    """A full-f64 policy reproduces the f32 policy's *distribution* —
    the cross-check that the default f32 trace/accum loses nothing that
    matters. Per-trial values are NOT comparable across trace dtypes
    (f64 uniforms consume different PRNG bits than f32), so the
    comparison is over aggregate statistics at 2048 trials."""
    spec32 = TrialSpec(trials=2048, schemes=("dg",), keep_trials=True)
    spec64 = dataclasses.replace(
        spec32, precision=PrecisionPolicy(trace="float64", accum="float64"))
    r32 = run_trials(engine, spec32, apps=(APP,))
    r64 = run_trials(engine, spec64, apps=(APP,))
    assert r64.estimates["dg"].dtype == np.float64
    np.testing.assert_allclose(np.mean(r32.estimates["dg"], axis=1),
                               np.mean(r64.estimates["dg"], axis=1),
                               rtol=1e-2)
    np.testing.assert_allclose(np.asarray(r32.stats["dg"].half_mean),
                               np.asarray(r64.stats["dg"].half_mean),
                               rtol=0.1)
    assert abs(float(r32.coverage["dg"][0])
               - float(r64.coverage["dg"][0])) <= 0.04


def test_trial_stats_merge_matches_split_accumulation():
    """Host-side merge of two partial accumulations == one accumulation
    over the concatenation (the additive-leaves contract the in-program
    psum relies on)."""
    rng = np.random.default_rng(0)
    err = rng.uniform(0.1, 30.0, size=(2, 64))
    half = rng.uniform(1e-3, 2.0, size=(2, 64))
    covered = rng.random((2, 64)) < 0.9
    valid = np.ones((2, 64), bool)
    whole = sampling_tables.trial_stats_update(
        sampling_tables.trial_stats_init((2,)), err, half, covered, valid)
    a = sampling_tables.trial_stats_update(
        sampling_tables.trial_stats_init((2,)), err[:, :40], half[:, :40],
        covered[:, :40], valid[:, :40])
    b = sampling_tables.trial_stats_update(
        sampling_tables.trial_stats_init((2,)), err[:, 40:], half[:, 40:],
        covered[:, 40:], valid[:, 40:])
    merged = sampling_tables.trial_stats_merge(a, b)
    np.testing.assert_array_equal(whole.count, merged.count)
    np.testing.assert_array_equal(whole.cover, merged.cover)
    np.testing.assert_array_equal(whole.err_hist, merged.err_hist)
    np.testing.assert_allclose(whole.err_sum, merged.err_sum, rtol=1e-6)
    # sketch quantiles track the dense percentile
    np.testing.assert_allclose(merged.err_quantile(0.95),
                               np.percentile(err, 95, axis=1), rtol=0.05)


# ------------------------------------------------ jitted Table IV sizing
def test_phase2_sizing_jit_matches_host_reference():
    """The jitted allocation program reproduces the historic host-numpy
    sizing exactly (f64 host-parity policy on CPU)."""
    from repro.core.sampling.allocation import neyman_allocation
    from repro.core.sampling.types import critical_value

    w = np.asarray([0.4, 0.3, 0.2, 0.1])
    s = np.asarray([1.5, 0.7, 0.3, 0.05])
    z = critical_value(0.95, None)
    margin, p1n, bvar = 0.05, 400, 0.09
    v_budget = (margin / z) ** 2 - bvar / p1n
    n_total = int(np.ceil((w * s).sum() ** 2 / v_budget))
    n_total = min(max(n_total, 2 * len(w)), 10**7)
    ref = neyman_allocation(w, s, n_total, min_per_stratum=2)
    got = phase2_sizes_for_margin(w, s, p1n, bvar,
                                  target_margin_abs=margin)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # proportional allocation routes through the same jitted program
    got_p = phase2_sizes_for_margin(w, s, p1n, bvar,
                                    target_margin_abs=margin,
                                    allocation="proportional")
    assert int(np.asarray(got_p).sum()) >= 2 * len(w)
    with pytest.raises(ValueError, match="unattainable"):
        phase2_sizes_for_margin(w, s, 10, 1.0, target_margin_abs=margin)