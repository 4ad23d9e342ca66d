"""Compile the main path's device programs for a described TPU v5e.

Interpret mode (what every other kernel test runs) cannot see the TPU
lowering's tiling and VMEM rules, so these tests compile — without a
chip — the two Pallas kernels, the build's k-means fit, the fused sweep
megaprogram and the trial scan at the paper bank's real shapes (10
apps, up to 120k regions, 7 configs, L=20) for one chip of a ``v5e:2x2``
topology, and the app-sharded build programs and the trial scan with
its ``psum`` merge for the whole ``v5e:2x2`` as a 2 x 2 ``("app",
"trial")`` mesh. Nothing runs; a refusal by the TPU compiler fails the
test.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file. Keep all such compiles in this one file.
Code that asks ``jax.default_backend()`` still sees the CPU here, so each
test steers the kernel backend to the compiled Pallas path itself.
"""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.features import RFV_METRICS
from repro.core.precision import PrecisionPolicy
from repro.core.sampling.plan import Centroid, RFVClusters, SamplingPlan
from repro.kernels.backend import ResolvedBackend
from repro.kernels.kmeans_assign import ops as kmeans_ops
from repro.kernels.segment_stats import ops as segment_ops
from repro.simcpu import APP_SPECS, CONFIGS
from repro.simcpu.perfmodel import NUM_CONFIG_FIELDS
from repro.simcpu.workload import NUM_FEATURES

A = len(APP_SPECS)                            # the paper bank: 10 apps
N_ROWS = 120_832                              # 120k regions, tile-padded
N_MAX = max(s.n_regions for s in APP_SPECS)   # memo row width
N1_MAX = max(s.phase1_n for s in APP_SPECS)   # largest phase-1 sample
C = len(CONFIGS)
L = 20
HBM_BYTES = 16 * 2**30                        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def spec(one_chip):
    """``ShapeDtypeStruct`` factory placed on the described chip."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture
def compiled_segment_stats(monkeypatch):
    """Resolve ``segment_stats`` to the compiled kernel, as on a TPU."""
    monkeypatch.setattr(segment_ops, "resolve_segment_backend",
                        lambda requested: ResolvedBackend(requested, "pallas"))


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [20, 50])
def test_kmeans_assign_compiles_at_bank_batch(spec, monkeypatch, k):
    monkeypatch.setattr(kmeans_ops, "_on_tpu", lambda: True)
    compiled = jax.jit(kmeans_ops.kmeans_assign).lower(
        spec((A, N_ROWS, 15), jnp.float32),
        spec((A, k, 15), jnp.float32)).compile()
    rec = kmeans_ops.last_dispatch()
    assert rec["interpret"] is False
    assert rec["grid"] == (A, N_ROWS // rec["block_n"])
    assert _has_kernel(compiled)


def test_bank_kmeans_fit_compiles_at_bank_batch(spec, monkeypatch):
    """The build's BBV fit as ``"auto"`` runs it on a TPU: the whole
    Lloyd loop over the 10-app bank with the compiled assignment kernel."""
    from repro.core.clustering.kmeans import _bank_fit_fn

    monkeypatch.setattr(kmeans_ops, "_on_tpu", lambda: True)
    fit = _bank_fit_fn(L, 100, "pallas", 1e-8)
    compiled = jax.jit(fit).lower(spec((2,), jnp.uint32),
                                  spec((A, N_MAX, 15), jnp.float32),
                                  spec((A, N_MAX), jnp.float32)).compile()
    rec = kmeans_ops.last_dispatch()
    assert rec["interpret"] is False and rec["grid"][0] == A
    assert _has_kernel(compiled)


def test_segment_stats_compiles_at_bank_batch(spec, compiled_segment_stats):
    fn = functools.partial(segment_ops.segment_stats, num_segments=L)
    compiled = jax.jit(fn).lower(spec((A, N_ROWS, 1), jnp.float32),
                                 spec((A, N_ROWS), jnp.int32)).compile()
    rec = segment_ops.last_dispatch()
    assert rec["interpret"] is False and rec["batch"] == A
    assert _has_kernel(compiled)


def test_fused_megaprogram_compiles_at_paper_matrix(spec,
                                                    compiled_segment_stats):
    """rfv × centroid over 10 apps × 7 configs under the default policy,
    with the in-trace stratum summary on the compiled kernel."""
    from repro.experiments import fused

    plan = SamplingPlan(RFVClusters(), Centroid())
    assert not PrecisionPolicy.default().needs_x64
    f32, i32 = jnp.float32, jnp.int32
    args = (
        spec((A, N1_MAX), i32),                       # labels
        spec((A, N1_MAX), jnp.bool_),                 # valid units
        spec((A, L), f32),                            # stratum weights
        spec((A, N1_MAX), f32),                       # baseline CPI
        spec((A, N1_MAX), i32),                       # phase-1 pool
        spec((A, N1_MAX, len(RFV_METRICS)), f32),     # standardized RFVs
        spec((A, L, len(RFV_METRICS)), f32),          # RFV centroids
        None,                                         # uniforms
        spec((A, N_MAX, NUM_FEATURES), f32),          # population feats
        spec((C, NUM_CONFIG_FIELDS), f32),            # config matrix
        spec((A, C), f32),                            # truth
        spec((A, C, N_MAX), jnp.bool_),               # memo mask block
        spec((A, C, N_MAX), f32),                     # memo CPI block
    )
    # a fresh jit, so the steered backend is traced (not a cached trace)
    prog = jax.jit(fused._make_traced(plan), donate_argnums=fused._DONATE)
    compiled = prog.lower(*args).compile()
    assert segment_ops.last_dispatch()["batch"] == A
    assert _has_kernel(compiled)
    est = jax.eval_shape(fused._make_traced(plan), *args)[0]
    assert est.shape == (A, C)


def _scope_gathers(hlo: str, scope: str) -> list[str]:
    """The compiled program's gather instructions whose metadata places
    them under the named scope."""
    return [line for line in hlo.splitlines()
            if re.search(r"= [^=]*\bgather\(", line)
            and re.search(r'op_name="[^"]*\b' + re.escape(scope) + "/", line)]


@pytest.mark.parametrize("scheme", ["random", "rfv"])
def test_trial_scan_fits_one_chip_at_bank_size(spec, scheme):
    """The 10^4-trial scan over the 10-app bank at the default chunk: the
    per-trial gathers must not broadcast a census pool over the trial
    axis (10 x 4096 x 120k f32 would not fit the chip), and the
    collapsed-pairs key order stays a select: a v5e runs a gather
    element by element, and at L = 20 that one took 45% of the scan."""
    from repro.experiments import montecarlo as mc

    f32, i32 = jnp.float32, jnp.int32
    if scheme == "random":
        chunk_fn, draws = mc._srs_chunk, 20
        tables = (spec((A, N_MAX), f32), spec((A,), i32))
    else:
        chunk_fn, draws = mc._stratified_chunk, L
        tables = (spec((A, N1_MAX), f32),) + tuple(
            spec((A, L), dt) for dt in (i32, i32, f32, i32)) + tuple(
            spec((A, L // 2), dt) for dt in (f32, bool, bool)) + (
            spec((A,), i32),)
    kb, n_chunks = mc._chunk_blocks(mc.TrialSpec(trials=10_000), 1)
    prog = mc._streaming_program(
        chunk_fn, None, kb=kb, n_chunks=n_chunks, trials=10_000,
        draws=draws, trace="float32", accum="float32", keep=False)
    compiled = prog.lower(spec((2,), jnp.uint32), spec((), i32),
                          spec((A,), i32), spec((A,), f32),
                          spec((A,), f32), *tables).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < HBM_BYTES // 16
    assert _scope_gathers(compiled.as_text(), "trials.select")
    assert not _scope_gathers(compiled.as_text(), "trials.ci")


# ------------------------------------------------ the 2 x 2 (app, trial) mesh
@pytest.fixture(scope="module")
def mesh2x2(topo):
    from repro.launch.mesh import make_app_trial_mesh

    return make_app_trial_mesh(app_devices=2, devices=topo.devices)


@pytest.fixture
def on_mesh(mesh2x2):
    """``ShapeDtypeStruct`` factory laid out on the described mesh: app
    shards (``"app"``) or replicated (``None``)."""
    def make(shape, dtype, axis="app"):
        spec = PartitionSpec(axis) if axis else PartitionSpec()
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh2x2, spec))
    return make


@pytest.mark.parametrize("program", ["kmeans_fit", "cpi_bank"])
def test_app_sharded_build_compiles_on_2x2(on_mesh, mesh2x2, monkeypatch,
                                           program):
    """The build's BBV fit, with the compiled assignment kernel, and the
    census over the 10-app bank, each shard_map-ped over the app axis
    of the four chips (the trial axis holds copies)."""
    from repro.core.clustering.kmeans import _bank_fit_fn
    from repro.distributed.appaxis import make_app_sharded
    from repro.simcpu import perfmodel

    f32 = jnp.float32
    if program == "kmeans_fit":
        monkeypatch.setattr(kmeans_ops, "_on_tpu", lambda: True)
        fn = make_app_sharded(_bank_fit_fn(L, 100, "pallas", 1e-8), mesh2x2,
                              (0,))
        args = (on_mesh((2,), jnp.uint32, None),
                on_mesh((A, N_MAX, 15), f32), on_mesh((A, N_MAX), f32))
    else:
        fn = make_app_sharded(perfmodel._cpi_bank_fn, mesh2x2, (1,))
        args = (on_mesh((A, N_ROWS, NUM_FEATURES), f32),
                on_mesh((C, NUM_CONFIG_FIELDS), f32, None))
    compiled = jax.jit(fn).lower(*args).compile()
    if program == "kmeans_fit":
        rec = kmeans_ops.last_dispatch()
        assert rec["interpret"] is False and rec["grid"][0] == A // 2
        assert _has_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES // 4


@pytest.mark.parametrize("scheme", ["random", "rfv"])
def test_trial_scan_compiles_on_2x2_with_its_merge(on_mesh, mesh2x2, scheme):
    """The 10^5-trial scan over the 10-app bank on the four chips: each
    chip scans its app shard's half of every chunk, and the statistics
    meet in all-reduces under the ``trials.merge`` scope."""
    from repro.experiments import montecarlo as mc

    f32, i32 = jnp.float32, jnp.int32
    if scheme == "random":
        chunk_fn, draws = mc._srs_chunk, 20
        tables = (on_mesh((A, N_MAX), f32), on_mesh((A,), i32))
    else:
        chunk_fn, draws = mc._stratified_chunk, L
        tables = (on_mesh((A, N1_MAX), f32),) + tuple(
            on_mesh((A, L), dt) for dt in (i32, i32, f32, i32)) + tuple(
            on_mesh((A, L // 2), dt) for dt in (f32, bool, bool)) + (
            on_mesh((A,), i32),)
    kb, n_chunks = mc._chunk_blocks(mc.TrialSpec(trials=100_000), 2, 4)
    prog = mc._streaming_program(
        chunk_fn, mesh2x2, kb=kb, n_chunks=n_chunks, trials=100_000,
        draws=draws, trace="float32", accum="float32", keep=False)
    compiled = jax.jit(prog).lower(
        on_mesh((2,), jnp.uint32, None), on_mesh((), i32, None),
        on_mesh((A,), i32), on_mesh((A,), f32), on_mesh((A,), f32),
        *tables).compile()
    hlo = compiled.as_text()
    merges = [line for line in hlo.splitlines()
              if re.search(r"= [^=]*\ball-reduce\(", line)]
    assert merges and all("trials.merge" in line for line in merges)
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES // 16
