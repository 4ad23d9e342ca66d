"""Profiler spans and named scopes of the trial engine and the build.

A traced ``run_trials`` on a fresh engine records, on the calling
thread's line of the host plane, the build's stage spans (``build.*``)
and the trial engine's host spans (``trials.*``) with their arguments;
the compiled streaming programs carry the five scan-stage scopes in
their HLO ``op_name`` metadata, which is where a device trace finds
them.
"""

import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.core.precision import resolve_precision
from repro.experiments import ExperimentEngine, TrialSpec, run_trials
from repro.experiments.montecarlo import (TRIAL_SCHEMES, _chunk_blocks,
                                          _scheme_setup, _streaming_program,
                                          trial_key)

APPS2 = ("505.mcf_r", "520.omnetpp_r")
BUILD_STAGES = ("build.population", "build.census", "build.bbv",
                "build.phase1", "build.rfv", "build.dg")
SCAN_SCOPES = ("trials.draw", "trials.select", "trials.ci", "trials.fold",
               "trials.hist")


def _host_spans(trace_dir: str, prefixes) -> list[dict]:
    """Host events whose name starts with one of ``prefixes``, from the
    newest trace under ``trace_dir``: name, [start, end) and stats."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    out.append(dict(
                        name=ev.name, start=ev.start_ns,
                        end=ev.start_ns + ev.duration_ns,
                        args={k: v for k, v in ev.stats}))
    return out


def _inside(inner: dict, outer: dict) -> bool:
    return outer["start"] <= inner["start"] and inner["end"] <= outer["end"]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The spans of one study on a fresh engine (so its build runs inside
    the trace), every scheme, 512 trials, dense arrays kept."""
    spec = TrialSpec(trials=512, schemes=TRIAL_SCHEMES, seed=11)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        run_trials(ExperimentEngine(), spec, apps=APPS2)
    return spec, _host_spans(trace_dir, ("trials.", "build."))


def test_run_trials_spans_nest_under_run(traced):
    spec, spans = traced
    runs = [s for s in spans if s["name"] == "trials.run"]
    assert len(runs) == 1
    run = runs[0]
    assert run["args"] == {"seed": spec.seed, "trials": spec.trials,
                           "mesh": "none"}
    names = {s["name"] for s in spans}
    assert names >= {"trials.setup", "trials.resolve", "trials.pool_fill",
                     "trials.tables", "trials.dispatch", "trials.fetch"}
    for s in spans:
        assert _inside(s, run), s["name"]
    setup = next(s for s in spans if s["name"] == "trials.setup")
    for name in ("trials.resolve", "trials.pool_fill", "trials.tables"):
        assert all(_inside(s, setup) for s in spans if s["name"] == name)


@pytest.mark.parametrize("name", ["trials.tables", "trials.dispatch",
                                  "trials.fetch"])
def test_per_scheme_spans_name_their_scheme(traced, name):
    spec, spans = traced
    per = [s for s in spans if s["name"] == name]
    assert [s["args"]["scheme"] for s in per] == list(spec.schemes)
    if name == "trials.dispatch":
        assert all(s["args"]["h2d_bytes"] > 0 for s in per)


def _mesh_2x2():
    """What ``mesh_tag`` and ``_h2d_bytes`` read of a 2 x 2
    ``("app", "trial")`` mesh, without four devices."""
    from types import SimpleNamespace

    return SimpleNamespace(axis_names=("app", "trial"),
                           shape={"app": 2, "trial": 2}, size=4)


def test_mesh_tag_names_the_layout():
    from repro.launch.mesh import mesh_tag

    assert mesh_tag(None) == "none"
    assert mesh_tag(_mesh_2x2()) == "app2xtrial2"


def test_h2d_bytes_count_every_devices_copy():
    """Under a 2 x 2 mesh each app shard goes to both devices of its
    trial axis, the app axis padded to whole shards first."""
    from repro.experiments.montecarlo import _h2d_bytes

    ten = (np.zeros(10, np.int32), np.zeros((10, 6), np.float32),
           np.int32(0))
    assert _h2d_bytes(ten, None) == 40 + 240
    assert _h2d_bytes(ten, _mesh_2x2()) == 2 * (40 + 240)
    three = (np.zeros((3, 5), np.float32),)
    assert _h2d_bytes(three, _mesh_2x2()) == 2 * 4 * 5 * 4


def test_build_stage_spans_in_order_inside_resolve(traced):
    _, spans = traced
    resolve = next(s for s in spans if s["name"] == "trials.resolve")
    stages = sorted((s for s in spans if s["name"].startswith("build.")),
                    key=lambda s: s["start"])
    assert tuple(s["name"] for s in stages) == BUILD_STAGES
    assert all(_inside(s, resolve) for s in stages)
    assert all(a["end"] <= b["start"] for a, b in zip(stages, stages[1:]))


@pytest.fixture(scope="module")
def engine():
    return ExperimentEngine()


@pytest.fixture(scope="module")
def traced_twice(engine, tmp_path_factory):
    """The spans of two studies of one key on one engine, at two seeds:
    the first resolves the study, the second is served from the memo."""
    trace_dir = str(tmp_path_factory.mktemp("trace_twice"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        for seed in (31, 32):
            run_trials(engine, TrialSpec(trials=512, seed=seed), apps=APPS2)
    spans = _host_spans(trace_dir, ("trials.",))
    runs = sorted((s for s in spans if s["name"] == "trials.run"),
                  key=lambda s: s["start"])
    return [[s for s in spans if _inside(s, run)] for run in runs]


def test_setup_span_says_whether_the_memo_served_it(traced_twice):
    """``trials.setup`` carries ``cached`` 0 on the first study and 1 on
    the second; only the first resolves and builds tables, both replay
    the charged fill."""
    assert len(traced_twice) == 2
    for cached, spans in enumerate(traced_twice):
        names = [s["name"] for s in spans]
        setup = [s for s in spans if s["name"] == "trials.setup"]
        assert [s["args"] for s in setup] == [{"cached": cached}]
        assert names.count("trials.pool_fill") == 1
        assert names.count("trials.resolve") == 1 - cached
        assert names.count("trials.tables") == (1 - cached) * len(
            TRIAL_SCHEMES)


def test_h2d_bytes_on_a_hit_count_only_the_key_and_chunk0(traced_twice):
    """Served from the memo, a dispatch sends its PRNG key (two uint32)
    and ``chunk0`` (one int32): the tables stay on the device."""
    first, second = ([s["args"]["h2d_bytes"] for s in spans
                      if s["name"] == "trials.dispatch"]
                     for spans in traced_twice)
    assert second == [12] * len(TRIAL_SCHEMES)
    assert all(b > 12 for b in first)


@pytest.mark.parametrize("scheme", ["random", "dg"])
def test_streaming_program_hlo_carries_scan_scopes(engine, scheme):
    """Both chunk functions' compiled scans name every stage in their
    ``op_name`` metadata, the SRS t-interval and the stratified
    collapsed-pairs alike."""
    spec = TrialSpec(trials=512, schemes=(scheme,), chunk_size=256)
    study = _scheme_setup(engine, spec, APPS2, None)
    pp = study.pp
    chunk_fn, draws, crit, tables = study.setups[scheme]
    kb, n_chunks = _chunk_blocks(spec, 1)
    program = _streaming_program(
        chunk_fn, None, kb=kb, n_chunks=n_chunks, trials=spec.trials,
        draws=draws, trace=pp.trace, accum=pp.accum, keep=False)
    tdt = resolve_precision(spec.precision, engine.precision).trace_dtype
    hlo = program.lower(trial_key(spec, scheme), np.int32(0),
                        np.arange(len(APPS2), dtype=np.int32),
                        study.truth.astype(tdt), crit,
                        *tables).compile().as_text()
    for scope in SCAN_SCOPES:
        assert re.search(r'op_name="[^"]*/' + re.escape(scope) + "/", hlo), \
            scope


def test_compile_cache_keeps_each_programs_scopes(monkeypatch, tmp_path):
    """Entry points key the persistent cache on the programs' metadata: a
    program that differs from a cached one only in a named scope is
    compiled afresh, so its profile shows its own scopes (by default JAX
    would load the cached executable and its stale scopes)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.runtime import compile_cache

    def scoped(scope):
        def f(x):
            with jax.named_scope(scope):
                return jax.numpy.sin(x) * 3
        return jax.jit(f)

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_include_metadata_in_key")
    before = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    try:
        cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compile_cache.enable_compile_cache()
        x = np.arange(8.0, dtype=np.float32)
        scoped("stage.first").lower(x).compile()
        hlo = scoped("stage.second").lower(x).compile().as_text()
        assert "stage.second" in hlo and "stage.first" not in hlo
        assert len(list(tmp_path.iterdir())) >= 2
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
