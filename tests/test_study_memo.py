"""The trial engine's per-engine memo of resolved studies.

A study's scheme inputs (truth, stratum tables, pools, critical values)
are pure functions of the engine's build and of the study's key, so the
engine keeps them, on the host and on the devices, and a later study
with the same key binds them again. These tests hold that memo to four
contracts:

* a study served from the memo is bitwise the study a fresh engine
  runs, on one device and on a 2 x 2 ``("app", "trial")`` mesh (four
  CPU devices, in a child process);
* the key: what changes a study's inputs misses, what does not hits,
  and the memo holds no more than its bound;
* the charged phase-1 fill runs in every study, so ``MemoBank``
  counters and ledgers are those of resolving every study anew, an
  evicted config column is charged again, and a resumed study stays
  bitwise an uninterrupted one;
* a hit uploads no table: its program inputs stay where they are.
"""

import dataclasses
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.precision import PrecisionPolicy
from repro.core.sampling.plan import DaleniusGurney, RFVClusters
from repro.experiments import (ExperimentEngine, TrialSpec, run_trials,
                               supervise_trials)
from repro.experiments.montecarlo import (_STUDY_MEMO_SIZE, SRS_DRAWS,
                                          TRIAL_BLOCK, TRIAL_SCHEMES,
                                          _scheme_setup, charged_pool_fill,
                                          study_memo)
from repro.runtime.faults import FaultEvent, FaultPlan

APPS = ("505.mcf_r", "520.omnetpp_r")
HIT_SPEC = TrialSpec(trials=1000, keep_trials=True, chunk_size=512, seed=21)


def _leaf_hashes(res) -> dict:
    """scheme -> {leaf: sha256} over every ``TrialStats`` leaf and the
    dense per-trial arrays of a ``TrialResult``."""
    out = {}
    for s in res.spec.schemes:
        st = res.stats[s]
        arrays = {f.name: getattr(st, f.name)
                  for f in dataclasses.fields(st)}
        for field in ("estimates", "errors", "half_widths"):
            arrays[field] = getattr(res, field)[s]
        out[s] = {k: hashlib.sha256(np.ascontiguousarray(v).tobytes())
                  .hexdigest() for k, v in arrays.items()}
    return out


def _hit_and_fresh(mesh) -> dict:
    """The study ``HIT_SPEC`` served from the memo (after a study of
    another seed on the same engine) and the same study on a fresh
    engine: their leaf hashes and the first engine's memo counts."""
    warm = ExperimentEngine(mesh=mesh)
    run_trials(warm, dataclasses.replace(HIT_SPEC, seed=20), apps=APPS)
    hit = run_trials(warm, HIT_SPEC, apps=APPS)
    fresh = run_trials(ExperimentEngine(mesh=mesh), HIT_SPEC, apps=APPS)
    memo = study_memo(warm)
    return {"hit": _leaf_hashes(hit), "fresh": _leaf_hashes(fresh),
            "hits": memo.hits, "misses": memo.misses}


_MESH_CHILD = r"""
import json
import sys

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, "tests")
from test_study_memo import APPS, HIT_SPEC, _hit_and_fresh

from repro.experiments import ExperimentEngine, run_trials
from repro.experiments.montecarlo import (_chunk_blocks, _scheme_setup,
                                          _streaming_program, trial_key)
from repro.launch.mesh import make_app_trial_mesh

mesh = make_app_trial_mesh(app_devices=2)
assert dict(mesh.shape) == {"app": 2, "trial": 2}, mesh.shape
out = _hit_and_fresh(mesh)

# a hit's inputs are resident in the program's layout: whole app shards
# with the sharding of the program's app-axis inputs (so it reshards
# nothing), and, with the key and chunk0 placed too, the call sends
# nothing from the host
engine = ExperimentEngine(mesh=mesh)
run_trials(engine, HIT_SPEC, apps=APPS)
study = _scheme_setup(engine, HIT_SPEC, APPS, mesh)
kb, n_chunks = _chunk_blocks(HIT_SPEC, 2, mesh.size)
rep, by_app = NamedSharding(mesh, P()), NamedSharding(mesh, P("app"))
moved = {}
for scheme in HIT_SPEC.schemes:
    chunk_fn, draws, _, _ = study.setups[scheme]
    program = _streaming_program(
        chunk_fn, mesh, kb=kb, n_chunks=n_chunks, trials=HIT_SPEC.trials,
        draws=draws, trace=study.pp.trace, accum=study.pp.accum, keep=True)
    args = study.device_args(scheme, mesh)
    moved[scheme] = "".join(
        f"input {i} {a.shape} {a.sharding}; " for i, a in enumerate(args)
        if a.shape[0] % 2 or not a.sharding.is_equivalent_to(by_app, a.ndim))
    key = jax.device_put(trial_key(HIT_SPEC, scheme), rep)
    chunk0 = jax.device_put(np.int32(0), rep)
    try:
        with jax.transfer_guard("disallow"):
            jax.block_until_ready(program(key, chunk0, *args))
    except Exception as exc:  # the guard's refusal names the transfer
        moved[scheme] += repr(exc)[:500]
out["moved"] = moved
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module", params=["none", "app2xtrial2"])
def hit_and_fresh(request):
    if request.param == "none":
        return _hit_and_fresh(None)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   p for p in ("src", os.environ.get("PYTHONPATH")) if p))
    proc = subprocess.run([sys.executable, "-c", _MESH_CHILD],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("scheme", TRIAL_SCHEMES)
def test_hit_is_bitwise_a_fresh_engines_study(hit_and_fresh, scheme):
    """Every ``TrialStats`` leaf and dense array of a study served from
    the memo equals a fresh engine's at the same seed, bit for bit; on
    the mesh, its resident inputs move nowhere when the program runs."""
    assert (hit_and_fresh["hits"], hit_and_fresh["misses"]) == (1, 1)
    hit, fresh = hit_and_fresh["hit"][scheme], hit_and_fresh["fresh"][scheme]
    assert hit.keys() == fresh.keys()
    assert [k for k in hit if hit[k] != fresh[k]] == []
    assert hit_and_fresh.get("moved", {}).get(scheme, "") == ""


# ------------------------------------------------------------------ the key
@pytest.fixture(scope="module")
def engine():
    eng = ExperimentEngine()
    eng.build(APPS)
    return eng


BASE = TrialSpec(trials=256, schemes=(SRS_DRAWS, "rfv"), seed=5)

KEY_CASES = [
    ("seed", dict(spec=dict(seed=6)), True),
    ("trials", dict(spec=dict(trials=512)), True),
    ("chunk_size", dict(spec=dict(chunk_size=TRIAL_BLOCK)), True),
    ("keep_trials", dict(spec=dict(keep_trials=False)), True),
    ("default_stratifier", dict(strats={"rfv": RFVClusters()}), True),
    ("config_index", dict(spec=dict(config_index=3)), False),
    ("schemes", dict(spec=dict(schemes=(SRS_DRAWS, "dg"))), False),
    ("confidence", dict(spec=dict(confidence=0.9)), False),
    ("units_per_trial", dict(spec=dict(units_per_trial=10)), False),
    ("stratifier_params", dict(strats={"rfv": RFVClusters(restarts=4)}),
     False),
    ("precision", dict(spec=dict(precision=PrecisionPolicy(
        trace="float64", accum="float64"))), False),
    ("apps", dict(apps=APPS[:1]), False),
]


@pytest.mark.parametrize("change,hits", [(c[1], c[2]) for c in KEY_CASES],
                         ids=[c[0] for c in KEY_CASES])
def test_key_hits_and_misses(engine, change, hits):
    """A change of what the scheme inputs depend on misses; a change of
    the seed, the trial count or the chunking hits."""
    memo = study_memo(engine)
    _scheme_setup(engine, BASE, APPS, None)
    h0, m0 = memo.hits, memo.misses
    spec = dataclasses.replace(BASE, **change.get("spec", {}))
    _scheme_setup(engine, spec, change.get("apps", APPS), None,
                  change.get("strats"))
    assert (memo.hits - h0, memo.misses - m0) == \
        ((1, 0) if hits else (0, 1))


def test_units_per_trial_is_no_key_without_srs(engine):
    """The SRS draw size changes no input of the stratified schemes."""
    memo = study_memo(engine)
    spec = TrialSpec(trials=256, schemes=("dg",), seed=5)
    _scheme_setup(engine, spec, APPS, None)
    h0, m0 = memo.hits, memo.misses
    _scheme_setup(engine, dataclasses.replace(spec, units_per_trial=7),
                  APPS, None)
    assert (memo.hits - h0, memo.misses - m0) == (1, 0)


def test_memo_holds_at_most_its_bound(engine):
    """Past ``_STUDY_MEMO_SIZE`` keys the least recently used goes: the
    memo never grows beyond its bound, a re-used key survives, and the
    oldest unused key misses again."""
    memo = study_memo(engine)
    spec = TrialSpec(trials=256, schemes=(SRS_DRAWS,))
    first = dataclasses.replace(spec, confidence=0.5)
    kept = dataclasses.replace(spec, confidence=0.51)
    _scheme_setup(engine, first, APPS, None)
    _scheme_setup(engine, kept, APPS, None)
    for i in range(_STUDY_MEMO_SIZE + 4):
        _scheme_setup(engine, kept, APPS, None)          # most recent
        _scheme_setup(engine, dataclasses.replace(
            spec, confidence=0.6 + 0.01 * i), APPS, None)
        assert len(memo) <= _STUDY_MEMO_SIZE
    assert len(memo) == _STUDY_MEMO_SIZE
    h0, m0 = memo.hits, memo.misses
    _scheme_setup(engine, kept, APPS, None)
    _scheme_setup(engine, first, APPS, None)
    assert (memo.hits - h0, memo.misses - m0) == (1, 1)


def test_unhashable_stratifier_is_resolved_every_study(engine):
    """A plug-in stratifier that cannot be hashed keys nothing: its
    studies resolve anew and the memo keeps none of them."""

    @dataclasses.dataclass(frozen=True)
    class ListedDG(DaleniusGurney):
        tags: list = dataclasses.field(default_factory=list)

    memo = study_memo(engine)
    spec = TrialSpec(trials=256, schemes=("dg",), seed=5)
    size, m0 = len(memo), memo.misses
    for _ in range(2):
        _scheme_setup(engine, spec, APPS, None, {"dg": ListedDG()})
    assert (memo.misses - m0, len(memo)) == (2, size)


def test_hit_gathers_no_features(engine, monkeypatch):
    """A fill the memo serves whole gathers no (A, n1_max, F) features;
    one that misses, after an eviction of the config, gathers them once."""
    from repro.experiments.engine import SweepStack

    calls = []
    gather = SweepStack.gather_feats
    monkeypatch.setattr(SweepStack, "gather_feats",
                        lambda self, idx: calls.append(1) or gather(self, idx))
    spec = TrialSpec(trials=256, schemes=("rfv",), config_index=1)
    _scheme_setup(engine, spec, APPS, None)
    calls.clear()
    _scheme_setup(engine, spec, APPS, None)
    assert calls == []
    engine.memo.evict(engine.memo.cols_for([engine.configs[1]]))
    _scheme_setup(engine, spec, APPS, None)
    assert calls == [1]


# ------------------------------------------------------- ledger exactness
def _counters(eng):
    memo = eng.memo
    return (list(memo.hit_count), list(memo.miss_count),
            memo.charges.copy(),
            [None if l is None else (l.regions_simulated,
                                     l.instructions_simulated)
             for l in memo.ledgers])


def _assert_counters_equal(a, b):
    assert a[0] == b[0] and a[1] == b[1] and a[3] == b[3]
    np.testing.assert_array_equal(a[2], b[2])


def test_memo_counters_equal_a_fill_per_study():
    """N studies on one key leave the ``MemoBank`` hit and miss counts,
    charges and ledgers of N charged fills, as when every study resolved
    anew; N - 1 of them are served from the memo."""
    n = 3
    spec = TrialSpec(trials=256, schemes=(SRS_DRAWS, "rfv", "dg"),
                     config_index=4)
    served = ExperimentEngine()
    for seed in range(n):
        run_trials(served, dataclasses.replace(spec, seed=seed), apps=APPS)
    replayed = ExperimentEngine()
    replayed.build(APPS)
    for _ in range(n):
        charged_pool_fill(replayed, spec, APPS)
    _assert_counters_equal(_counters(served), _counters(replayed))
    memo = study_memo(served)
    assert (memo.hits, memo.misses) == (n - 1, 1)


def test_evicted_config_column_charges_again_with_the_same_study():
    """After ``MemoBank.evict`` of the study's config column the next
    study charges the phase-1 fill again, as a first fill does, and is
    still served from the memo with the same statistics."""
    spec = TrialSpec(trials=512, schemes=(SRS_DRAWS, "rfv", "dg"),
                     config_index=5, keep_trials=True, seed=9)
    eng = ExperimentEngine()
    eng.build(APPS)
    regions0 = [l.regions_simulated for l in eng.memo.ledgers]
    first = run_trials(eng, spec, apps=APPS)
    paid = [l.regions_simulated - r
            for l, r in zip(eng.memo.ledgers, regions0)]
    assert min(paid) > 0
    run_trials(eng, spec, apps=APPS)                  # a pure hit: free
    before = [l.regions_simulated for l in eng.memo.ledgers]
    assert [b - r for b, r in zip(before, regions0)] == paid
    eng.memo.evict(eng.memo.cols_for([eng.configs[spec.config_index]]))
    memo = study_memo(eng)
    h0 = memo.hits
    again = run_trials(eng, spec, apps=APPS)
    assert [l.regions_simulated - b
            for l, b in zip(eng.memo.ledgers, before)] == paid
    assert memo.hits == h0 + 1
    assert _leaf_hashes(again) == _leaf_hashes(first)


def test_resumed_study_on_a_warm_engine_equals_uninterrupted(tmp_path):
    """A study killed after a checkpoint and resumed on an engine that
    already holds its resolved study equals, bit for bit, the same run
    uninterrupted on that engine when it was fresh."""
    spec = TrialSpec(trials=512, chunk_size=TRIAL_BLOCK, keep_trials=True)
    eng = ExperimentEngine()
    whole, _ = supervise_trials(lambda mesh: eng, spec, tmp_path / "u",
                                apps=APPS, segment_trials=256)
    memo = study_memo(eng)
    assert (memo.hits, memo.misses) == (0, 1)
    plan = FaultPlan((FaultEvent("kill", 3),))
    resumed, rep = supervise_trials(lambda mesh: eng, spec, tmp_path / "f",
                                    apps=APPS, faults=plan,
                                    segment_trials=256)
    assert rep.restarts == 1
    assert (memo.hits, memo.misses) == (2, 1)
    assert _leaf_hashes(resumed) == _leaf_hashes(whole)


def _scale_pool(eng, spec, factor):
    """Scale the values the memo holds at the study's config: the fill
    now returns other values than it did."""
    rows = eng.stack(APPS).rows
    col = eng.memo.cols_for([eng.configs[spec.config_index]])[0]
    eng.memo.cpi[rows, col] *= np.float32(factor)
    eng.memo.touch()


def test_stale_pool_resolves_the_study_anew():
    """A kept study serves only while the fill returns the pool its
    tables were built from: past that it resolves anew, as the parent
    does every study, with one fill and its memo counts."""
    spec = TrialSpec(trials=512, schemes=(SRS_DRAWS, "rfv", "dg"),
                     config_index=2, keep_trials=True, seed=4)
    warm = ExperimentEngine()
    run_trials(warm, spec, apps=APPS)
    _scale_pool(warm, spec, 1.5)
    stale = run_trials(warm, spec, apps=APPS)
    fresh = ExperimentEngine()
    fresh.build(APPS)
    charged_pool_fill(fresh, spec, APPS)     # pays as warm's first study
    _scale_pool(fresh, spec, 1.5)
    want = run_trials(fresh, spec, apps=APPS)
    memo = study_memo(warm)
    assert (memo.hits, memo.misses) == (0, 2)
    assert _leaf_hashes(stale) == _leaf_hashes(want)
    _assert_counters_equal(_counters(warm), _counters(fresh))
