"""Checkpointed, fault-tolerant drivers over ``run_sweep``/``run_trials``.

Long sweep campaigns are restartable batch jobs: this module adds
restart boundaries ("quanta") at the paths' natural grain and proves —
structurally, not probabilistically — that a killed-and-resumed run is
the same run:

* **Sweeps** (``run_sweep_resumable``): a quantum is one
  ``(app-block × config-block)`` sub-sweep executed by the ordinary
  ``run_sweep`` (fused or staged). Selection, fills and estimates are
  pure functions of ``(engine build, spec, block)``, and the memo bank
  charges misses only — so any blocking's union of fills equals the
  unblocked run's, and ledger totals are path-independent.
* **Trials** (``run_trials_resumable``): a quantum is one segment of
  scan chunks per scheme. PRNG blocks are pure functions of
  ``(seed, scheme, block, app)`` (the ``TRIAL_BLOCK`` contract in
  ``repro.experiments.montecarlo``), so the streaming program replays
  any chunk suffix via its ``chunk0`` offset; the additive ``TrialStats``
  segments merge exactly like the in-scan carry.

After every quantum the driver snapshots the ``MemoBank`` (mask+value
blocks, charge matrix, ledger totals, ``version``), the partial results
and the progress cursor through ``repro.runtime.checkpoint`` — written
atomically, validated manifest-first on restore. Restore ORDER matters:
the engine is rebuilt (deterministically re-paying its phase-1 fill),
then ``MemoBank.load_state`` OVERWRITES all accounting with the
snapshot's, so nothing is double-charged and a resumed run's totals are
bitwise-equal to an uninterrupted one's.

The supervisors (``supervise_sweep``/``supervise_trials``) wrap a driver
in the elastic retry loop: catch ``HostLoss`` (real or injected via
``repro.runtime.faults``), shrink the device pool, re-plan the
``("app",)`` / ``("app", "trial")`` mesh (``repro.runtime.elastic``),
rebuild the engine, restore the latest checkpoint and continue — with
``repro.runtime.health.QuantumHealth`` recording per-quantum wall times
for the ``FleetReport`` postmortem.

Equivalence discipline (tests/test_fault_tolerance.py): killed/resumed
vs uninterrupted runs of the same blocking are bitwise-identical in
estimates, ledger charge totals and every ``TrialStats`` leaf. Across
*different* blockings (resumable vs plain, or an elastic re-mesh), the
integer leaves stay bitwise and float moment sums agree to summation
order; dense per-trial arrays are bitwise across chunkings of the same
dispatch (the PRNG block contract) but a re-mesh can refuse XLA's
per-trial arithmetic at the ULP level when the per-device block count
degenerates to one. Selection policies that consume
host-side randomness (``random``/``rankedset``) draw per app-block, so
their picks are deterministic given ``(seed, blocking)`` but differ
from an unblocked run — the paper matrix's deterministic policies
(``centroid``/``mean``) are blocking-invariant.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Sequence

import jax
import numpy as np

from ..core.sampling import tables as sampling_tables
from ..runtime.checkpoint import (latest_step, restore_checkpoint,
                                  save_checkpoint)
from ..runtime.elastic import ElasticRunner, build_mesh
from ..runtime.faults import FaultPlan, HostLoss
from ..runtime.health import QuantumHealth
from ..simcpu import APP_NAMES
from .engine import ExperimentEngine
from .montecarlo import (_KEEP_TRIALS_MAX, TRIAL_BLOCK, TrialResult,
                         TrialSpec, _chunk_blocks, _fetch_streaming_out,
                         _scheme_setup, _streaming_program, trial_key)
from .sweep import ResultsTable, SweepRow, SweepSpec, run_sweep

__all__ = ["FleetReport", "run_sweep_resumable", "run_trials_resumable",
           "supervise_sweep", "supervise_trials"]


def _trial_axis_size(mesh) -> int:
    if mesh is None:
        return 1
    from ..distributed.appaxis import app_trial_axes
    _, trial_axis = app_trial_axes(mesh)
    return 1 if trial_axis is None else int(mesh.shape[trial_axis])


# ------------------------------------------------------------------ sweeps
def run_sweep_resumable(engine: ExperimentEngine, spec: SweepSpec,
                        directory, *, app_block: int = 1,
                        config_block: Optional[int] = None,
                        injector=None, mesh=None,
                        monitor: Optional[Callable] = None,
                        keep: int = 3) -> ResultsTable:
    """``run_sweep`` with restart boundaries at app/config blocks.

    The sweep's (apps × configs) grid is partitioned into quanta of
    ``app_block`` apps × ``config_block`` configs (default: all configs
    per quantum); each quantum runs through the ordinary ``run_sweep``
    (fused or staged per ``spec.fused``) and is followed by one atomic
    checkpoint of the memo bank + partial result matrices + cursor into
    ``directory``. If ``directory`` already holds a checkpoint for the
    SAME run identity (scheme, policy, apps, configs, seeds, blocking —
    validated manifest-first), execution resumes at the saved cursor;
    a different identity raises ``ManifestMismatch`` before loading.

    ``injector`` is a ``repro.runtime.faults.FaultInjector`` threaded
    through the quantum lifecycle; ``monitor(quantum, seconds)`` feeds
    the supervisor's health trace. Returns the same ``ResultsTable`` an
    uninterrupted ``run_sweep`` of this blocking produces.
    """
    if spec.trials is not None:
        raise ValueError(
            "run_sweep_resumable checkpoints the sweep grid only; run the "
            "Monte-Carlo study through run_trials_resumable")
    mesh = engine.mesh if mesh is None else mesh
    apps = tuple(spec.apps)
    cfg_is = (tuple(range(len(engine.configs)))
              if spec.config_indices is None
              else tuple(int(i) for i in spec.config_indices))
    a_n, c_n = len(apps), len(cfg_is)
    ab = max(1, int(app_block))
    cb = c_n if config_block is None else max(1, int(config_block))
    quanta = [(a0, min(a0 + ab, a_n), c0, min(c0 + cb, c_n))
              for a0 in range(0, a_n, ab) for c0 in range(0, c_n, cb)]

    exps = engine.build(apps)                   # deterministic rebuild
    # fix the memo's config axis up front so every checkpoint in this
    # run (and its resumed continuations) has congruent table shapes
    engine.memo.cols_for(tuple(engine.configs[i] for i in cfg_is))
    truth = np.stack([e.truth for e in exps])[:, list(cfg_is)]

    run_id = {"kind": "sweep", "scheme": spec.scheme,
              "policy": spec.policy, "apps": list(apps),
              "config_indices": list(cfg_is),
              "selection_seed": int(spec.selection_seed),
              "fused": bool(spec.fused),
              "app_block": ab, "config_block": cb}

    ests = np.full((a_n, c_n), np.nan)
    errs = np.full((a_n, c_n), np.nan)
    margins = np.full((a_n, c_n), np.nan)
    n_units = np.zeros(a_n, np.int64)

    def snapshot():
        tree, meta = engine.memo.state()
        return {"memo": tree,
                "results": {"ests": ests, "errs": errs,
                            "margins": margins, "n_units": n_units}}, meta

    start = 0
    if latest_step(directory) is not None:
        template, _ = snapshot()
        tree, extra = restore_checkpoint(directory, template,
                                         expect={"run": run_id})
        engine.memo.load_state(tree["memo"], extra["memobank"],
                               universe=engine.configs)
        res = tree["results"]
        ests, errs = res["ests"], res["errs"]
        margins, n_units = res["margins"], res["n_units"]
        start = int(extra["next_quantum"])
    if injector is not None:
        injector.on_resume(start)

    for q in range(start, len(quanta)):
        t0 = time.perf_counter()
        a0, a1, c0, c1 = quanta[q]
        sub = dataclasses.replace(spec, apps=apps[a0:a1],
                                  config_indices=cfg_is[c0:c1])
        table = run_sweep(engine, sub, mesh=mesh)
        for i in range(a1 - a0):
            for j in range(c1 - c0):
                row = table.rows[i * (c1 - c0) + j]
                ests[a0 + i, c0 + j] = row.estimate
                errs[a0 + i, c0 + j] = row.err_pct
                if row.margin_pct is not None:
                    margins[a0 + i, c0 + j] = row.margin_pct
                n_units[a0 + i] = row.n_units
        if injector is not None:
            injector.quantum_computed()
        tree, meta = snapshot()
        save_checkpoint(directory, q, tree,
                        extra={"run": run_id, "memobank": meta,
                               "next_quantum": q + 1},
                        keep=keep,
                        fault_hook=None if injector is None
                        else injector.hook)
        if monitor is not None:
            monitor(q, time.perf_counter() - t0)
        if injector is not None:
            injector.quantum_checkpointed()

    srs = spec.plan is None
    rows = []
    for a, name in enumerate(apps):
        for j, cix in enumerate(cfg_is):
            rows.append(SweepRow(
                app=name, scheme=spec.scheme, config_index=int(cix),
                estimate=float(ests[a, j]), truth=float(truth[a, j]),
                err_pct=float(errs[a, j]), n_units=int(n_units[a]),
                margin_pct=float(margins[a, j]) if srs else None))
    return ResultsTable(rows)


# ------------------------------------------------------------------ trials
def run_trials_resumable(engine: ExperimentEngine,
                         spec: TrialSpec, directory, *,
                         apps: Optional[Sequence[str]] = None,
                         segment_trials: Optional[int] = None,
                         injector=None, mesh=None,
                         monitor: Optional[Callable] = None,
                         keep: int = 3) -> TrialResult:
    """``run_trials`` with restart boundaries at chunk segments.

    A quantum is one (scheme, chunk-segment) cell: ``segment_trials``
    trials' worth of scan chunks (default: the scheme's whole run in one
    quantum), executed by the shared streaming program with its
    ``chunk0`` offset — the PRNG-block contract makes the replayed
    chunks bitwise-identical to the same chunks of an uninterrupted
    scan. Segment ``TrialStats`` merge additively into the running
    accumulator (integer leaves exact; float moments associate by
    segment, identically in every resumed replay of the same blocking);
    dense per-trial arrays (when kept) slot into their trial range
    unchanged. Checkpoints carry accumulator + dense partials + memo
    bank + cursor, atomically, manifest-validated; ``injector`` /
    ``monitor`` follow ``run_sweep_resumable``.
    """
    apps = tuple(apps or APP_NAMES)
    mesh = engine.mesh if mesh is None else mesh
    # blocking is part of the run identity, so it must NOT depend on the
    # attempt's mesh (an elastic re-mesh would otherwise change the
    # quantum grid and refuse its own checkpoints): derive it
    # mesh-independently, and shard the trial axis only when it divides
    # the blocking — otherwise this attempt dispatches unsharded, which
    # is bitwise-equal (the chunked == unchunked contract), just slower
    kb, n_chunks = _chunk_blocks(spec, 1)
    ntd = _trial_axis_size(mesh)
    prog_mesh = mesh if (mesh is None or kb % max(ntd, 1) == 0) else None
    keep_dense = (spec.keep_trials if spec.keep_trials is not None
                  else spec.trials <= _KEEP_TRIALS_MAX)
    seg_chunks = (n_chunks if segment_trials is None
                  else max(1, -(-int(segment_trials) // (kb * TRIAL_BLOCK))))
    segments = [(c0, min(seg_chunks, n_chunks - c0))
                for c0 in range(0, n_chunks, seg_chunks)]
    quanta = [(scheme, c0, nc)
              for scheme in spec.schemes for (c0, nc) in segments]

    study = _scheme_setup(engine, spec, apps, mesh, None)
    pp = study.pp
    tdt = pp.trace_dtype
    a_n = len(apps)
    t_pad = n_chunks * kb * TRIAL_BLOCK

    run_id = {"kind": "trials", "apps": list(apps),
              "schemes": list(spec.schemes), "trials": int(spec.trials),
              "units_per_trial": int(spec.units_per_trial),
              "config_index": int(spec.config_index),
              "seed": int(spec.seed), "confidence": float(spec.confidence),
              "precision": [str(pp.trace), str(pp.accum)],
              "kb": int(kb), "seg_chunks": int(seg_chunks),
              "keep": bool(keep_dense)}

    stats = {s: sampling_tables.trial_stats_init(
        (a_n,), accum_dtype=np.dtype(pp.accum), xp=np)
        for s in spec.schemes}
    dense = ({s: {"est": np.zeros((a_n, t_pad), tdt),
                  "err": np.zeros((a_n, t_pad), tdt),
                  "half": np.zeros((a_n, t_pad), tdt)}
              for s in spec.schemes} if keep_dense else None)

    def snapshot():
        tree, meta = engine.memo.state()
        out = {"memo": tree, "stats": stats}
        if dense is not None:
            out["dense"] = dense
        return out, meta

    start = 0
    if latest_step(directory) is not None:
        template, _ = snapshot()
        tree, extra = restore_checkpoint(directory, template,
                                         expect={"run": run_id})
        engine.memo.load_state(tree["memo"], extra["memobank"],
                               universe=engine.configs)
        stats = tree["stats"]
        dense = tree.get("dense", dense)
        start = int(extra["next_quantum"])
    if injector is not None:
        injector.on_resume(start)

    for q in range(start, len(quanta)):
        t0 = time.perf_counter()
        scheme, c0, nc = quanta[q]
        chunk_fn, draws, _, _ = study.setups[scheme]
        program = _streaming_program(
            chunk_fn, prog_mesh, kb=kb, n_chunks=nc, trials=spec.trials,
            draws=draws, trace=pp.trace, accum=pp.accum, keep=keep_dense)
        with pp.x64_context():
            out = program(trial_key(spec, scheme), np.int32(c0),
                          *study.device_args(scheme, prog_mesh))
        st, ys = _fetch_streaming_out(out, a_n)
        stats[scheme] = sampling_tables.trial_stats_merge(stats[scheme], st)
        if keep_dense:
            off = c0 * kb * TRIAL_BLOCK
            for name, y in zip(("est", "err", "half"), ys):
                arr = y.transpose(1, 0, 2).reshape(a_n, -1)
                dense[scheme][name][:, off:off + arr.shape[1]] = arr
        if injector is not None:
            injector.quantum_computed()
        tree, meta = snapshot()
        save_checkpoint(directory, q, tree,
                        extra={"run": run_id, "memobank": meta,
                               "next_quantum": q + 1},
                        keep=keep,
                        fault_hook=None if injector is None
                        else injector.hook)
        if monitor is not None:
            monitor(q, time.perf_counter() - t0)
        if injector is not None:
            injector.quantum_checkpointed()

    estimates, errors, halves = {}, {}, {}
    if keep_dense:
        for s in spec.schemes:
            estimates[s] = dense[s]["est"][:, :spec.trials]
            errors[s] = dense[s]["err"][:, :spec.trials]
            halves[s] = dense[s]["half"][:, :spec.trials]
    return TrialResult(apps=apps, spec=spec, stats=dict(stats),
                       estimates=estimates, errors=errors,
                       half_widths=halves)


# -------------------------------------------------------------- supervisor
@dataclasses.dataclass
class FleetReport:
    """Postmortem of one supervised (elastic, fault-tolerant) run.

    ``attempts`` records each driver attempt (device count, mesh shape,
    outcome); ``mesh_history`` the elastic re-plans; ``quanta`` /
    ``stragglers`` the per-quantum health trace from ``QuantumHealth``.
    """

    attempts: list
    mesh_history: list
    quanta: list
    stragglers: list

    @property
    def restarts(self) -> int:
        """Restart count: attempts beyond the first."""
        return max(0, len(self.attempts) - 1)


def _supervise(run_attempt, *, faults: Optional[FaultPlan],
               max_restarts: int, mesh_kind: str, app_devices: int = 1,
               devices: Optional[Sequence] = None):
    """The elastic retry loop shared by both supervisors.

    Each attempt plans a mesh over the current healthy pool, builds it
    on those devices explicitly, and calls ``run_attempt(mesh, injector,
    monitor)``. A ``HostLoss`` (injected or real) shrinks the pool by
    ``devices_lost`` (never below 1) and retries — the driver's
    checkpoint restore plus the re-mesh invariant (app/trial lanes are
    pure data parallelism; global work is unchanged) carry the run
    forward. One injector spans all attempts so each planned fault fires
    exactly once.
    """
    pool = list(jax.devices() if devices is None else devices)
    injector = None if faults is None else faults.injector()
    runner = ElasticRunner(mesh_kind=mesh_kind, app_devices=app_devices)
    health = QuantumHealth()
    attempts: list[dict] = []
    for attempt in range(max_restarts + 1):
        n = len(pool)
        if n > 1:
            plan = runner.on_pool_change(n)
            mesh = build_mesh(plan, pool)
            shape = tuple(plan.shape)
        else:
            # a single device needs no mesh: the engine paths treat
            # mesh=None as the (bitwise-equal) unsharded dispatch
            mesh, shape = None, (1,)
            runner.history.append({"n_devices": 1, "shape": shape})
        record = {"attempt": attempt, "n_devices": n, "mesh_shape": shape}
        try:
            result = run_attempt(mesh, injector, health.record)
            record["outcome"] = "completed"
            attempts.append(record)
            return result, FleetReport(attempts=attempts,
                                       mesh_history=list(runner.history),
                                       quanta=list(health.quanta),
                                       stragglers=list(health.stragglers))
        except HostLoss as loss:
            record["outcome"] = "host_loss"
            record["error"] = str(loss)
            attempts.append(record)
            lost = max(0, int(loss.devices_lost))
            pool = pool[:max(1, n - lost)]
    raise RuntimeError(
        f"supervised run did not complete within {max_restarts} restarts")


def supervise_sweep(make_engine: Callable, spec: SweepSpec, directory, *,
                    faults: Optional[FaultPlan] = None, app_block: int = 1,
                    config_block: Optional[int] = None,
                    max_restarts: int = 8, keep: int = 3,
                    devices: Optional[Sequence] = None
                    ) -> tuple[ResultsTable, FleetReport]:
    """Run a checkpointed sweep under the elastic supervisor.

    ``make_engine(mesh)`` builds a fresh ``ExperimentEngine`` for each
    attempt's mesh (engines are rebuilt, state comes from the checkpoint
    in ``directory``); ``faults`` optionally injects a deterministic
    failure schedule. Returns ``(ResultsTable, FleetReport)``.
    """
    def attempt(mesh, injector, monitor):
        engine = make_engine(mesh)
        return run_sweep_resumable(
            engine, spec, directory, app_block=app_block,
            config_block=config_block, injector=injector, mesh=mesh,
            monitor=monitor, keep=keep)
    return _supervise(attempt, faults=faults, max_restarts=max_restarts,
                      mesh_kind="app", devices=devices)


def supervise_trials(make_engine: Callable, spec: TrialSpec, directory, *,
                     apps: Optional[Sequence[str]] = None,
                     faults: Optional[FaultPlan] = None,
                     segment_trials: Optional[int] = None,
                     max_restarts: int = 8, app_devices: int = 1,
                     keep: int = 3, devices: Optional[Sequence] = None
                     ) -> tuple[TrialResult, FleetReport]:
    """Run a checkpointed Monte-Carlo study under the elastic supervisor.

    Same contract as ``supervise_sweep`` over ``run_trials_resumable``;
    the mesh re-plans as 2-D ``("app", "trial")`` with the app degree
    held at ``app_devices`` while the trial axis absorbs pool shrink.
    Returns ``(TrialResult, FleetReport)``.
    """
    def attempt(mesh, injector, monitor):
        engine = make_engine(mesh)
        return run_trials_resumable(
            engine, spec, directory, apps=apps,
            segment_trials=segment_trials, injector=injector, mesh=mesh,
            monitor=monitor, keep=keep)
    return _supervise(attempt, faults=faults, max_restarts=max_restarts,
                      mesh_kind="app_trial", app_devices=app_devices,
                      devices=devices)
