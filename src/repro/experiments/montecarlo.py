"""Engine-backed Monte-Carlo trials (paper Fig 8) as a streaming reduction.

``run_trials`` used to vmap one monolithic ``(A, T, ...)`` program per
scheme, so host and device memory scaled linearly with the trial count T
— fine at the paper's 1000 trials, a wall at the 10^5–10^6 replications
the conservative-CI claim needs. This module streams instead: a chunked
``lax.scan`` over fixed-size trial blocks folds every chunk's per-trial
outcomes into an *additive* accumulator (``TrialStats`` in
``repro.core.sampling.tables`` — running coverage counts, error moments,
log-histogram quantile sketches), so memory is bounded by one chunk at
any trial count and per-trial arrays never materialize unless asked for.

PRNG contract (the chunked == unchunked bitwise guarantee): uniforms are
drawn in fixed ``TRIAL_BLOCK``-sized trial blocks, block ``b`` of app
``a`` from ``fold_in(fold_in(trial_key, b), a)`` — a pure function of
(seed, scheme, block, app). Any chunking of the scan, any ``("app",)``
or ``("app", "trial")`` mesh sharding, and the ``trial_uniforms``
reference helper therefore consume bitwise-identical draws.

Mesh story: with a 2-D ``("app", "trial")`` mesh
(``repro.launch.mesh.make_app_trial_mesh``) each chunk is ``shard_map``-
ped over both axes — app lanes stay independent, and the trial axis
splits each chunk's blocks across devices, with the accumulator merged
by a ``psum`` over the trial axis (additivity makes the cross-device
coverage/CI merge exact: sharded totals equal single-device totals).
The float moments are summed per PRNG block and then over the blocks in
an order fixed by the trial count (``_block_moments``,
``_pairwise_sum``), so they too are bitwise the same at any chunking and
on any mesh.

The per-trial math is unchanged from the vmapped design: the SRS scheme
evaluates the eq. (2) t-interval, the one-unit-per-stratum schemes the
pairwise collapsed-strata variance (eq. 4) over occupied strata in
baseline-CPI order, lane-wise via ``repro.core.sampling.tables``.
Dtypes route through ONE ``PrecisionPolicy`` (``repro.core.precision``):
trace dtype for the chunk programs, accumulator dtype for the scan
carry, host dtype for numpy-side statistics.

Cost accounting matches the figure's semantics exactly: schemes drawing
from census CPI (``random``, ``bbv``) are analysis-only and free; schemes
drawing from the phase-1 sample (``rfv``, ``dg``) pull their value pool
through the engine's charged ``MemoBank`` (paid once, like the historic
``exp.cpi(cfg, exp.idx1)``).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import weakref
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.precision import PrecisionPolicy, resolve_precision
from ..core.sampling import plan as sampling_plan
from ..core.sampling import tables as sampling_tables
from ..core.sampling.types import critical_values
from ..simcpu import APP_NAMES, stack_ragged
from .engine import ExperimentEngine, stratum_tables

__all__ = ["SRS_DRAWS", "TRIAL_SCHEMES", "TRIAL_BLOCK", "ResolvedStudy",
           "StudyMemo", "TrialSpec", "TrialResult", "charged_pool_fill",
           "run_trials", "study_memo", "trial_key", "trial_uniforms"]

# the plan-less trial scheme: n-unit uniform draws from the census pool
SRS_DRAWS = "random"
# canonical scheme order: key derivation is position-based so a scheme's
# draws are identical no matter which subset a TrialSpec requests;
# registry plug-ins hash their name past this range (trial_key)
TRIAL_SCHEMES = (SRS_DRAWS, "bbv", "rfv", "dg")

# PRNG block granularity: uniforms are drawn per TRIAL_BLOCK trials from a
# per-block fold-in, so draws are a function of the block index alone —
# the unit the chunked scan, the trial-mesh split and the dense reference
# all agree on. Chunk sizes are multiples of this.
TRIAL_BLOCK = 256
# default trials per scan step on one device: bounds its live memory at
# ~chunk × pool-width
_DEFAULT_CHUNK = 4096
# keep dense (A, T) per-trial arrays by default up to this many trials
# (the Fig 8 regime); past it only the streamed statistics come home
_KEEP_TRIALS_MAX = 8192
# resolved studies an engine keeps, the least recently used dropped past
# it: an entry holds every scheme's tables, about 10 MB at the 10-app
# bank, on the host and again on the devices
_STUDY_MEMO_SIZE = 16


@dataclasses.dataclass(frozen=True)
class TrialSpec:
    """Monte-Carlo repetition axes for one study configuration.

    ``schemes`` names the stratifications to study: ``"random"`` (the
    plan-less SRS reference) plus any *registered* stratifier name
    (``repro.core.sampling.plan``) — names are validated against the
    registry at construction, so an unknown scheme fails here rather
    than mid-study.

    Streaming knobs: ``chunk_size`` fixes the trials evaluated per scan
    step (a positive multiple of ``TRIAL_BLOCK``; default ~4096 per
    device of the mesh, rounded to the trial-mesh split and evened out
    over the scan) — it changes memory and scheduling, never
    results. ``keep_trials`` forces (True) or suppresses (False) the
    dense per-trial ``(A, T)`` arrays; default keeps them only up to
    8192 trials. ``precision`` overrides the engine's
    ``PrecisionPolicy`` for the trial programs.
    """

    trials: int = 1000
    units_per_trial: int = 20          # SRS draw size (scheme "random")
    schemes: tuple[str, ...] = TRIAL_SCHEMES
    config_index: int = 6              # study config (paper: Config 6)
    seed: int = 7
    confidence: float = 0.95           # per-trial CI level
    chunk_size: Optional[int] = None   # trials per scan step
    keep_trials: Optional[bool] = None  # materialize dense (A, T) arrays
    precision: Optional[PrecisionPolicy] = None

    def __post_init__(self):
        unknown = (set(self.schemes) - {SRS_DRAWS}
                   - set(sampling_plan.registered_stratifiers()))
        if unknown:
            raise ValueError(
                f"unknown trial scheme(s) {sorted(unknown)}; known: "
                f"{(SRS_DRAWS,) + sampling_plan.registered_stratifiers()}")
        if self.chunk_size is not None and (
                self.chunk_size <= 0 or self.chunk_size % TRIAL_BLOCK):
            raise ValueError(
                f"chunk_size must be a positive multiple of TRIAL_BLOCK="
                f"{TRIAL_BLOCK}, got {self.chunk_size}")


@dataclasses.dataclass(frozen=True)
class TrialResult:
    """Per-scheme Monte-Carlo outcomes for one ``run_trials`` study.

    ``stats[scheme]`` is the streamed ``TrialStats`` accumulator — the
    always-available product of the chunked scan: trial/coverage counts,
    error and half-width moments, and log-histogram quantile sketches,
    all per app. ``coverage``, ``p95`` and ``half_width_pct`` read from
    it, so they work at any trial count without per-trial arrays.

    ``estimates[scheme]`` / ``errors[scheme]`` / ``half_widths[scheme]``
    are the dense ``(A, T)`` per-trial arrays (estimated mean CPI,
    percent |error| vs the census truth, absolute CI half-width at
    ``spec.confidence``) — populated only when the spec keeps them
    (``TrialSpec.keep_trials``; default up to 8192 trials). SRS trials
    use the eq. (2) t-interval; stratified one-unit-per-stratum trials
    the eq. (4) collapsed-pairs interval.
    """

    apps: tuple[str, ...]
    spec: TrialSpec
    stats: dict[str, sampling_tables.TrialStats]
    estimates: dict[str, np.ndarray] = dataclasses.field(
        default_factory=dict)       # scheme -> (A, T), only when kept
    errors: dict[str, np.ndarray] = dataclasses.field(
        default_factory=dict)       # scheme -> (A, T), only when kept
    half_widths: dict[str, np.ndarray] = dataclasses.field(
        default_factory=dict)       # scheme -> (A, T), only when kept

    @property
    def coverage(self) -> dict[str, np.ndarray]:
        """scheme -> (A,) empirical coverage — the fraction of trials
        whose CI contains the truth, from the streamed counts (exact)."""
        return {s: np.asarray(st.coverage) for s, st in self.stats.items()}

    def p95(self, scheme: str) -> np.ndarray:
        """(A,) 95th-percentile |error| per app (the Fig 8 statistic),
        read from the streamed quantile sketch — no per-trial arrays."""
        return np.asarray(self.stats[scheme].err_quantile(0.95))

    def half_width_pct(self, scheme: str, truth: np.ndarray) -> np.ndarray:
        """(A,) mean CI half-width as percent of the per-app truth, from
        the streamed moments (the nanmean of per-trial widths)."""
        return 100.0 * np.asarray(self.stats[scheme].half_mean) \
            / np.asarray(truth)


def trial_key(spec: TrialSpec, scheme: str) -> jax.Array:
    """Per-scheme PRNG key; exposed so reference implementations (tests)
    can reproduce the exact uniforms ``run_trials`` consumes.

    Canonical schemes keep their historic fold-in positions; registered
    plug-in schemes hash their name past the canonical range
    (``sampling_plan.trial_scheme_index``) so draws never depend on
    registration order.
    """
    return jax.random.fold_in(
        jax.random.PRNGKey(spec.seed),
        sampling_plan.trial_scheme_index(scheme, TRIAL_SCHEMES))


def _block_uniforms(key, block_index, app_ids, draws: int, dtype):
    """(A, TRIAL_BLOCK, D) canonical draws for one trial block.

    Block ``b`` of app ``a`` is ``uniform(fold_in(fold_in(key, b), a))``
    — a pure function of (key, block, app), independent of the total
    trial count, the chunking, the mesh, or which apps run together.
    This is the contract that makes chunked == unchunked and sharded ==
    single-device runs consume bitwise-identical uniforms.
    """
    bk = jax.random.fold_in(key, block_index)
    return jax.vmap(lambda a: jax.random.uniform(
        jax.random.fold_in(bk, a), (TRIAL_BLOCK, draws), dtype))(app_ids)


def _run_uniforms(key, start_block, num_blocks: int, app_ids,
                  draws: int, dtype):
    """(A, num_blocks * TRIAL_BLOCK, D) draws for consecutive blocks."""
    blocks = jax.vmap(
        lambda b: _block_uniforms(key, b, app_ids, draws, dtype))(
            start_block + jnp.arange(num_blocks))
    a = app_ids.shape[0]
    return blocks.transpose(1, 0, 2, 3).reshape(
        a, num_blocks * TRIAL_BLOCK, draws)


def trial_uniforms(spec: TrialSpec, scheme: str, num_apps: int,
                   draws_per_trial: int) -> np.ndarray:
    """The (A, T, D) uniform draws backing one scheme's trials — the
    dense reference view of the block-based PRNG contract
    (``_block_uniforms``); trial ``t`` lives at offset ``t % TRIAL_BLOCK``
    of block ``t // TRIAL_BLOCK``."""
    pp = resolve_precision(spec.precision)
    n_blocks = -(-spec.trials // TRIAL_BLOCK)
    u = _run_uniforms(trial_key(spec, scheme), 0, n_blocks,
                      jnp.arange(num_apps), draws_per_trial,
                      jnp.dtype(pp.trace))
    return np.asarray(u[:, :spec.trials])


def _gather_lanes(table, idx):
    """(A, W) per-app table at (A, T, k) indices -> (A, T, k), gathered
    per app: the table is never broadcast over the trial axis, which
    would materialize A x T x W values (tens of GB for a census pool)."""
    return jnp.take_along_axis(table[:, None, :], idx, axis=2)


def _sum_draws(x):
    """Sum over the last (draw or stratum) axis, left to right, as a
    scan of elementwise adds. An XLA reduce picks its summation order
    per program shape, backend and host vector width, so per-trial
    outcomes would differ in the last bit between chunkings, meshes and
    machines; this order is the same in every program, and the program
    does not grow with the number of draws."""
    xs = jnp.moveaxis(x, -1, 0)
    total, _ = jax.lax.scan(lambda acc, xi: (acc + xi, None), xs[0], xs[1:])
    return total


def _srs_chunk(u, truth, crit, pool, n_valid):
    """(A, Tc, n) uniforms x (A, N) value pool -> per-trial estimate,
    percent error, eq. (2) t-interval half-width and CI-covers-truth."""
    n = u.shape[2]
    with jax.named_scope("trials.select"):
        idx = jnp.minimum((u * n_valid[:, None, None]).astype(jnp.int32),
                          (n_valid - 1)[:, None, None].astype(jnp.int32))
        vals = _gather_lanes(pool, idx)
        est = _sum_draws(vals) / n
        err = 100.0 * jnp.abs(est - truth[:, None]) / truth[:, None]
    with jax.named_scope("trials.ci"):
        ss = _sum_draws((vals - est[:, :, None]) ** 2)
        v_mean = jnp.where(n > 1, ss / max(n - 1, 1), jnp.nan) / n
        half = crit[:, None] * jnp.sqrt(v_mean)
        covered = jnp.abs(est - truth[:, None]) <= half
    return est, err, half, covered


def _pairwise_sum(x, width: int):
    """Sum over the last axis as a tree of elementwise adds over
    ``width`` entries (a power of two): the axis is cut to ``width`` or
    padded with zeros to it, then halved ``log2(width)`` times. The order
    of every add depends on ``width`` alone, so the sum is the same bits
    in every program that holds the same entries, whatever else it
    holds; the caller cuts off only zeros."""
    x = x[..., :width]
    x = jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                + ((0, width - x.shape[-1]),))
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


# the float moments of ``TrialStats``, in the order ``_block_moments``
# stacks them
_MOMENTS = ("err_sum", "err_sumsq", "half_sum", "half_sumsq")


def _block_moments(err, half, valid, accum):
    """(4, A, Tc // TRIAL_BLOCK) sums of the error, its square, the
    half-width and its square over each PRNG block's valid, finite
    trials, in ``_MOMENTS`` order and the accumulator dtype (the values
    ``trial_stats_update`` sums, summed per block in a fixed order)."""
    e = jnp.where(valid & jnp.isfinite(err), err, 0).astype(accum)
    h = jnp.where(valid & jnp.isfinite(half), half, 0).astype(accum)
    x = jnp.stack([e, e * e, h, h * h])
    x = x.reshape(x.shape[:2] + (-1, TRIAL_BLOCK))
    return _pairwise_sum(x, TRIAL_BLOCK)


def _key_order_select(vals, key_order):
    """(A, T, L) stratum draws into each app's collapsed-pairs key order:
    ``vals[a, t, key_order[a, p]]`` at ``[a, t, p]``, ``key_order`` an
    (A, L) permutation of the strata.

    One vector select per stratum rather than a gather, which the TPU
    runs element by element (on a v5e, 45% of the L = 20 scan). No
    arithmetic touches a value, so the result is the gathered value bit
    for bit: subnormals, signed zeros and NaNs too. The cost grows as
    L * L per trial.
    """
    y = jnp.broadcast_to(vals[:, :, :1], vals.shape)
    for s in range(1, vals.shape[-1]):
        y = jnp.where((key_order == s)[:, None, :], vals[:, :, s:s + 1], y)
    return y


def _stratified_chunk(u, truth, crit, sorted_vals, offsets, counts,
                      weights, key_order, wsq, in_grp, has3, n_occ):
    """One unit per non-empty stratum per trial, weighted sum (the Fig 8
    estimator: empty strata contribute nothing, no renormalization) —
    plus the eq. (4) collapsed-pairs CI over occupied strata, evaluated
    lane-wise by ``sampling_tables.collapsed_pairs_grouped`` from the
    app's host-computed ``collapsed_pairs_groups`` (wsq, in_grp, has3)."""
    with jax.named_scope("trials.select"):
        pick = offsets[:, None, :] + jnp.minimum(
            (u * counts[:, None, :]).astype(jnp.int32),
            jnp.maximum(counts - 1, 0)[:, None, :].astype(jnp.int32))
        # trailing empty strata put offsets at the row width: clamp
        # explicitly (the pick is zero-weighted via `occupied` below)
        pick = jnp.minimum(pick, sorted_vals.shape[1] - 1)
        vals = _gather_lanes(sorted_vals, pick)
        occupied = (counts > 0)[:, None, :]
        est = _sum_draws(vals * weights[:, None, :] * occupied)
        err = 100.0 * jnp.abs(est - truth[:, None]) / truth[:, None]
    with jax.named_scope("trials.ci"):
        y_sorted = _key_order_select(vals, key_order)
        var, _ = sampling_tables.collapsed_pairs_grouped(
            y_sorted, tuple(g[:, None, :] for g in (wsq, in_grp, has3)),
            n_occ[:, None])
        half = crit[:, None] * jnp.sqrt(var)
        covered = jnp.abs(est - truth[:, None]) <= half
    return est, err, half, covered


@functools.lru_cache(maxsize=None)
def _streaming_program(chunk_fn, mesh, *, kb: int, n_chunks: int,
                       trials: int, draws: int, trace: str, accum: str,
                       keep: bool):
    """Build (and cache) the chunked-scan trial program for one geometry.

    The returned callable takes ``(key, chunk0, app_ids, truth, crit,
    *tables)`` — app-leading arrays except the replicated key and the
    traced scalar ``chunk0`` — and returns ``(TrialStats, ys)`` where
    ``ys`` is the per-chunk dense stack ``(n_chunks, A, chunk)`` triple
    when ``keep`` else ``None``.

    ``chunk0`` offsets the whole scan by that many chunks into the
    global PRNG-block sequence: chunk ``c`` of the scan draws the blocks
    of global chunk ``chunk0 + c``. A full run passes 0; the resumable
    driver (``repro.experiments.resumable``) replays any suffix of a
    run's chunk sequence from a checkpoint — the scan fold is
    position-based, so segment-at-a-time accumulation reproduces the
    same per-chunk outcomes bitwise.

    Geometry: each scan step evaluates one chunk of ``kb`` PRNG blocks
    (``kb * TRIAL_BLOCK`` trials). Under an ``("app", "trial")`` mesh the
    chunk's blocks split evenly across the trial axis (``kb`` is a
    multiple of the axis size), each device folds its own blocks into a
    local accumulator, and a final ``psum`` over the trial axis merges
    the totals — additive leaves make the merge exact.

    The float moments do not ride the running sums, whose order of adds
    would follow the chunking and the split: each block's moments land
    in a column of their own, the ``psum`` adds only zeros to them, and
    one ``_pairwise_sum`` over a width fixed by ``trials`` totals them.
    """
    chunk = kb * TRIAL_BLOCK
    dt = jnp.dtype(trace)
    if mesh is None:
        trial_axis, ntd = None, 1
    else:
        from ..distributed.appaxis import app_trial_axes
        _, trial_axis = app_trial_axes(mesh)
        ntd = 1 if trial_axis is None else mesh.shape[trial_axis]
    kbd = kb // ntd                 # blocks per trial-device per chunk
    tc = kbd * TRIAL_BLOCK          # trials per trial-device per chunk
    # the tree that totals the per-block moments spans every block of
    # the trial count (a power of two wide), whatever this program holds
    width = 1 << max(-(-trials // TRIAL_BLOCK) - 1, 0).bit_length()

    def prog(key, chunk0, app_ids, truth, crit, *tables):
        ti = (jax.lax.axis_index(trial_axis)
              if trial_axis is not None else 0)
        a_n = app_ids.shape[0]
        stats0 = sampling_tables.trial_stats_init(
            (a_n,), accum_dtype=np.dtype(accum), xp=jnp)
        parts0 = jnp.zeros((len(_MOMENTS), a_n, n_chunks * kb),
                           np.dtype(accum))

        def step(carry, c):
            stats, parts = carry
            with jax.named_scope("trials.draw"):
                b0 = (chunk0 + c) * kb + ti * kbd
                u = _run_uniforms(key, b0, kbd, app_ids, draws, dt)
            est, err, half, covered = chunk_fn(u, truth, crit, *tables)
            with jax.named_scope("trials.fold"):
                valid = ((b0 * TRIAL_BLOCK + jnp.arange(tc)) < trials)[None]
                parts = jax.lax.dynamic_update_slice(
                    parts, _block_moments(err, half, valid, np.dtype(accum)),
                    (0, 0, c * kb + ti * kbd))
            # its own moment sums are left unread (XLA drops them)
            stats = sampling_tables.trial_stats_update(
                stats, err, half, covered, valid)
            return (stats, parts), ((est, err, half) if keep else None)

        (stats, parts), ys = jax.lax.scan(step, (stats0, parts0),
                                          jnp.arange(n_chunks))
        if trial_axis is not None:
            with jax.named_scope("trials.merge"):
                stats, parts = jax.lax.psum((stats, parts), trial_axis)
        with jax.named_scope("trials.fold"):
            totals = _pairwise_sum(parts, width)
        stats = dataclasses.replace(stats, **dict(zip(_MOMENTS, totals)))
        return stats, ys

    if mesh is None:
        return jax.jit(prog)
    from jax.sharding import PartitionSpec as P

    from ..distributed.appaxis import app_trial_axes, make_app_trial_sharded
    app_axis, trial_axis = app_trial_axes(mesh)
    ys_spec = (P(None, app_axis, trial_axis),) * 3 if keep else None
    # the app padding stays on the devices: the host drops it as it
    # fetches the outputs (``_fetch_streaming_out``)
    return make_app_trial_sharded(
        prog, mesh, replicated=(0, 1), out_specs=(P(app_axis), ys_spec))


def _fetch_streaming_out(out, a_size: int):
    """Host copies of a streaming program's outputs, gathered from every
    device that holds a shard, without the app-axis padding: stats lead
    with the app axis, dense chunk stacks carry it second
    (``(n_chunks, A, chunk)``)."""
    stats, ys = out
    stats = jax.tree.map(lambda x: np.asarray(x)[:a_size], stats)
    if ys is not None:
        ys = tuple(np.asarray(y)[:, :a_size] for y in ys)
    return stats, ys


def _h2d_bytes(host, mesh) -> int:
    """Bytes a dispatch sends from the host to the devices: each host
    array once on one device, or, under ``mesh``, its app axis padded
    to the app-axis size and each app shard on every device of the
    trial axis that holds a copy of it."""
    arrays = [a for a in host if isinstance(a, np.ndarray)]
    if mesh is None:
        return sum(a.nbytes for a in arrays)
    from ..distributed.appaxis import app_trial_axes
    n_app = int(mesh.shape[app_trial_axes(mesh)[0]])
    copies = mesh.size // n_app
    return sum(a.nbytes // max(a.shape[0], 1) * -(-a.shape[0] // n_app)
               * n_app * copies for a in arrays)


def _chunk_blocks(spec: TrialSpec, ntd: int,
                  devices: int = 1) -> tuple[int, int]:
    """(kb, n_chunks): blocks per chunk — a multiple of the trial-axis
    size so each device owns whole blocks — and the scan length.

    The default chunk bounds one device's live memory: over a mesh of
    ``devices`` each device holds a ``1/devices`` share of a chunk's
    trial-lanes, so the default chunk is ``devices`` times one device's,
    evened out over the scan so that the last chunk pads as few blocks
    as it can. An explicit ``chunk_size`` is taken as given."""
    blocks_needed = -(-spec.trials // TRIAL_BLOCK)
    kb = -(-(spec.chunk_size or _DEFAULT_CHUNK * devices) // TRIAL_BLOCK)
    kb = min(kb, blocks_needed)
    kb = -(-kb // ntd) * ntd
    n_chunks = -(-blocks_needed // kb)
    if spec.chunk_size is None:
        even = -(-blocks_needed // n_chunks)
        kb = -(-even // ntd) * ntd
    return kb, n_chunks


def _stratum_key_counts(baseline: np.ndarray, labels: np.ndarray,
                        valid: np.ndarray, num_strata: int,
                        precision: Optional[PrecisionPolicy] = None,
                        ) -> tuple[np.ndarray, np.ndarray]:
    """(A, L) per-stratum mean-baseline-CPI ordering key (+inf for empty
    strata) AND the stratum counts, from the engine's ONE-dispatch
    stratum-summary path (the ``segment_stats`` kernel contract) — the
    counts feed ``stratum_tables`` so no second dispatch is needed."""
    from .engine import _segment_sums_counts

    sums, cnts = _segment_sums_counts(labels, valid, num_strata, baseline,
                                      precision=precision)
    key = np.where(cnts > 0, sums / np.maximum(cnts, 1.0), np.inf)
    return key, cnts


def charged_pool_fill(engine: ExperimentEngine, spec: TrialSpec, apps,
                      mesh=None, stratifiers: Optional[dict] = None
                      ) -> Optional[np.ndarray]:
    """Run the trial path's ONLY charged memo interaction for ``spec``.

    Schemes whose stratifier draws values from the phase-1 sample
    (``pool_kind == "phase1"``) pull their pool through the engine's
    charged ``MemoBank`` at the study config — paid once, hits
    thereafter. Returns the (A, n1_max) phase-1 CPI pool, or ``None``
    when no requested scheme needs one (census-pool schemes are
    analysis-only and free).

    Exposed for the serving path: when identical trial requests dedup to
    one ``run_trials`` execution, replaying this fill per duplicate (a
    pure cache hit) keeps hit/miss counters and ledger totals identical
    to running every request serially.
    """
    charged = any(
        ((stratifiers or {}).get(s)
         or sampling_plan.make_stratifier(s)).pool_kind == "phase1"
        for s in spec.schemes if s != SRS_DRAWS)
    if not charged:
        return None
    stack = engine.stack(tuple(apps))
    cfg = engine.configs[spec.config_index]
    # the (A, n1_max, F) features are gathered only on a miss
    cpi, _ = engine.memo.fill(stack.rows, stack.idx1, stack.idx1_valid,
                              (cfg,),
                              feats=lambda: stack.gather_feats(stack.idx1),
                              mesh=mesh)
    return cpi[:, 0, :]


@dataclasses.dataclass
class ResolvedStudy:
    """Everything a study's streaming programs consume besides its seed.

    ``truth`` is the (A,) census truth at the study config, ``pp`` the
    resolved ``PrecisionPolicy``, ``setups`` per scheme the tuple
    ``(chunk_fn, draws, crit, tables)`` the streaming program binds, and
    ``pool`` the charged phase-1 pool the tables were built from
    (``None`` where no scheme draws from one). The program inputs are
    copied to the devices on first use, once per program mesh, and kept
    (``device_args``).
    """

    truth: np.ndarray
    pp: PrecisionPolicy
    setups: dict
    pool: Optional[np.ndarray]
    _device: dict = dataclasses.field(default_factory=dict, repr=False)

    def _host_args(self, scheme: str) -> dict:
        """The program's ``(app_ids, truth, crit, *tables)`` for
        ``scheme`` as host arrays, by their key in the device store."""
        _, _, crit, tables = self.setups[scheme]
        host = {"app_ids": np.arange(len(self.truth), dtype=np.int32),
                "truth": self.truth.astype(self.pp.trace_dtype),
                (scheme, "crit"): crit}
        host.update(((scheme, i), t) for i, t in enumerate(tables))
        return host

    def upload_bytes(self, scheme: str, mesh) -> int:
        """Bytes ``device_args(scheme, mesh)`` sends to the devices: the
        inputs not yet resident there (``_h2d_bytes``), 0 once all are."""
        return _h2d_bytes(tuple(v for k, v in self._host_args(scheme).items()
                                if (mesh, k) not in self._device), mesh)

    def device_args(self, scheme: str, mesh) -> tuple:
        """The program's ``(app_ids, truth, crit, *tables)`` for
        ``scheme`` as device arrays in the layout the program consumes,
        each sent on its first use under ``mesh`` and kept.

        On one device each array is put as it is; under ``mesh`` its app
        axis is padded to the app-axis size and placed with the ``P(app)``
        sharding of ``make_app_trial_sharded``'s inputs, so the program
        call neither pads nor reshards it.
        """
        host = self._host_args(scheme)
        if mesh is None:
            put = jax.device_put
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..distributed.appaxis import app_trial_axes, pad_app_axis
            app_axis, _ = app_trial_axes(mesh)
            n_app = int(mesh.shape[app_axis])
            sharding = NamedSharding(mesh, P(app_axis))

            def put(x):
                return jax.device_put(pad_app_axis(np.asarray(x), n_app),
                                      sharding)
        with self.pp.x64_context():
            for k, v in host.items():
                if (mesh, k) not in self._device:
                    self._device[(mesh, k)] = put(v)
        return tuple(self._device[(mesh, k)] for k in host)


class StudyMemo:
    """An engine's resolved studies by key, the least recently used
    dropped past ``_STUDY_MEMO_SIZE``; ``hits`` and ``misses`` count the
    studies that were and were not served from it."""

    def __init__(self):
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, key) -> Optional[ResolvedStudy]:
        """The entry under ``key``, now the most recently used, or None."""
        study = self._entries.get(key)
        if study is not None:
            self._entries.move_to_end(key)
        return study

    def put(self, key, study: ResolvedStudy) -> None:
        """Keep ``study`` under ``key``, dropping the least recently used
        entry past the bound."""
        self._entries[key] = study
        self._entries.move_to_end(key)
        while len(self._entries) > _STUDY_MEMO_SIZE:
            self._entries.popitem(last=False)


_STUDY_MEMOS: "weakref.WeakKeyDictionary[ExperimentEngine, StudyMemo]" = \
    weakref.WeakKeyDictionary()


def study_memo(engine: ExperimentEngine) -> StudyMemo:
    """The ``StudyMemo`` of ``engine`` (made on first use)."""
    memo = _STUDY_MEMOS.get(engine)
    if memo is None:
        memo = _STUDY_MEMOS[engine] = StudyMemo()
    return memo


def _study_key(spec: TrialSpec, apps, mesh, strats: dict,
               pp: PrecisionPolicy):
    """What a resolved study depends on beyond its engine's build: apps,
    config, schemes, CI level, the SRS draw size where SRS runs, the
    precision, the stratifiers by value and the mesh of the charged
    fill (the build's k-means seed is always 0 here). ``None`` where a
    plug-in stratifier cannot be hashed: such a study is not kept."""
    key = (apps, 0, spec.config_index, spec.schemes, spec.confidence,
           spec.units_per_trial if SRS_DRAWS in spec.schemes else None,
           pp, tuple(strats.items()), mesh)
    try:
        hash(key)
    except TypeError:
        return None
    return key


def _same_bits(a: Optional[np.ndarray], b: Optional[np.ndarray]) -> bool:
    if a is None or b is None:
        return a is None and b is None
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


def _scheme_setup(engine: ExperimentEngine, spec: TrialSpec, apps, mesh,
                  stratifiers: Optional[dict] = None) -> ResolvedStudy:
    """The ``ResolvedStudy`` of ``spec`` over ``apps``: from the engine's
    ``StudyMemo`` where it holds the study's key, else resolved here
    (``_resolve_study``) and kept.

    Shared by ``run_trials`` and the resumable driver
    (``repro.experiments.resumable``) so an interrupted run binds
    bitwise-identical program inputs: the stratum tables, value pools
    and critical values are pure functions of the engine build and the
    key. The charged phase-1 pool fill — the trial path's ONLY charged
    work — runs in every study, hit or miss, so memo counters and ledger
    totals are those of resolving every study anew (after a restore, or
    a memo eviction of the study's config, it charges again); a kept
    study serves only while the fill's pool is bit for bit the one its
    tables were built from.

    Profiler spans: ``trials.setup`` around the whole (arg ``cached``: 1
    where the memo held the key), ``trials.pool_fill`` and, on a miss
    only, ``trials.resolve`` (the build's banks and the census pool) and
    per scheme ``trials.tables``.
    """
    apps = tuple(apps)
    pp = resolve_precision(spec.precision, engine.precision)
    strats = {s: (stratifiers or {}).get(s)
              or sampling_plan.make_stratifier(s)
              for s in spec.schemes if s != SRS_DRAWS}
    memo = study_memo(engine)
    key = _study_key(spec, apps, mesh, strats, pp)
    study = None if key is None else memo.get(key)

    def fill():
        # phase-1 CPI value pool (charged once, via the serving-shared
        # helper so request dedup can replay the hit)
        with jax.profiler.TraceAnnotation("trials.pool_fill"):
            pool = charged_pool_fill(engine, spec, apps, mesh, stratifiers)
        return None if pool is None else pool.astype(pp.trace_dtype)

    with jax.profiler.TraceAnnotation("trials.setup",
                                      cached=int(study is not None)):
        if study is not None:
            p1_pool = fill()
            if _same_bits(p1_pool, study.pool):
                memo.hits += 1
                return study
        memo.misses += 1
        # a kept study whose pool went stale has had this study's fill
        study = _resolve_study(engine, spec, apps, strats, pp,
                               fill if study is None else lambda: p1_pool)
        if key is not None:
            memo.put(key, study)
        return study


def _resolve_study(engine: ExperimentEngine, spec: TrialSpec, apps,
                   strats: dict, pp: PrecisionPolicy,
                   fill) -> ResolvedStudy:
    """Resolve on the host everything a scheme's chunk program consumes
    (``ResolvedStudy``), ``fill()`` giving the (A, n1_max) phase-1 pool
    once the build is resolved."""
    ci = spec.config_index
    l_n = engine.num_strata
    tdt = pp.trace_dtype
    with jax.profiler.TraceAnnotation("trials.resolve"):
        exps = engine.build(apps)
        stack = engine.stack(apps)
        truth = np.stack([e.truth[ci] for e in exps])

        # registry-resolved stratifications: each scheme's Stratifier
        # resolves to a StratumBank that declares its labels, weights and
        # order key — and its ``pool_kind`` declares the value-pool cost
        # semantics — no per-scheme branches below
        banks = {s: strat.resolve(exps) for s, strat in strats.items()}
        charged = {s for s, strat in strats.items()
                   if strat.pool_kind == "phase1"}
        # census CPI value pool (free)
        census, _ = stack_ragged([e.census(ci) for e in exps], dtype=tdt)

    p1_pool = fill()

    setups: dict[str, tuple] = {}
    for scheme in spec.schemes:
        with jax.profiler.TraceAnnotation("trials.tables", scheme=scheme):
            if scheme == SRS_DRAWS:
                n = spec.units_per_trial
                dfs = np.full(len(apps), float(n - 1) if n < 30 else np.inf)
                crit = critical_values(spec.confidence, dfs).astype(tdt)
                setups[scheme] = (_srs_chunk, n, crit,
                                  (census, stack.n_regions))
                continue
            bank = banks[scheme]
            labels, lv = bank.labels, bank.valid
            weights = bank.weights
            if scheme in charged:                 # phase-1 pool, paid once
                pool = p1_pool
            elif bank.pool is None:               # census-indexed labels
                pool = census
            else:                                 # census values at pool idx
                pool = np.take_along_axis(census, bank.pool, axis=1)
            baseline = bank.baseline.astype(tdt)
            # ONE stratum-summary dispatch serves the collapsed-pairs
            # ordering key AND the gather-table counts
            key, countsf = _stratum_key_counts(baseline, labels, lv, l_n,
                                               precision=pp)
            order, offsets, counts = stratum_tables(labels, lv, l_n,
                                                    counts=countsf)
            sorted_vals = np.take_along_axis(pool, order, axis=1)
            # collapsed-pairs CI geometry: occupied strata first, in
            # baseline-CPI key order (static per app)
            key_order = np.argsort(key, axis=1, kind="stable")
            w_sorted = np.take_along_axis(weights, key_order, axis=1)
            n_occ = (counts > 0).sum(axis=1)
            groups = sampling_tables.collapsed_pairs_groups(
                w_sorted.astype(tdt), n_occ, num_strata=l_n)
            dfs = np.maximum(n_occ - n_occ // 2, 1).astype(np.float64)
            crit = critical_values(spec.confidence, dfs).astype(tdt)
            setups[scheme] = (_stratified_chunk, l_n, crit,
                              (sorted_vals, offsets.astype(np.int32),
                               counts.astype(np.int32), weights.astype(tdt),
                               key_order.astype(np.int32)) + groups
                              + (n_occ.astype(np.int32),))
    return ResolvedStudy(truth=truth, pp=pp, setups=setups, pool=p1_pool)


def run_trials(engine: ExperimentEngine, spec: TrialSpec = TrialSpec(),
               apps: Optional[Sequence[str]] = None,
               mesh=None, stratifiers: Optional[dict] = None) -> TrialResult:
    """Monte-Carlo selection trials, one streaming program per scheme.

    No host-side per-app or per-trial loops: each scheme is one chunked
    ``lax.scan`` dispatch (optionally ``shard_map``-ped over an
    ``("app",)`` or ``("app", "trial")`` mesh) that folds every chunk of
    trials into the additive ``TrialStats`` accumulator — including the
    per-trial CI half-width and its empirical coverage of the census
    truth (see ``TrialResult``). Memory is bounded by one chunk at any
    trial count; results are invariant to the chunking and the mesh.

    ``stratifiers`` optionally maps scheme names to configured
    ``Stratifier`` *instances* (``run_sweep`` passes its plan's), so a
    parameterized plug-in studies the same stratification its sweep
    used; unmapped schemes are built from the registry with defaults.
    """
    from ..launch.mesh import mesh_tag

    apps = tuple(apps or APP_NAMES)
    mesh = engine.mesh if mesh is None else mesh
    # profiler spans: ``trials.run`` around the study (with the mesh's
    # layout), then per scheme ``trials.dispatch`` (with the bytes it
    # sends to all devices) and ``trials.fetch`` (its results gathered
    # to the host)
    with jax.profiler.TraceAnnotation("trials.run", seed=spec.seed,
                                      trials=spec.trials,
                                      mesh=mesh_tag(mesh)):
        if mesh is None:
            ntd = 1
        else:
            from ..distributed.appaxis import app_trial_axes
            _, trial_axis = app_trial_axes(mesh)
            ntd = 1 if trial_axis is None else mesh.shape[trial_axis]
        kb, n_chunks = _chunk_blocks(
            spec, ntd, 1 if mesh is None else mesh.size)
        keep = (spec.keep_trials if spec.keep_trials is not None
                else spec.trials <= _KEEP_TRIALS_MAX)
        study = _scheme_setup(engine, spec, apps, mesh, stratifiers)
        pp = study.pp

        stats: dict[str, sampling_tables.TrialStats] = {}
        estimates: dict[str, np.ndarray] = {}
        errors: dict[str, np.ndarray] = {}
        halves: dict[str, np.ndarray] = {}
        for scheme in spec.schemes:
            chunk_fn, draws, _, _ = study.setups[scheme]
            program = _streaming_program(
                chunk_fn, mesh, kb=kb, n_chunks=n_chunks, trials=spec.trials,
                draws=draws, trace=pp.trace, accum=pp.accum, keep=keep)
            with pp.x64_context():
                key, chunk0 = trial_key(spec, scheme), np.int32(0)
                # the inputs not yet on the devices, then the key and
                # chunk0 to every device
                sent = study.upload_bytes(scheme, mesh) + (
                    key.nbytes + chunk0.nbytes) * (
                        1 if mesh is None else mesh.size)
                with jax.profiler.TraceAnnotation(
                        "trials.dispatch", scheme=scheme, h2d_bytes=sent):
                    out = program(key, chunk0,
                                  *study.device_args(scheme, mesh))
            with jax.profiler.TraceAnnotation("trials.fetch", scheme=scheme):
                stats[scheme], ys = _fetch_streaming_out(out, len(apps))
                if keep:
                    # (n_chunks, A, chunk) stacks -> (A, T) trial-major views
                    est, err, half = (
                        y.transpose(1, 0, 2).reshape(len(apps), -1)
                        [:, :spec.trials] for y in ys)
                    estimates[scheme] = est
                    errors[scheme] = err
                    halves[scheme] = half
        return TrialResult(apps=apps, spec=spec, stats=stats,
                           estimates=estimates, errors=errors,
                           half_widths=halves)
