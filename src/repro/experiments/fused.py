"""The fused sweep megaprogram: selection → memo fill → estimates, ONE
dispatch.

The staged sweep path runs four host-synchronized stages per sweep —
``plan_selection_bank`` (selection), ``MemoBank.fill`` (miss-only CPI
fill), ``StratumTables`` construction, and the estimator's jitted
reduction — and at paper scale the launch overhead between them swamps
the device work. This module fuses the whole pipeline into one jitted
program per ``SamplingPlan`` shape:

* the selection context is built **in-trace** (``build_selection_context``
  is namespace-agnostic; the stratum summary routes through the same
  ``segment_stats`` kernel contract the staged path uses),
* the policy's picks drive an in-trace miss-only memo update — the memo
  mask/value blocks enter as **donated buffers** (``donate_argnums``) so
  the update is in-place where the backend supports it,
* the selected-unit CPI gathers straight out of the updated block and
  flows into ``Estimator.estimate_stage`` (the same traceable stage the
  staged jitted program calls), so the two paths cannot drift.

Only O(apps × configs × strata) selected-unit results come home with
the estimates — the updated (A, C, N) blocks stay device-side, aliased
to the donated inputs — and are folded back into the host ``MemoBank``
via ``absorb_selected``; ledger charge totals are bitwise identical to
the staged path's ``fill``. Random selection policies pre-draw their
uniforms on the host with the staged rng sequence (``uses_uniforms``),
so fused picks equal staged picks exactly.

Programs are cached per ``(plan, precision policy, mesh)``; under an
``("app",)`` mesh the program is ``shard_map``-ped over the app axis
with the config matrix replicated, and padding rows are trimmed before
any memo write-back so sharded accounting matches single-device.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.precision import PrecisionPolicy, resolve_precision
from ..core.sampling import plan as sampling_plan
from ..simcpu.perfmodel import _cpi_bank_fn, config_matrix

__all__ = ["fused_sweep_program", "run_fused_sweep"]

# positions of the donated memo blocks in the traced signature below
_DONATE = (11, 12)
# position of the replicated config matrix under an app mesh
_REPLICATED = frozenset({9})

# device-resident uploads of per-sweep-constant host arrays, keyed by
# object identity + trace dtype (the held reference keeps the id valid).
# ``stratifier.resolve`` and ``engine.stack`` are cached, so repeated
# sweeps see the same host objects and skip the host->device copies that
# otherwise dominate the warm driver time.
_DEV_CACHE: dict = {}


def _dev_bank_arrays(bank, dt, x64: bool):
    """The StratumBank's seven traced inputs, uploaded once per bank."""
    key = (id(bank), np.dtype(dt).name, x64)
    hit = _DEV_CACHE.get(key)
    if hit is not None and hit[0] is bank:
        return hit[1]
    arrs = (jnp.asarray(bank.labels), jnp.asarray(bank.valid),
            jnp.asarray(bank.weights, dt), jnp.asarray(bank.baseline),
            None if bank.pool is None else jnp.asarray(bank.pool),
            None if bank.feats is None else jnp.asarray(bank.feats),
            None if bank.centroids is None else jnp.asarray(bank.centroids))
    _DEV_CACHE[key] = (bank, arrs)
    return arrs


def _dev_feats(feats, x64: bool):
    """The stacked population features, uploaded once per stack."""
    key = (id(feats), "feats", x64)
    hit = _DEV_CACHE.get(key)
    if hit is not None and hit[0] is feats:
        return hit[1]
    arr = jnp.asarray(feats)
    _DEV_CACHE[key] = (feats, arr)
    return arr


# device-resident memo blocks, chained through donation: each fused
# sweep CONSUMES the previous sweep's output blocks (donated in, updated
# in place, emitted as outputs) so warm re-sweeps skip the host block
# checkout + upload entirely. One entry per MemoBank, keyed by the
# bank's ``version`` counter — any host-side table mutation (a staged
# ``fill``, a ``merge``, growth, or an explicit ``touch()``) invalidates
# it and the next sweep re-checks out via ``donation_block``.
_BLOCK_CACHE: dict = {}


def _checkout_blocks(memo, rows, cfgs):
    """(mask, cpi, cols) for the dispatch: cached device blocks when the
    bank is unchanged since the last fused sweep, else a fresh host
    checkout. The cache entry is REMOVED here — the blocks are about to
    be donated — and re-stamped by the caller after absorb."""
    cols = memo.cols_for(cfgs)
    rows_key = tuple(np.asarray(rows, np.int64).tolist())
    cols_key = tuple(cols.tolist())
    hit = _BLOCK_CACHE.get(id(memo))
    if (hit is not None and hit[0] is memo and hit[1] == rows_key
            and hit[2] == cols_key and hit[3] == memo.version):
        del _BLOCK_CACHE[id(memo)]
        return hit[4], hit[5], cols, rows_key, cols_key
    mask_blk, cpi_blk, cols = memo.donation_block(rows, cfgs)
    return mask_blk, cpi_blk, cols, rows_key, cols_key


@functools.lru_cache(maxsize=None)
def _dev_config_matrix(cfgs):
    """float32 device config matrix, built once per config tuple.

    Pinned to float32 OUTSIDE any x64 context: the perf model is float32
    by contract, and an f64 matrix would promote the in-trace CPI
    evaluation away from the staged ``cpi_bank`` dispatch's ulps.
    """
    mat = config_matrix(cfgs)
    return jnp.asarray(mat, jnp.float32)  # jaxlint: disable=JL003


def _traced_summarize(labels, valid, num_strata, values, precision=None):
    """In-trace mirror of ``engine._segment_sums_counts``: same
    ``segment_stats`` kernel contract, same ``PrecisionPolicy`` dtypes,
    but traceable (no eager dispatch, no host round-trip)."""
    from ..kernels.segment_stats.ops import segment_stats

    pp = resolve_precision(precision)
    lab = jnp.where(valid, labels, -1).astype(jnp.int32)
    sums, _, counts = segment_stats(jnp.asarray(values, pp.trace_dtype),
                                    lab, num_strata, precision=pp)
    return (sums[..., 0].astype(pp.host_dtype),
            counts.astype(pp.host_dtype))


def _make_traced(plan: sampling_plan.SamplingPlan):
    """The full selection→fill→estimate trace for one plan.

    Positional signature (optional arrays pass ``None`` — a static
    empty-pytree branch under ``jit``): ``labels, valid_units, weights,
    baseline, pool, feats_sel, cents, uniforms, feats_pop, cm, truth,
    mask_blk, cpi_blk`` with ``mask_blk``/``cpi_blk`` donated.
    """

    def traced(labels, valid_units, weights, baseline, pool, feats_sel,
               cents, uniforms, feats_pop, cm, truth, mask_blk, cpi_blk):
        bank = sampling_plan.StratumBank(
            labels=labels, valid=valid_units, weights=weights,
            baseline=baseline, feats=feats_sel, centroids=cents, pool=pool)
        ctx = sampling_plan.build_selection_context(
            bank, summarize=_traced_summarize, uniforms=uniforms)
        local = plan.policy(ctx)
        # barrier: without it XLA may fuse the fill/estimator stages
        # backward into the policy's distance/argmin subgraph, changing
        # its rounding (FMA contraction) and flipping near-tie picks vs
        # the staged eager selection — picks must be program-shape
        # independent
        local, counts = jax.lax.optimization_barrier((local, ctx.counts))
        valid_sel = counts > 0
        picks = local if pool is None \
            else jnp.take_along_axis(pool, local, axis=1)
        picks = jnp.where(valid_sel, picks, 0)

        a_n, n_strata = picks.shape
        c_n = cm.shape[0]
        n_memo = mask_blk.shape[-1]
        # miss-only fill, mirroring MemoBank.fill's dense-request
        # accounting: duplicate picks dedup through the request scatter,
        # invalid picks scatter to the out-of-range sentinel and drop
        safe = jnp.where(valid_sel, picks, n_memo)
        req = jnp.zeros((a_n, n_memo), bool).at[
            jnp.arange(a_n)[:, None], safe].set(True, mode="drop")
        miss = req[:, None, :] & ~mask_blk
        n_miss = miss.sum(axis=2)

        gfeats = jnp.take_along_axis(
            feats_pop, jnp.minimum(picks, feats_pop.shape[1] - 1)[:, :, None],
            axis=1)
        computed = _cpi_bank_fn(gfeats, cm)            # (A, C, L) float32
        # everything below stays O(A*C*L): gather the stored values and
        # miss flags at the picked columns, select computed-vs-stored,
        # and write the selected column back into the DONATED block
        # in-place (hits rewrite their stored value — a no-op — and
        # invalid picks hit the out-of-range sentinel and drop)
        picks_b = jnp.broadcast_to(picks[:, None, :], (a_n, c_n, n_strata))
        stored = jnp.take_along_axis(cpi_blk, picks_b, axis=2)
        miss_sel = jnp.take_along_axis(miss, picks_b, axis=2)
        cpi_sel = jnp.where(miss_sel, computed, stored)
        new_cpi = cpi_blk.at[
            jnp.arange(a_n)[:, None, None],
            jnp.arange(c_n)[None, :, None],
            jnp.broadcast_to(safe[:, None, :], (a_n, c_n, n_strata))].set(
                cpi_sel, mode="drop")
        new_mask = mask_blk | miss

        est, err = plan.estimator.estimate_stage(
            cpi_sel.astype(truth.dtype), valid_sel,
            weights.astype(truth.dtype), truth)
        return (est, err, valid_sel, picks, n_miss, miss_sel, cpi_sel,
                new_mask, new_cpi)

    return traced


@functools.lru_cache(maxsize=None)
def fused_sweep_program(plan: sampling_plan.SamplingPlan,
                        precision: PrecisionPolicy, mesh=None):
    """The jitted (optionally app-sharded) megaprogram for one plan.

    Cached per ``(plan, precision, mesh)`` — the plan fixes the traced
    selection/estimator code, the policy fixes the trace dtypes, and
    ``jit`` itself re-specializes per input shape, so one cache entry
    serves every sweep with the same plan. The memo mask/value blocks
    (last two arguments) are donated.
    """
    traced = _make_traced(plan)
    if mesh is None:
        return jax.jit(traced, donate_argnums=_DONATE)

    from ..distributed.appaxis import app_trial_axes, pad_app_axis
    from jax.sharding import PartitionSpec as P

    axis, _ = app_trial_axes(mesh)
    n_dev = int(mesh.shape[axis])
    in_specs = tuple(P() if i in _REPLICATED else P(axis)
                     for i in range(13))
    prog = jax.jit(jax.shard_map(traced, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(axis), check_vma=False),
                   donate_argnums=_DONATE)

    def call(*args):
        a_size = np.shape(args[0])[0]
        padded = tuple(
            a if (i in _REPLICATED or a is None) else pad_app_axis(a, n_dev)
            for i, a in enumerate(args))
        out = prog(*padded)
        # trim padding BEFORE any write-back: duplicate edge rows never
        # reach the host MemoBank, so sharded accounting == single-device
        return jax.tree.map(lambda o: o[:a_size], out)

    return call


def run_fused_sweep(engine, spec, exps, stack, cfgs, truth, mesh=None):
    """Drive one fused sweep: resolve the plan's ``StratumBank``, check
    out the memo blocks under the donation contract, dispatch the
    megaprogram once, and absorb the selected-unit results + miss counts
    back into the host ``MemoBank`` (ledger totals
    bitwise-staged-identical).

    Returns ``(ests, errs, valid, weights)`` — percent errors included,
    all host numpy — and records the ``fused=True`` dispatch marker
    (``sampling_plan.last_sweep_dispatch``).
    """
    plan = spec.plan
    bank = plan.stratifier.resolve(exps)
    a_n, n_strata = bank.weights.shape
    pp = resolve_precision(engine.precision, PrecisionPolicy.host_parity())
    dt = pp.trace_dtype
    uniforms = None
    if plan.policy.uses_uniforms:
        # the staged policy's exact rng sequence (first draw from the
        # selection seed), so fused picks == staged picks bit-for-bit
        uniforms = np.random.default_rng(spec.selection_seed).random(
            (a_n, n_strata))
    if mesh is None:
        mask_blk, cpi_blk, cols, rows_key, cols_key = _checkout_blocks(
            engine.memo, stack.rows, cfgs)
    else:
        # sharded runs keep the per-sweep checkout: their outputs are
        # trimmed/padded views whose chaining isn't worth the bookkeeping
        mask_blk, cpi_blk, cols = engine.memo.donation_block(
            stack.rows, cfgs)
    cm = _dev_config_matrix(cfgs)
    prog = fused_sweep_program(plan, pp, mesh)
    with pp.x64_context():
        mask_dev = jnp.asarray(mask_blk)
        cpi_dev = jnp.asarray(cpi_blk)
        args = _dev_bank_arrays(bank, dt, pp.needs_x64) + (
            None if uniforms is None else jnp.asarray(uniforms, dt),
            _dev_feats(stack.feats, pp.needs_x64), cm,
            jnp.asarray(truth, dt), mask_dev, cpi_dev)
        with warnings.catch_warnings():
            # CPU XLA may decline donation; correctness is unaffected
            # (the donated flag in the dispatch marker records it)
            warnings.filterwarnings(
                "ignore", message=".*donated buffers were not usable.*")
            (est, err, valid_sel, picks, n_miss, miss_sel, cpi_sel,
             _new_mask, _new_cpi) = prog(*args)
        # only the O(A*C*L) selected-unit results come home; the updated
        # (A, C, N) block outputs stay device-side (aliased to the
        # donated inputs) and are dropped — the host MemoBank mirror
        # advances from the selected results below
        est, err = np.asarray(est), np.asarray(err)
        valid = np.asarray(valid_sel)
        picks, n_miss = np.asarray(picks), np.asarray(n_miss)
        miss_sel, cpi_sel = np.asarray(miss_sel), np.asarray(cpi_sel)
    donated = bool(mask_dev.is_deleted() and cpi_dev.is_deleted())
    engine.memo.absorb_selected(stack.rows, cols, picks, miss_sel, cpi_sel,
                                n_miss,
                                requested=valid.sum(axis=1) * len(cfgs))
    if mesh is None:
        # the program's output blocks hold exactly the post-absorb table
        # content: stamp them with the post-absorb version so the next
        # fused sweep over the same rows/configs skips the checkout
        _BLOCK_CACHE[id(engine.memo)] = (
            engine.memo, rows_key, cols_key, engine.memo.version,
            _new_mask, _new_cpi)
    sampling_plan._record_sweep_dispatch(
        batch_shape=(a_n, len(cfgs)), num_strata=n_strata,
        x64=pp.needs_x64, backend=jax.default_backend(),
        fused=True, donated=donated)
    return est, err, valid, np.asarray(bank.weights)
