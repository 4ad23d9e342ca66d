"""Analytical out-of-order core performance model (vectorized JAX).

Maps (region intrinsic features × UarchConfig) -> CPI plus the 38 Table III
counters. This is the TPU-idiomatic stand-in for the cycle-accurate
simulator: inherently-serial discrete-event simulation does not transfer to
TPU, but the *population evaluation* — what the sampling methodology needs —
is embarrassingly parallel and lives as one fused vector program.

Model structure (classic top-down decomposition):
  CPI = 1/ipc_core                                 (retire/issue/ILP bound)
      + branch-flush stalls                        (TAGE-capacity dependent)
      + frontend miss stalls (icache/iTLB)
      + data-side miss stalls / effective MLP      (cache + prefetch + ROB)

All cache miss rates follow power-law size scaling  mpki(size) =
mpki_ref * (ref/size)^alpha; prefetchers convert a coverage fraction of
next-level misses into L2-latency hits; a larger ROB raises the usable MLP
of the miss stream. Deterministic per (region, config): repeated simulation
of the same region is bit-identical, like re-running a deterministic
simulator checkpoint.
"""

from __future__ import annotations

# jaxlint: disable-file=JL003 — the perf model is float32 BY CONTRACT
# (deterministic bit-identical CPI across dispatch paths keys the
# MemoBank); its dtypes are the contract itself, not policy leaks.

import functools
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.features import RFV_METRICS
from .uarch import UarchConfig
from .workload import NUM_FEATURES

NUM_CONFIG_FIELDS = 14

_F = {name: i for i, name in enumerate(
    ("ilp", "br_pki", "br_mpr", "br_predict", "cond_frac", "ic_mpki",
     "ic_alpha", "itlb_mpki", "l1d_apki", "load_frac", "l1d_mpki",
     "l1d_alpha", "l2_mpki", "l2_alpha", "l3_mpki", "l3_alpha", "wb_frac",
     "sms_cov", "bo_cov", "mlp", "rob_sens"))}


def _config_vector(cfg: UarchConfig) -> jnp.ndarray:
    return jnp.asarray([
        cfg.issue_width, cfg.retire_width, cfg.rob_size,
        cfg.icache_kb, cfg.dcache_kb, cfg.l2_kb, cfg.l3_mb,
        cfg.l2_hit_lat, cfg.l3_hit_latency_cyc, cfg.mem_latency_cyc,
        1.0 if cfg.sms_pf else 0.0, 1.0 if cfg.bo_pf else 0.0,
        cfg.tage_capacity_ratio, cfg.fetch_width,
    ], jnp.float32)


@functools.partial(jax.jit, static_argnames=())
def _evaluate(features: jnp.ndarray, cv: jnp.ndarray) -> dict[str, jnp.ndarray]:
    f = lambda name: features[:, _F[name]]
    (issue_w, retire_w, rob, ic_kb, dc_kb, l2_kb, l3_mb, l2_lat, l3_lat,
     mem_lat, sms_on, bo_on, tage_ratio, fetch_w) = [cv[i] for i in range(14)]

    # --- core-bound term ----------------------------------------------------
    ilp_eff = f("ilp") * (1.0 + 0.08 * f("rob_sens") * (rob / 128.0 - 1.0))
    ipc_core = jnp.minimum(jnp.minimum(ilp_eff, retire_w), issue_w)
    base_cpi = 1.0 / ipc_core

    # --- branch mispredictions ----------------------------------------------
    mpr_eff = f("br_mpr") * tage_ratio ** (-f("br_predict"))
    br_mpki = f("br_pki") * jnp.clip(mpr_eff, 0.0, 0.15)
    flush_penalty = 12.0 + rob / 32.0
    stall_br = br_mpki / 1000.0 * flush_penalty

    # --- frontend misses ----------------------------------------------------
    ic_mpki = f("ic_mpki") * (32.0 / ic_kb) ** f("ic_alpha")
    stall_ic = ic_mpki / 1000.0 * l2_lat * 0.7     # partly hidden by BTB/queue
    itlb_mpki = f("itlb_mpki")
    stall_itlb = itlb_mpki / 1000.0 * 20.0

    # --- data-side cache hierarchy -------------------------------------------
    l1d_mpki = f("l1d_mpki") * (32.0 / dc_kb) ** f("l1d_alpha")
    l2_mpki = jnp.minimum(l1d_mpki, f("l2_mpki") * (512.0 / l2_kb) ** f("l2_alpha"))
    l3_mpki = jnp.minimum(l2_mpki, f("l3_mpki") * (2.0 / l3_mb) ** f("l3_alpha"))

    l2_served = jnp.maximum(l1d_mpki - l2_mpki, 0.0)   # hit in L2
    l3_served = jnp.maximum(l2_mpki - l3_mpki, 0.0)    # hit in L3
    mem_served = l3_mpki                               # go to DRAM

    cov_sms = f("sms_cov") * sms_on                    # covers DRAM misses
    cov_bo = f("bo_cov") * bo_on                       # covers L3-hit misses
    mem_cost = mem_served * ((1.0 - cov_sms) * mem_lat + cov_sms * l2_lat)
    l3_cost = l3_served * ((1.0 - cov_bo) * l3_lat + cov_bo * l2_lat)
    l2_cost = l2_served * l2_lat * 0.5                 # mostly OoO-hidden

    rob_cap = rob / 32.0
    mlp = f("mlp")
    mlp_eff = 1.0 + (mlp - 1.0) * jnp.clip(rob_cap / mlp, 0.0, 1.0)
    stall_mem = (mem_cost + l3_cost + l2_cost) / 1000.0 / mlp_eff

    cpi = base_cpi + stall_br + stall_ic + stall_itlb + stall_mem

    # --- Table III counters (rates per kilo-instruction) ---------------------
    cond = f("cond_frac")
    l1d_total = l1d_mpki
    demand_l3_misses = mem_served * (1.0 - cov_sms)
    demand_l2_misses = l3_served * (1.0 - cov_bo) + mem_served
    out: dict[str, jnp.ndarray] = {
        "cpi": cpi,
        "branch_mispredicts": br_mpki,
        "cond_branch_mispredicts": br_mpki * cond,
        "target_branch_mispredicts": br_mpki * (1.0 - cond),
        "icache_misses": ic_mpki,
        "itlb_misses": itlb_mpki,
        "l1d_access": f("l1d_apki"),
        "l1d_load_miss": l1d_total * f("load_frac"),
        "l1d_store_miss": l1d_total * (1.0 - f("load_frac")),
        "l1d_total_miss": l1d_total,
        "l1d_writeback": l1d_total * f("wb_frac"),
        "l2_misses": demand_l2_misses,
        "l2_load_misses": demand_l2_misses * f("load_frac"),
        "l2_writebacks": l2_mpki * f("wb_frac"),
        "l3_read_accesses": demand_l2_misses,
        "l3_write_accesses": l2_mpki * f("wb_frac"),
        "l3_misses": demand_l3_misses,
    }

    # --- 21 top-down stall bins (cycles per instruction, x1000 => per ki) ----
    dram_stall = mem_cost / 1000.0 / mlp_eff
    l3_stall = l3_cost / 1000.0 / mlp_eff
    l2_stall = l2_cost / 1000.0 / mlp_eff
    fe_lat = stall_ic + stall_itlb
    fe_bw = jnp.maximum(0.0, (1.0 / fetch_w) - (1.0 / ipc_core)) + 0.01 * base_cpi
    rob_press = jnp.clip(mlp - rob_cap, 0.0, None) / (mlp + 1.0)
    bins = [
        stall_ic,                          # 00 frontend icache
        stall_itlb,                        # 01 frontend itlb
        stall_br * 0.4,                    # 02 branch resteer
        fe_bw,                             # 03 frontend bandwidth
        stall_br * 0.6,                    # 04 bad speculation
        l2_stall,                          # 05 backend mem L2-bound
        l3_stall,                          # 06 backend mem L3-bound
        dram_stall,                        # 07 backend mem DRAM-bound
        l1d_total * f("wb_frac") / 1000.0 * 2.0,  # 08 store-bound
        rob_press * stall_mem,             # 09 ROB-full
        base_cpi * 0.10,                   # 10 RS-full proxy
        base_cpi * 0.05,                   # 11 phys-reg pressure
    ]
    # 12..20: finer-grained sub-bins of the real stall terms (a real top-down
    # profiler splits the same cycles into more buckets, it does not invent
    # orthogonal noise dimensions).
    mixes = [
        dram_stall * 0.30 + l3_stall * 0.10,       # 12 mem latency-bound
        dram_stall * 0.10 + l2_stall * 0.40,       # 13 mem bandwidth proxy
        stall_mem * rob_press * 0.50,              # 14 ROB-blocked mem
        stall_br * 0.25 + fe_bw * 0.30,            # 15 resteer bandwidth
        stall_ic * 0.50 + stall_itlb * 0.20,       # 16 fetch latency split
        base_cpi * 0.08 + stall_br * 0.05,         # 17 dispatch stalls
        l2_stall * 0.20 + l3_stall * 0.30,         # 18 L2/L3 queueing
        stall_mem * 0.15,                          # 19 store/forwarding
        base_cpi * 0.04 + stall_mem * 0.02,        # 20 misc core
    ]
    bins.extend(mixes)
    for i, b in enumerate(bins):
        out[f"stall_bin_{i:02d}"] = b
    return out


class _Evaluator:
    """Caches jitted evaluation per config vector."""

    def __init__(self):
        self._feat_cache: dict[int, jnp.ndarray] = {}

    def __call__(self, features: np.ndarray, cfg: UarchConfig,
                 indices=None) -> dict[str, np.ndarray]:
        x = jnp.asarray(features, jnp.float32)
        if indices is not None:
            x = x[jnp.asarray(indices)]
        stats = _evaluate(x, _config_vector(cfg))
        return {k: np.asarray(v) for k, v in stats.items()}


evaluate_regions = _Evaluator()


def config_matrix(cfgs: Sequence[UarchConfig]) -> jnp.ndarray:
    """Stack config vectors into a (C, 14) matrix for batched evaluation."""
    if not cfgs:
        raise ValueError("need at least one config")
    return jnp.stack([_config_vector(c) for c in cfgs])


# One XLA program for all configs: vmap the fused model over the config axis.
_evaluate_batch = jax.jit(jax.vmap(_evaluate, in_axes=(None, 0)))
# cpi-only variant: XLA dead-code-eliminates the 37 unused counters, so
# census-scale sweeps don't materialize (C, N, 38) intermediates.
_cpi_batch = jax.jit(
    lambda x, cm: jax.vmap(_evaluate, in_axes=(None, 0))(x, cm)["cpi"])


def evaluate_regions_batch(features: np.ndarray, cfgs: Sequence[UarchConfig],
                           indices=None) -> dict[str, np.ndarray]:
    """Evaluate many configs in one batched dispatch.

    Returns the same metric dict as ``evaluate_regions`` but with every
    value shaped ``(len(cfgs), n_regions)``; row ``i`` matches
    ``evaluate_regions(features, cfgs[i], indices)`` to float32 precision.
    """
    x = jnp.asarray(features, jnp.float32)
    if indices is not None:
        x = x[jnp.asarray(indices)]
    stats = _evaluate_batch(x, config_matrix(cfgs))
    return {k: np.asarray(v) for k, v in stats.items()}


def cpi_batch(features: np.ndarray, cfgs: Sequence[UarchConfig],
              indices=None) -> np.ndarray:
    """(C, n) CPI matrix across configs in one batched dispatch."""
    x = jnp.asarray(features, jnp.float32)
    if indices is not None:
        x = x[jnp.asarray(indices)]
    return np.asarray(_cpi_batch(x, config_matrix(cfgs)))


def cpi_only(features: np.ndarray, cfg: UarchConfig, indices=None) -> np.ndarray:
    return evaluate_regions(features, cfg, indices)["cpi"]


# --- app-axis (bank) entry points ------------------------------------------
# The application axis of a PopulationBank is plain data parallelism: the
# same fused model vmapped over the leading (A, ...) axis. These programs
# are what the experiment engine shards over an ("app",) mesh — per-app
# lanes never communicate, so sharded and single-device results agree.
def _cpi_bank_fn(x: jnp.ndarray, cm: jnp.ndarray) -> jnp.ndarray:
    """(A, N, F) features x (C, 14) configs -> (A, C, N) CPI."""
    per_app = lambda xa: jax.vmap(_evaluate, in_axes=(None, 0))(xa, cm)["cpi"]
    return jax.vmap(per_app)(x)


def _rfv_bank_fn(x: jnp.ndarray, cv: jnp.ndarray
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(A, N, F) features x one config vector -> ((A, N) cpi, (A, N, 38) rfv)."""
    stats = jax.vmap(lambda xa: _evaluate(xa, cv))(x)
    rfv = jnp.stack([stats[m] for m in RFV_METRICS], axis=-1)
    return stats["cpi"], rfv


_cpi_bank_jit = jax.jit(_cpi_bank_fn)
# region-axis tile of cpi_bank dispatches (a multiple of any host's
# vector width and of the TPU's 128 lanes)
_REGION_TILE = 128
_rfv_bank_jit = jax.jit(_rfv_bank_fn)


# apps per dispatch, and per device of an app mesh, at the least: XLA's
# CPU backend folds away an app axis of one and compiles other code for
# it, whose CPI came out with other last bits than the same app's in a
# dispatch over several, so a census depended on how the apps were
# split over devices
_MIN_APPS = 2


def _sharded(fn, mesh):
    from ..distributed.appaxis import app_sharded_cached
    return app_sharded_cached(fn, mesh, (1,))


def _pin_apps(x: jnp.ndarray, mesh) -> jnp.ndarray:
    """``x`` with its app axis padded by edge replication, so that the
    dispatch, or each device's share of it under ``mesh``, holds at least
    ``_MIN_APPS`` apps: the same code then computes every app's rows
    whatever the app count and the mesh (callers drop the padding)."""
    n_dev = 1
    if mesh is not None:
        from ..distributed.appaxis import app_trial_axes
        n_dev = int(mesh.shape[app_trial_axes(mesh)[0]])
    pad = _MIN_APPS * n_dev - x.shape[0]
    if pad <= 0:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1), mode="edge")


def _as_config_matrix(cfgs) -> jnp.ndarray:
    return cfgs if hasattr(cfgs, "ndim") else config_matrix(cfgs)


def cpi_bank(features, cfgs, *, mesh=None) -> np.ndarray:
    """(A, C, N) CPI matrix for stacked app features, one batched dispatch.

    ``features``: (A, N, F) stacked (possibly padded) app feature arrays;
    ``cfgs``: a config sequence or a prebuilt (C, 14) matrix. With ``mesh``
    (a 1-D ``("app",)`` mesh, or the app axis of an ``("app", "trial")``
    mesh) the app axis runs device-parallel with results bitwise equal to
    the single-device path (``_pin_apps``).
    """
    x = jnp.asarray(features, jnp.float32)
    a, n = x.shape[:2]
    # pad the region axis to whole tiles so every region is computed by
    # the same code in every batch: on a CPU host the ragged tail of a
    # row came out with other last bits, so a memo fill's CPI depended
    # on which apps and picks shared its dispatch
    x = jnp.pad(_pin_apps(x, mesh),
                ((0, 0), (0, -n % _REGION_TILE), (0, 0)), mode="edge")
    cm = _as_config_matrix(cfgs)
    fn = _cpi_bank_jit if mesh is None else _sharded(_cpi_bank_fn, mesh)
    return np.asarray(fn(x, cm))[:a, :, :n]


def rfv_bank(features, cfg: UarchConfig, *, mesh=None
             ) -> tuple[np.ndarray, np.ndarray]:
    """Stacked phase-1 measurement: (A, N) CPI + (A, N, 38) RFV matrix."""
    x = jnp.asarray(features, jnp.float32)
    a = x.shape[0]
    cv = _config_vector(cfg)
    fn = _rfv_bank_jit if mesh is None else _sharded(_rfv_bank_fn, mesh)
    cpi, rfv = fn(_pin_apps(x, mesh), cv)
    return np.asarray(cpi)[:a], np.asarray(rfv)[:a]


def stats_matrix(stats: Mapping[str, np.ndarray]) -> np.ndarray:
    """Order the stats dict into the canonical 38-column RFV matrix."""
    return np.stack([np.asarray(stats[m]) for m in RFV_METRICS], axis=1)


assert NUM_FEATURES == len(_F)


@functools.partial(jax.jit, static_argnames=())
def _evaluate_approx(features: jnp.ndarray, cv: jnp.ndarray) -> dict:
    """Deliberately degraded fast model (paper §VI.C 'cheaper
    characterization with a faster simulator'): two-term CPI (core +
    unoverlapped memory), no branch/frontend modeling, no prefetchers.
    ~half the metrics, systematically biased — only its *correlation* with
    the accurate model matters for stratification."""
    f = lambda name: features[:, _F[name]]
    (issue_w, retire_w, rob, ic_kb, dc_kb, l2_kb, l3_mb, l2_lat, l3_lat,
     mem_lat, sms_on, bo_on, tage_ratio, fetch_w) = [cv[i] for i in range(14)]
    ipc_core = jnp.minimum(f("ilp"), retire_w)
    l1d_mpki = f("l1d_mpki") * (32.0 / dc_kb) ** f("l1d_alpha")
    l2_mpki = jnp.minimum(l1d_mpki, f("l2_mpki") * (512.0 / l2_kb) ** 0.5)
    l3_mpki = jnp.minimum(l2_mpki, f("l3_mpki") * (2.0 / l3_mb) ** 0.5)
    stall = (l3_mpki * mem_lat + (l2_mpki - l3_mpki) * l3_lat) / 1000.0 \
        / jnp.maximum(f("mlp") * 0.5, 1.0)
    cpi = 1.0 / ipc_core + stall
    out = {"cpi": cpi, "l1d_mpki": l1d_mpki, "l2_mpki": l2_mpki,
           "l3_mpki": l3_mpki, "ipc_core": ipc_core, "stall_mem": stall}
    return out


def evaluate_regions_approx(features: np.ndarray, cfg: UarchConfig,
                            indices=None) -> dict[str, np.ndarray]:
    """Fast approximate simulator (6 metrics, ~1/6 the model terms)."""
    x = jnp.asarray(features, jnp.float32)
    if indices is not None:
        x = x[jnp.asarray(indices)]
    stats = _evaluate_approx(x, _config_vector(cfg))
    return {k: np.asarray(v) for k, v in stats.items()}
