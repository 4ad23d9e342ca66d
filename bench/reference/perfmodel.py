"""Plain copy of the analytical core model: region features x uarch
config -> CPI and the 38 Table III counters of the RFV.

Written against an array namespace ``xp`` (numpy or jax.numpy) and a
dtype, so the same equations give the reference (numpy float64) and the
low-precision control (jax.numpy bfloat16). The seven configs of the
paper's Table I are data in ``CONFIGS``.
"""

from __future__ import annotations

import numpy as np

FREQ_GHZ = 3.0

_BASE = dict(fetch_width=8, issue_width=8, l2_hit_lat=8, icache_kb=32,
             dcache_kb=32, l2_kb=512, l3_mb=2, sms_pf=False, rob_size=128,
             retire_width=4, mem_latency_ns=130.0, l3_hit_latency_ns=30.0,
             bo_pf=False, tage_tables=4, tage_entries=2048)


def _table_i():
    c0 = dict(_BASE)
    c1 = dict(c0, icache_kb=64, dcache_kb=64, l2_kb=1024, l3_mb=4)
    c2 = dict(c1, sms_pf=True)
    c3 = dict(c2, rob_size=256, retire_width=8)
    c4 = dict(c3, mem_latency_ns=90.0, l3_hit_latency_ns=20.0)
    c5 = dict(c4, bo_pf=True)
    c6 = dict(c5, tage_tables=8, tage_entries=4096)
    return (c0, c1, c2, c3, c4, c5, c6)


CONFIGS = _table_i()

FEATURES = ("ilp", "br_pki", "br_mpr", "br_predict", "cond_frac", "ic_mpki",
            "ic_alpha", "itlb_mpki", "l1d_apki", "load_frac", "l1d_mpki",
            "l1d_alpha", "l2_mpki", "l2_alpha", "l3_mpki", "l3_alpha",
            "wb_frac", "sms_cov", "bo_cov", "mlp", "rob_sens")
_F = {n: i for i, n in enumerate(FEATURES)}

RFV_METRICS = (
    ("cpi", "branch_mispredicts", "cond_branch_mispredicts",
     "target_branch_mispredicts", "icache_misses", "itlb_misses",
     "l1d_access", "l1d_load_miss", "l1d_store_miss", "l1d_total_miss",
     "l1d_writeback", "l2_misses", "l2_load_misses", "l2_writebacks",
     "l3_read_accesses", "l3_write_accesses", "l3_misses")
    + tuple(f"stall_bin_{i:02d}" for i in range(21)))


def model(features, cfg: dict, *, xp=np, dtype=np.float64,
          counters: bool = False):
    """CPI of every region (``counters=False``) or the dict of all 38
    RFV metrics, for (..., 21) features under one config."""
    x = xp.asarray(features).astype(dtype)

    def f(name):
        return x[..., _F[name]]

    def c(v):
        return xp.asarray(float(v), dtype)

    issue_w, retire_w = c(cfg["issue_width"]), c(cfg["retire_width"])
    rob, ic_kb, dc_kb = c(cfg["rob_size"]), c(cfg["icache_kb"]), c(cfg["dcache_kb"])
    l2_kb, l3_mb, l2_lat = c(cfg["l2_kb"]), c(cfg["l3_mb"]), c(cfg["l2_hit_lat"])
    l3_lat = c(cfg["l3_hit_latency_ns"] * FREQ_GHZ)
    mem_lat = c(cfg["mem_latency_ns"] * FREQ_GHZ)
    sms_on = c(1.0 if cfg["sms_pf"] else 0.0)
    bo_on = c(1.0 if cfg["bo_pf"] else 0.0)
    tage_ratio = c(cfg["tage_tables"] * cfg["tage_entries"] / (4 * 2048))
    fetch_w = c(cfg["fetch_width"])
    one = c(1.0)

    ilp_eff = f("ilp") * (one + c(0.08) * f("rob_sens") * (rob / c(128.0) - one))
    ipc_core = xp.minimum(xp.minimum(ilp_eff, retire_w), issue_w)
    base_cpi = one / ipc_core
    mpr_eff = f("br_mpr") * tage_ratio ** (-f("br_predict"))
    br_mpki = f("br_pki") * xp.clip(mpr_eff, c(0.0), c(0.15))
    stall_br = br_mpki / c(1000.0) * (c(12.0) + rob / c(32.0))
    ic_mpki = f("ic_mpki") * (c(32.0) / ic_kb) ** f("ic_alpha")
    stall_ic = ic_mpki / c(1000.0) * l2_lat * c(0.7)
    itlb_mpki = f("itlb_mpki")
    stall_itlb = itlb_mpki / c(1000.0) * c(20.0)
    l1d_mpki = f("l1d_mpki") * (c(32.0) / dc_kb) ** f("l1d_alpha")
    l2_mpki = xp.minimum(l1d_mpki,
                         f("l2_mpki") * (c(512.0) / l2_kb) ** f("l2_alpha"))
    l3_mpki = xp.minimum(l2_mpki,
                         f("l3_mpki") * (c(2.0) / l3_mb) ** f("l3_alpha"))
    l2_served = xp.maximum(l1d_mpki - l2_mpki, c(0.0))
    l3_served = xp.maximum(l2_mpki - l3_mpki, c(0.0))
    mem_served = l3_mpki
    cov_sms = f("sms_cov") * sms_on
    cov_bo = f("bo_cov") * bo_on
    mem_cost = mem_served * ((one - cov_sms) * mem_lat + cov_sms * l2_lat)
    l3_cost = l3_served * ((one - cov_bo) * l3_lat + cov_bo * l2_lat)
    l2_cost = l2_served * l2_lat * c(0.5)
    rob_cap = rob / c(32.0)
    mlp = f("mlp")
    mlp_eff = one + (mlp - one) * xp.clip(rob_cap / mlp, c(0.0), one)
    stall_mem = (mem_cost + l3_cost + l2_cost) / c(1000.0) / mlp_eff
    cpi = base_cpi + stall_br + stall_ic + stall_itlb + stall_mem
    if not counters:
        return cpi

    cond = f("cond_frac")
    demand_l3 = mem_served * (one - cov_sms)
    demand_l2 = l3_served * (one - cov_bo) + mem_served
    wb = f("wb_frac")
    load = f("load_frac")
    out = {
        "cpi": cpi, "branch_mispredicts": br_mpki,
        "cond_branch_mispredicts": br_mpki * cond,
        "target_branch_mispredicts": br_mpki * (one - cond),
        "icache_misses": ic_mpki, "itlb_misses": itlb_mpki,
        "l1d_access": f("l1d_apki"), "l1d_load_miss": l1d_mpki * load,
        "l1d_store_miss": l1d_mpki * (one - load),
        "l1d_total_miss": l1d_mpki, "l1d_writeback": l1d_mpki * wb,
        "l2_misses": demand_l2, "l2_load_misses": demand_l2 * load,
        "l2_writebacks": l2_mpki * wb, "l3_read_accesses": demand_l2,
        "l3_write_accesses": l2_mpki * wb, "l3_misses": demand_l3,
    }
    dram = mem_cost / c(1000.0) / mlp_eff
    l3s = l3_cost / c(1000.0) / mlp_eff
    l2s = l2_cost / c(1000.0) / mlp_eff
    fe_bw = xp.maximum(c(0.0), one / fetch_w - one / ipc_core) \
        + c(0.01) * base_cpi
    rob_press = xp.clip(mlp - rob_cap, c(0.0), None) / (mlp + one)
    bins = [stall_ic, stall_itlb, stall_br * c(0.4), fe_bw,
            stall_br * c(0.6), l2s, l3s, dram,
            l1d_mpki * wb / c(1000.0) * c(2.0), rob_press * stall_mem,
            base_cpi * c(0.10), base_cpi * c(0.05),
            dram * c(0.30) + l3s * c(0.10), dram * c(0.10) + l2s * c(0.40),
            stall_mem * rob_press * c(0.50),
            stall_br * c(0.25) + fe_bw * c(0.30),
            stall_ic * c(0.50) + stall_itlb * c(0.20),
            base_cpi * c(0.08) + stall_br * c(0.05),
            l2s * c(0.20) + l3s * c(0.30), stall_mem * c(0.15),
            base_cpi * c(0.04) + stall_mem * c(0.02)]
    for i, b in enumerate(bins):
        out[f"stall_bin_{i:02d}"] = b
    return out


def rfv(features, cfg: dict, *, xp=np, dtype=np.float64):
    """(..., 38) RFV matrix in Table III order."""
    stats = model(features, cfg, xp=xp, dtype=dtype, counters=True)
    return xp.stack([stats[m] for m in RFV_METRICS], axis=-1)
