"""Plain copy of the bank's population and BBV generators.

The benchmark's yardstick may not import the program, so the synthetic
SPECint-2017-like region populations the engine builds on are generated
here again, from the same published seeds: per app a sticky Markov phase
sequence, per-phase feature means, input-data jitter, heavy-tail
outliers, and BBVs drawn around per-profile Dirichlet block mixes. The
output is compared bitwise with the program's bank before anything else
is checked, so a drift in either copy shows at once.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Optional

import numpy as np

NUM_FEATURES = 21
REGION_LEN_INSTR = 1_000_000
NUM_BLOCKS = 256
BBV_NOISE = 0.04
JITTER_VISIBILITY = 0.03


@dataclasses.dataclass(frozen=True)
class AppSpec:
    name: str
    n_regions: int
    n_phases: int
    phase1_n: int
    jitter: float
    ilp_range: tuple
    br_pki_mean: float
    br_mpr_mean: float
    mem_l1_mpki_mean: float
    mem_escape: float
    mlp_range: tuple
    prefetchability: float
    phase_spread: float
    outlier_prob: float = 0.0
    outlier_l3_mpki: float = 0.0
    outlier_sms_cov: float = 0.7
    alias_pairs: int = 0
    alias_scheme: str = "adjacent"
    alias_mem_scale: tuple = (2.0, 3.5)
    zipf: float = 0.7
    dominant_jitter: Optional[float] = None
    dominant_mem_scale: float = 1.0
    dominant_bimodal: Optional[tuple] = None
    markov_stickiness: float = 0.995


APP_SPECS = {s.name: s for s in (
    AppSpec("500.perlbench_r", 60_000, 8, 1_997, jitter=0.30,
            ilp_range=(2.2, 5.0), br_pki_mean=190.0, br_mpr_mean=0.013,
            mem_l1_mpki_mean=15.8, mem_escape=0.28, mlp_range=(1.8, 5.0),
            prefetchability=0.45, phase_spread=0.47),
    AppSpec("502.gcc_r", 120_000, 40, 6_195, jitter=0.30,
            ilp_range=(2.0, 5.0), br_pki_mean=210.0, br_mpr_mean=0.011,
            mem_l1_mpki_mean=11.4, mem_escape=0.38, mlp_range=(1.8, 4.5),
            prefetchability=0.40, phase_spread=0.55, zipf=1.3,
            dominant_jitter=1.00, dominant_mem_scale=1.6,
            dominant_bimodal=(0.40, 2.8),
            outlier_prob=0.0010, outlier_l3_mpki=70.0, outlier_sms_cov=0.72,
            alias_pairs=4),
    AppSpec("505.mcf_r", 40_000, 4, 964, jitter=0.45,
            ilp_range=(2.0, 3.5), br_pki_mean=160.0, br_mpr_mean=0.016,
            mem_l1_mpki_mean=34.0, mem_escape=0.46, mlp_range=(3.0, 7.0),
            prefetchability=0.30, phase_spread=0.12, alias_pairs=1,
            alias_mem_scale=(1.5, 2.0)),
    AppSpec("520.omnetpp_r", 40_000, 6, 967, jitter=0.08,
            ilp_range=(2.0, 4.0), br_pki_mean=180.0, br_mpr_mean=0.010,
            mem_l1_mpki_mean=9.3, mem_escape=0.38, mlp_range=(1.8, 3.0),
            prefetchability=0.35, phase_spread=0.10, alias_pairs=2,
            alias_mem_scale=(1.35, 1.7)),
    AppSpec("523.xalancbmk_r", 100_000, 10, 6_861, jitter=0.40,
            ilp_range=(2.5, 5.5), br_pki_mean=200.0, br_mpr_mean=0.009,
            mem_l1_mpki_mean=12.6, mem_escape=0.31, mlp_range=(2.0, 6.0),
            prefetchability=0.55, phase_spread=0.10, alias_pairs=4,
            alias_mem_scale=(1.5, 2.2)),
    AppSpec("525.x264_r", 40_000, 5, 915, jitter=0.12,
            ilp_range=(3.6, 7.5), br_pki_mean=90.0, br_mpr_mean=0.006,
            mem_l1_mpki_mean=7.0, mem_escape=0.30, mlp_range=(6.0, 12.0),
            prefetchability=0.80, phase_spread=0.58),
    AppSpec("531.deepsjeng_r", 40_000, 4, 1_041, jitter=0.07,
            ilp_range=(3.0, 5.0), br_pki_mean=170.0, br_mpr_mean=0.017,
            mem_l1_mpki_mean=5.0, mem_escape=0.22, mlp_range=(2.0, 4.0),
            prefetchability=0.35, phase_spread=0.30),
    AppSpec("541.leela_r", 40_000, 3, 1_062, jitter=0.05,
            ilp_range=(3.0, 4.5), br_pki_mean=150.0, br_mpr_mean=0.014,
            mem_l1_mpki_mean=5.0, mem_escape=0.20, mlp_range=(2.0, 4.0),
            prefetchability=0.40, phase_spread=0.03),
    AppSpec("548.exchange2_r", 40_000, 2, 1_030, jitter=0.05,
            ilp_range=(2.8, 4.2), br_pki_mean=140.0, br_mpr_mean=0.012,
            mem_l1_mpki_mean=0.48, mem_escape=0.10, mlp_range=(2.0, 4.0),
            prefetchability=0.30, phase_spread=0.04),
    AppSpec("557.xz_r", 80_000, 30, 3_047, jitter=0.35,
            ilp_range=(2.0, 6.0), br_pki_mean=170.0, br_mpr_mean=0.015,
            mem_l1_mpki_mean=16.5, mem_escape=0.38, mlp_range=(1.5, 8.0),
            prefetchability=0.45, phase_spread=0.60, zipf=1.2,
            dominant_jitter=0.95, dominant_mem_scale=1.5,
            dominant_bimodal=(0.45, 2.9),
            outlier_prob=0.001, outlier_l3_mpki=45.0, outlier_sms_cov=0.6,
            alias_pairs=6, alias_scheme="spread"),
)}


@dataclasses.dataclass(frozen=True)
class Population:
    spec: AppSpec
    features: np.ndarray      # (n, 21) float64
    phase_ids: np.ndarray     # (n,)
    profile_ids: np.ndarray   # (n_phases,)
    jitter_u: np.ndarray      # (n,) float32


def _phase_means(spec, rng):
    P = spec.n_phases
    m = np.zeros((P, NUM_FEATURES))
    spread = spec.phase_spread

    def ln(mean, sig):
        return mean * np.exp(rng.normal(0.0, sig, P))

    m[:, 0] = rng.uniform(*spec.ilp_range, P)
    m[:, 1] = ln(spec.br_pki_mean, 0.2)
    m[:, 2] = np.clip(ln(spec.br_mpr_mean, spread), 1e-4, 0.08)
    m[:, 3] = rng.uniform(0.10, 0.45, P)
    m[:, 4] = rng.uniform(0.6, 0.95, P)
    m[:, 5] = np.clip(ln(1.2, spread), 0.01, 40.0)
    m[:, 6] = rng.uniform(0.3, 1.0, P)
    m[:, 7] = np.clip(ln(0.15, 0.4), 0.0, 4.0)
    m[:, 8] = ln(350.0, 0.10)
    m[:, 9] = rng.uniform(0.6, 0.8, P)
    m[:, 10] = np.clip(ln(spec.mem_l1_mpki_mean, spread), 0.02, 120.)
    m[:, 11] = rng.uniform(0.2, 0.9, P)
    esc = np.clip(spec.mem_escape * np.exp(rng.normal(0, spread / 2, P)),
                  0.02, 0.85)
    m[:, 12] = m[:, 10] * esc
    m[:, 13] = rng.uniform(0.2, 0.9, P)
    esc3 = np.clip(spec.mem_escape * np.exp(rng.normal(0, spread / 2, P)),
                   0.02, 0.85)
    m[:, 14] = m[:, 12] * esc3
    m[:, 15] = rng.uniform(0.1, 0.8, P)
    m[:, 16] = rng.uniform(0.15, 0.5, P)
    m[:, 17] = np.clip(spec.prefetchability
                       * np.exp(rng.normal(0, 0.3, P)), 0.02, 0.95)
    m[:, 18] = np.clip(spec.prefetchability
                       * np.exp(rng.normal(0, 0.3, P)), 0.02, 0.95)
    m[:, 19] = rng.uniform(*spec.mlp_range, P)
    m[:, 20] = rng.uniform(0.1, 0.9, P)
    return m


def _phase_sequence(spec, rng):
    P, n = spec.n_phases, spec.n_regions
    pop = 1.0 / np.arange(1, P + 1) ** spec.zipf
    pop /= pop.sum()
    first = rng.choice(P, p=pop)
    jumps = rng.random(n) > spec.markov_stickiness
    targets = rng.choice(P, size=n, p=pop)
    # seq[i] = targets[i] at a jump, else seq[i - 1]: carry the last jump
    jumps[0] = False
    last = np.maximum.accumulate(np.where(jumps, np.arange(n), 0))
    seq = np.where(last > 0, targets[last], first).astype(np.int32)
    return seq


_JITTER_COLS = (2, 5, 10, 12, 14, 19)
_CODE_COLS = [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 11, 13, 15, 16, 20]
_DATA_SCALE_COLS = [10, 12, 14]


def _alias_profiles(spec):
    profile_ids = np.arange(spec.n_phases, dtype=np.int32)
    alias_of = {}
    for a in range(spec.alias_pairs):
        if spec.alias_scheme == "adjacent":
            i, j = 2 * a, 2 * a + 1
        else:
            i, j = a, spec.n_phases - 1 - a
        if i < j < spec.n_phases:
            profile_ids[j] = profile_ids[i]
            alias_of[j] = i
    return profile_ids, alias_of


def generate(name: str, seed: int = 0) -> Population:
    """One app's region population, as the program's generator makes it
    for ``seed`` (the engine always uses seed 0)."""
    spec = APP_SPECS[name]
    root = np.random.SeedSequence([zlib.crc32(spec.name.encode()), seed])
    rng_means, rng_alias, rng_seq, rng_jit, rng_out = [
        np.random.default_rng(s) for s in root.spawn(5)]
    means = _phase_means(spec, rng_means)
    profile_ids, alias_of = _alias_profiles(spec)
    for j, i in alias_of.items():
        means[j, _CODE_COLS] = means[i, _CODE_COLS]
        scale = rng_alias.uniform(*spec.alias_mem_scale)
        means[j, _DATA_SCALE_COLS] = means[i, _DATA_SCALE_COLS] * scale
        means[j, 19] = max(1.0, means[i, 19] * rng_alias.uniform(0.55, 0.8))
        means[j, 17] = means[i, 17]
        means[j, 18] = means[i, 18]
    if spec.dominant_mem_scale != 1.0:
        means[0, _DATA_SCALE_COLS] *= spec.dominant_mem_scale
    seq = _phase_sequence(spec, rng_seq)
    feats = means[seq].copy()

    n = spec.n_regions
    sigma = np.full(n, spec.jitter)
    if spec.dominant_jitter is not None:
        sigma[seq == 0] = spec.dominant_jitter
    u = rng_jit.normal(0.0, 1.0, n)
    if spec.dominant_bimodal is not None and spec.dominant_jitter is not None:
        frac_heavy, delta_u = spec.dominant_bimodal
        dom = seq == 0
        u[dom] = rng_jit.normal(0.0, 0.55, int(dom.sum()))
        heavy = dom & (rng_jit.random(n) < frac_heavy)
        u[heavy] += delta_u
    for col in _JITTER_COLS:
        mix = 0.75 * u + 0.25 * rng_jit.normal(0.0, 1.0, n)
        feats[:, col] *= np.exp(sigma * mix)
    feats[:, 0] = np.clip(feats[:, 0] + rng_jit.normal(0, 0.15, n), 1.0, 8.0)
    feats[:, 19] = np.clip(
        feats[:, 19] * np.exp(-0.3 * sigma * u
                              + (spec.jitter / 2) * rng_jit.normal(0.0, 1.0, n)),
        1.0, 16.0)

    is_out = rng_out.random(n) < spec.outlier_prob
    if is_out.any():
        k = int(is_out.sum())
        feats[is_out, 14] = spec.outlier_l3_mpki * np.exp(
            rng_out.normal(0, 0.15, k))
        feats[is_out, 12] = np.maximum(feats[is_out, 12],
                                       feats[is_out, 14] * 1.1)
        feats[is_out, 10] = np.maximum(feats[is_out, 10],
                                       feats[is_out, 12] * 1.2)
        feats[is_out, 19] = 1.0
        feats[is_out, 15] = 0.05
        feats[is_out, 17] = spec.outlier_sms_cov
        feats[is_out, 20] = 0.1
    return Population(spec=spec, features=feats, phase_ids=seq,
                      profile_ids=profile_ids,
                      jitter_u=u.astype(np.float32))


def bbvs(pop: Population, seed: int = 1) -> np.ndarray:
    """(n, 256) float32 basic-block vectors of one population."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [zlib.crc32(pop.spec.name.encode()), seed, 7]))
    n_profiles = int(pop.profile_ids.max()) + 1
    profiles = rng.dirichlet(np.full(NUM_BLOCKS, 0.06), size=n_profiles)
    directions = rng.choice([-1.0, 1.0], size=(n_profiles, NUM_BLOCKS))
    prof = pop.profile_ids[pop.phase_ids]
    noise = rng.normal(1.0, BBV_NOISE, (len(prof), NUM_BLOCKS))
    sway = 1.0 + JITTER_VISIBILITY * pop.jitter_u[:, None] * directions[prof]
    bbv = profiles[prof] * np.clip(noise * np.clip(sway, 0.2, 3.0), 0.2, 3.0)
    bbv /= bbv.sum(axis=1, keepdims=True)
    return (bbv * REGION_LEN_INSTR).astype(np.float32)


def phase1_indices(n_regions: int, n: int, seed: int = 42) -> np.ndarray:
    """The phase-1 simple random sample: ``n`` of ``n_regions`` without
    replacement, from ``default_rng(seed)`` (one fresh generator per app)."""
    return np.random.default_rng(seed).choice(n_regions, size=n,
                                              replace=False)
