"""Kernel micro-benchmarks: correctness deltas + host-side timings.

On this CPU container the Pallas kernels run in interpret mode (slow by
construction — correctness validation only); the jnp reference paths are
what the timings characterize. us_per_call is wall time of the jitted call.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, *args, iters=5) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) \
        else fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        leaf = out[0] if isinstance(out, tuple) else out
        leaf.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def bench_kernels() -> dict:
    from repro.core.clustering.kmeans import _assign_jnp
    from repro.kernels.kmeans_assign.ops import kmeans_assign
    from repro.kernels.kmeans_assign.ref import kmeans_assign_ref
    from repro.kernels.segment_stats.ops import segment_stats
    from repro.kernels.segment_stats.ref import segment_stats_ref

    rng = np.random.default_rng(0)
    out = {}

    # k-means assignment: the paper's scalability hot spot (>=100k BBVs)
    x = jnp.asarray(rng.normal(size=(100_000, 15)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(20, 15)), jnp.float32)
    # timed: the "jnp" backend's assignment; agreement: the exact
    # direct-form reference
    us_ref = _timeit(jax.jit(_assign_jnp), x, c)
    l1, d1 = kmeans_assign(x[:4096], c)
    l2, d2 = kmeans_assign_ref(x[:4096], c)
    agree = float((np.asarray(l1) == np.asarray(l2)).mean())
    print(f"kmeans_assign_ref_100k,{us_ref:.0f},us_per_call")
    print(f"kmeans_assign_pallas_agreement,{agree:.4f},interpret-mode vs ref")
    out["kmeans_agree"] = agree

    # segment stats (stratified moments); backend="pallas" so the kernel
    # body is actually exercised off-TPU (interpret mode) — the default
    # "auto" would serve the oracle and compare it to itself
    lab = jnp.asarray(rng.integers(0, 20, 100_000), jnp.int32)
    ref2 = jax.jit(lambda a, b: segment_stats_ref(a, b, 20))
    us2 = _timeit(ref2, x, lab)
    s1, q1, c1 = segment_stats(x[:8192], lab[:8192], 20, backend="pallas")
    s2, q2, c2 = segment_stats_ref(x[:8192], lab[:8192], 20)
    err = float(jnp.max(jnp.abs(s1 - s2)))
    print(f"segment_stats_ref_100k,{us2:.0f},us_per_call")
    print(f"segment_stats_pallas_maxerr,{err:.2e},interpret-mode vs ref")
    out["segment_err"] = err

    # flash attention (oracle check at a serving-ish shape)
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    o1 = flash_attention(q, k, v)
    o2 = attention_ref(q, k, v, causal=True)
    ferr = float(jnp.max(jnp.abs(o1 - o2)))
    us3 = _timeit(jax.jit(lambda a, b, c_: attention_ref(a, b, c_,
                                                         causal=True)),
                  q, k, v)
    print(f"flash_attention_ref,{us3:.0f},us_per_call")
    print(f"flash_attention_pallas_maxerr,{ferr:.2e},interpret-mode vs ref")
    out["flash_err"] = ferr

    # distributed k-means (paper §VII.B at host scale)
    from repro.core.clustering.distributed import distributed_kmeans
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    t0 = time.perf_counter()
    _, _, inertia = distributed_kmeans(np.asarray(x[:20_000]), 20, mesh,
                                       iters=5)
    dt = (time.perf_counter() - t0) * 1e6
    print(f"distributed_kmeans_20k_5it,{dt:.0f},inertia={inertia:.3e}")
    return out
