"""Batched k-means assignment: batch-grid kernel vs vmap-of-kernel vs oracle.

Measures the dispatch the tentpole replaced against the one it introduced,
over a (B, N, K) sweep:

* ``batched`` — ONE ``(batch, tile)``-grid Pallas launch for the whole
  stack (the path ``kmeans_batch``/``kmeans_bank`` now take);
* ``vmapped`` — ``jax.vmap`` over the per-problem 2-D wrapper, i.e. the
  legacy vmap-of-``pallas_call`` lifting;
* ``oracle`` — the jitted ``"jnp"`` backend (the expanded-form einsum the
  Lloyd loop runs off-TPU).

Agreement is measured against ``kmeans_assign_ref``, the exact
direct-form assignment (no matmul), which is not timed.

On this CPU container both Pallas variants run in interpret mode, so their
timings characterize the interpreter, not the MXU — the numbers to watch
off-TPU are the oracle timings and the agreement columns (which gate CI:
``benchmarks/run.py`` FAILs the claim row if agreement drops). On TPU the
same rows compare compiled launch strategies directly.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

# (B, N, K) sweep; D fixed at the post-projection feature width
SWEEP = ((2, 512, 20), (4, 1024, 20), (8, 512, 64))
FEAT_D = 16


def _time_us(fn, *args, iters: int = 3) -> float:
    """Mean wall time of the jitted call in microseconds (post-warmup)."""
    fn(*args)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(*args)[0].block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def bench_kmeans_batched() -> dict:
    """CSV rows per (B, N, K) point + worst-case agreement for CI gating."""
    from repro.core.clustering.kmeans import _assign_jnp_stacked
    from repro.kernels.kmeans_assign.ops import kmeans_assign, last_dispatch
    from repro.kernels.kmeans_assign.ref import kmeans_assign_ref

    batched = jax.jit(kmeans_assign)
    # the vmap-of-kernel leg IS the measured anti-pattern (JL006's
    # regression baseline), not production dispatch
    vmapped = jax.jit(jax.vmap(kmeans_assign))  # jaxlint: disable=JL006
    oracle = jax.jit(_assign_jnp_stacked)
    exact = jax.jit(kmeans_assign_ref)

    rng = np.random.default_rng(0)
    worst_agree = 1.0
    for b, n, k in SWEEP:
        x = jnp.asarray(rng.normal(size=(b, n, FEAT_D)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(b, k, FEAT_D)), jnp.float32)

        us_batched = _time_us(batched, x, c)
        rec = last_dispatch()
        us_vmapped = _time_us(vmapped, x, c)
        us_oracle = _time_us(oracle, x, c)

        l_b, _ = batched(x, c)
        l_o, _ = exact(x, c)
        agree = float((np.asarray(l_b) == np.asarray(l_o)).mean())
        worst_agree = min(worst_agree, agree)

        tag = f"B{b}_N{n}_K{k}"
        mode = "interpret" if rec and rec["interpret"] else "compiled"
        print(f"kmeans_assign_batched_{tag},{us_batched:.0f},"
              f"us_per_call grid={rec['grid'] if rec else '?'} {mode}")
        print(f"kmeans_assign_vmapped_{tag},{us_vmapped:.0f},"
              f"us_per_call vmap-of-pallas_call {mode}")
        print(f"kmeans_assign_oracle_{tag},{us_oracle:.0f},us_per_call jnp")
        print(f"kmeans_assign_agreement_{tag},{agree:.4f},batched vs exact "
              "reference")

    print(f"kmeans_assign_worst_agreement,{worst_agree:.4f},"
          "min over (B,N,K) sweep")
    return {"worst_agree": worst_agree}
