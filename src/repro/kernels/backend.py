"""Shared backend-selection policy for the Pallas kernels.

Every kernel wrapper in ``repro.kernels`` offers the same backend
contract (documented in ``docs/kernels.md``):

* ``"jnp"`` — the pure-jnp oracle; always available, never warns.
* ``"pallas"`` — the TPU kernel as requested. Off-TPU it degrades to the
  Pallas *interpreter* (same kernel body, correctness validation only)
  and on import failure to the oracle — each degradation emits a
  one-time ``BackendFallbackWarning`` naming the reason. On TPU a kernel
  import failure raises instead of degrading.
* ``"auto"`` — the production default: the kernel on TPU, the oracle
  elsewhere (interpret mode is far too slow for hot paths). The off-TPU
  choice emits a one-time ``BackendFallbackWarning`` so runs that
  expected TPU throughput can see they did not get it.

``repro.core.clustering.kmeans`` re-exports these names so historic
imports (`from repro.core.clustering.kmeans import BackendFallbackWarning`)
keep working.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp


class BackendFallbackWarning(UserWarning):
    """Raised once per (kernel, requested, active) triple when a requested
    kernel backend falls back to a different active backend."""


@dataclasses.dataclass(frozen=True)
class ResolvedBackend:
    """Outcome of kernel-backend selection.

    ``requested`` is the caller's ``backend=`` string; ``active`` is what
    will actually run (``"jnp"``, ``"pallas"`` or ``"pallas_interpret"``);
    ``reason`` explains any divergence (``None`` when served as asked).
    """

    requested: str
    active: str
    reason: Optional[str] = None


_FALLBACK_WARNED: set[tuple[str, str, str]] = set()


def warn_fallback_once(kernel: str, requested: str, active: str,
                       reason: str) -> None:
    """Emit ``BackendFallbackWarning`` once per (kernel, requested, active)."""
    key = (kernel, requested, active)
    if key in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(key)
    if requested == "auto":
        msg = (f"{kernel} backend 'auto' resolved to {active!r} ({reason})")
    else:
        msg = (f"{kernel} backend {requested!r} is not available as "
               f"requested; using {active!r} instead ({reason})")
    warnings.warn(msg, BackendFallbackWarning, stacklevel=4)


def reset_backend_warnings() -> None:
    """Re-arm the one-time fallback warnings (test helper)."""
    _FALLBACK_WARNED.clear()


def kernel_compute_dtype(precision=None) -> jnp.dtype:
    """The dtype a kernel contract computes in under a ``PrecisionPolicy``.

    The jnp oracle honors the policy's *trace* dtype exactly; the Pallas
    kernel bodies accumulate in f32 by construction, so wider traces only
    widen the oracle path (kernel wrappers cast back to f32 before a
    Pallas launch). ``precision=None`` resolves to the repo-wide default
    policy (f32 trace) — the historic hardcoded-f32 behavior.
    """
    from repro.core.precision import resolve_precision

    return jnp.dtype(resolve_precision(precision).trace)


def resolve_backend(requested: str, *, kernel: str,
                    import_probe: Callable[[], None]) -> ResolvedBackend:
    """Map a requested kernel backend to the one that can run here.

    ``kernel`` names the kernel for warning messages; ``import_probe``
    imports the kernel package (raising on failure). Selection policy:

    * ``"jnp"`` resolves to itself, silently.
    * ``"pallas"`` resolves to ``"pallas"`` on TPU, to
      ``"pallas_interpret"`` elsewhere, and to ``"jnp"`` when the kernel
      package cannot import off-TPU — the latter two warn once. On TPU
      an import failure raises: the device path never hides behind the
      oracle.
    * ``"auto"`` resolves to ``"pallas"`` on TPU and to ``"jnp"``
      elsewhere (warning once off-TPU: interpret mode is validation-only,
      not a production path).
    """
    if requested == "jnp":
        return ResolvedBackend("jnp", "jnp")
    if requested not in ("pallas", "auto"):
        raise ValueError(f"unknown backend {requested!r}; "
                         "expected 'jnp', 'pallas' or 'auto'")
    platform = jax.default_backend()
    try:
        import_probe()
    except Exception as e:  # pragma: no cover - import is cheap and local
        if platform == "tpu":
            raise
        reason = (f"import of the {kernel} kernel failed: "
                  f"{type(e).__name__}: {e}")
        warn_fallback_once(kernel, requested, "jnp", reason)
        return ResolvedBackend(requested, "jnp", reason)
    if platform == "tpu":
        return ResolvedBackend(requested, "pallas")
    if requested == "auto":
        reason = (f"platform={platform!r} has no TPU; using the jnp oracle "
                  "(interpret mode is correctness validation, not a "
                  "production path)")
        warn_fallback_once(kernel, requested, "jnp", reason)
        return ResolvedBackend("auto", "jnp", reason)
    reason = (f"platform={platform!r} has no TPU; the Pallas kernel "
              "runs in interpret mode (correctness validation only)")
    warn_fallback_once(kernel, requested, "pallas_interpret", reason)
    return ResolvedBackend("pallas", "pallas_interpret", reason)
