"""The ONE precision policy for the numeric stack.

Before this module, dtype choices were scattered as ad-hoc casts:
``run_trials`` forced ``float32`` pools, ``trial_uniforms`` drew f32,
``tables.py`` cast device inputs to ``jnp.float32`` while keeping f64 on
the numpy path, the sweep estimator picked f64-off-TPU inside
``plan._x64_sweep_programs``, and the ``segment_stats`` kernel hardcoded
f32 accumulation. ``PrecisionPolicy`` replaces all of those with one
explicit, threadable object of three dtypes:

* ``trace`` — the dtype traced device programs compute in (uniform
  draws, gathers, per-trial estimates). f32 by default: it is what the
  TPU kernels run natively.
* ``accum`` — the dtype streaming accumulators carry (error-moment
  sums in the chunked trial scan). f32 by default; the
  coverage-calibration gate in ``tests/test_streaming_trials.py`` proves
  f32 accumulators do not degrade empirical coverage at 10^5+ trials
  (the load-bearing counters — coverage, histogram sketches — are
  integers and therefore exact in any accumulator dtype).
* ``host`` — the dtype host-side (numpy) statistics use. f64: the
  scalar-parity reference path.

Policies are frozen, hashable (usable as ``lru_cache``/``jit`` static
keys) and carry dtypes as canonical numpy names so equality is by value.
Jax is imported lazily: constructing a policy never initializes device
state (``host_parity`` and ``x64_context`` touch jax on use only).
"""

from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

__all__ = ["PrecisionPolicy", "DEFAULT_PRECISION", "resolve_precision"]

_ALLOWED = ("float32", "float64")


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Trace / accumulator / host dtype triple for one numeric pipeline."""

    trace: str = "float32"   # traced device programs (kernels, trial math)
    accum: str = "float32"   # streaming accumulators (chunked scan carry)
    host: str = "float64"    # host-side numpy statistics (parity path)

    def __post_init__(self):
        for field in ("trace", "accum", "host"):
            name = np.dtype(getattr(self, field)).name
            if name not in _ALLOWED:
                raise ValueError(
                    f"PrecisionPolicy.{field} must be one of {_ALLOWED}, "
                    f"got {getattr(self, field)!r}")
            object.__setattr__(self, field, name)

    # dtype views -----------------------------------------------------------
    @property
    def trace_dtype(self) -> np.dtype:
        return np.dtype(self.trace)

    @property
    def accum_dtype(self) -> np.dtype:
        return np.dtype(self.accum)

    @property
    def host_dtype(self) -> np.dtype:
        return np.dtype(self.host)

    @property
    def needs_x64(self) -> bool:
        """Whether traced programs under this policy require 64-bit jax."""
        return "float64" in (self.trace, self.accum)

    def x64_context(self):
        """Context manager enabling jax x64 iff this policy needs it.

        Device programs run under ``with policy.x64_context():`` so a
        64-bit trace/accumulator request actually computes in f64
        (outside the context jax silently truncates to f32).
        """
        if not self.needs_x64:
            return contextlib.nullcontext()
        import jax
        return jax.enable_x64(True)

    # canonical policies ----------------------------------------------------
    @classmethod
    def default(cls) -> "PrecisionPolicy":
        """The trial-path production policy: f32 trace/accum, f64 host."""
        return cls()

    @classmethod
    def host_parity(cls) -> "PrecisionPolicy":
        """The sweep-estimate policy: trace in the host dtype off-TPU so
        on-device estimates match the numpy reference bitwise (f64 on CPU
        hosts), f32 trace on TPU where f64 is emulated, so estimates
        there match the reference to f32 rounding only."""
        import jax
        if jax.default_backend() == "tpu":
            return cls(trace="float32", accum="float32", host="float64")
        return cls(trace="float64", accum="float64", host="float64")


DEFAULT_PRECISION = PrecisionPolicy()


def resolve_precision(precision: PrecisionPolicy | None,
                      *fallbacks: PrecisionPolicy | None) -> PrecisionPolicy:
    """First non-None of (precision, *fallbacks, DEFAULT_PRECISION)."""
    for p in (precision,) + fallbacks:
        if p is not None:
            return p
    return DEFAULT_PRECISION
