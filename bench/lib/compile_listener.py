"""Counts XLA backend compiles and their seconds in this process.

JAX reports each backend compile as the duration event
``/jax/core/compile/backend_compile_duration``; a program found in the
persistent cache or the in-memory jit cache reports none.
"""

from __future__ import annotations

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def install(self) -> "CompileCounter":
        import jax

        def on_duration(event, duration, **_):
            if event == COMPILE_EVENT:
                self.count += 1
                self.seconds += float(duration)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        return self

    def snapshot(self) -> tuple[int, float]:
        return self.count, self.seconds
