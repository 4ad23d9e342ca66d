"""JAX's persistent compilation cache at a place the caller can fix.

Every entry point (``chip_smoke.py``, ``bench/run.py``,
``python -m repro.paper``, ``python -m repro.serving.cli`` and the
examples) calls ``enable_compile_cache()`` before its first compile, so
a second run with the same programs loads them instead of compiling
again. Library imports and the test suite never turn the cache on.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; the
  directory is not set in code.
* Otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored).
  The path is part of the cache's key, so it is the same on every run:
  never a temporary name, a process id or a timestamp.

Either way the cache's key includes the programs' metadata (source
locations and ``jax.named_scope`` names). JAX leaves it out by default,
and a program loaded from the cache then carries the metadata of
whichever commit compiled it first: a profile of the trial scan would
show another commit's scopes, or none.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["CACHE_ENV", "default_cache_dir", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> pathlib.Path:
    """``<checkout>/.jax_cache`` — the checkout holding ``src/repro``."""
    return pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(default_cache_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
