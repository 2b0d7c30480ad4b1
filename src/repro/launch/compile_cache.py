"""JAX's persistent compilation cache for the entry points.

Every entry point calls ``enable_compile_cache()`` before its first
compile, so the processes of one run (and later runs on the same
checkout) reuse each other's compiled programs. The cache directory is
part of the cache key, so it is never derived from a temp name, a pid
or the clock:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else
  is configured here.
- otherwise: ``.jax_cache`` at the root of the checkout this package
  was imported from (listed in ``.gitignore``).

Tests never call this: they compile with the cache off.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """The directory ``enable_compile_cache`` uses."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    # src/repro/launch/compile_cache.py -> the checkout root
    return str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
