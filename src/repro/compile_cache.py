"""JAX's persistent compilation cache, at one fixed place per checkout.

Every entry point that compiles for the chip (``chip_smoke.py``,
``benchmarks/run.py``, the examples) calls :func:`enable` before its first
compile. A later run finds a compiled program only where an earlier run
put it, so the directory is fixed, never built from a temporary name, a
process id or the time:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  module sets no other directory;
* otherwise the cache lives in ``.jax_cache/`` at the checkout root (the
  directory holding ``src/``), which ``.gitignore`` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fallback cache directory: ``<checkout>/.jax_cache``
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    env = os.environ.get(ENV)
    if env:
        # jax already took the directory from the environment
        return env
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
