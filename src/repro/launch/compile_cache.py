"""Persistent XLA compilation cache for the entry-point scripts.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve_queries``) call :func:`enable_compile_cache` before
their first compile; importing the library never touches the setting.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set -> JAX reads it itself and this
  module sets nothing;
* otherwise -> ``<checkout>/.jax_cache``, a fixed path (never a
  temporary name, a pid or the time: a directory that moves between runs
  is never found again), listed in ``.gitignore``.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
