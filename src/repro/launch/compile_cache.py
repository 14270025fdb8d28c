"""Where JAX keeps its persistent compilation cache.

Every entry point (``launch.serve``, ``launch.train``, ``benchmarks.run``,
``chip_smoke.py``) calls ``enable_compile_cache`` once, before its first
compile, and nothing else in the repo places the cache:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
    here.
  * unset: the cache lives in ``<checkout>/.jax_cache`` (gitignored). The
    path is fixed — never derived from a temp name, a pid or the time — so
    a later process in the same checkout finds what an earlier one wrote.

The test suite does not call it, so tests run with the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
