"""Where JAX keeps its persistent compilation cache.

Entry points that compile large programs (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`use_compile_cache` before anything else;
nothing calls it on import, and the tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache — a fixed path, so each run finds what the last one
# compiled. Listed in .gitignore.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache goes to
    ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
