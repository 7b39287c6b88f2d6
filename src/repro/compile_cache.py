"""Where JAX keeps its persistent compilation cache.

Entry points that touch the device (``chip_smoke.py``, the benchmark
runners) call :func:`configure_compile_cache` once, before their first
compile. ``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it
itself. Otherwise the cache lives at the fixed, git-ignored
``<repo>/.jax_cache``: the directory is part of the cache key, so it is
never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
