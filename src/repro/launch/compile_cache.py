"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) call :func:`use_compile_cache` from ``main()``;
importing a module never changes the setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory inside the checkout, used when the environment
#: names none.  It is fixed: the directory is part of what a later run
#: looks up, so a name that moves never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.
    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it and that
    setting stands; otherwise the cache lives in :data:`REPO_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
