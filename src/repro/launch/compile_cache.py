"""Where compiled XLA programs persist between processes.

JAX keys its persistent compilation cache by the directory too, so the
directory must not move between runs: it is ``$JAX_COMPILATION_CACHE_DIR``
when that is set (JAX reads the variable itself, and nothing here
overrides it), else the fixed ``.jax_cache/`` at the repository root.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory used when the environment names none.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call before the process compiles anything: JAX fixes the cache when
    it compiles for the first time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
