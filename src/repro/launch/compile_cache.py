"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so it has to be a fixed path: one
built from a temporary name, a PID or the time never hits again.

Functions only — importing this module touches no backend.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache (this file is <repo>/src/repro/launch/compile_cache.py)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Point the persistent cache at its directory and return it.

    If JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and nothing
    is changed; otherwise the cache goes to <repo>/.jax_cache. Call this
    before the first compilation."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
