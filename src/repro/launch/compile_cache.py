"""Where the launchers keep JAX's persistent compilation cache.

JAX reads `JAX_COMPILATION_CACHE_DIR` itself; where it is set, nothing here
overrides it.  Otherwise the cache lives at `<repo>/.jax_cache`.  The path
is fixed because a cache directory that moves between runs never hits.
Call `use_compile_cache()` once at program start, never on import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX at the persistent compile cache; returns the directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
