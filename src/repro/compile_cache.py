"""JAX's persistent compilation cache, kept at one fixed place per checkout.

The cache key includes the cache path, so a directory that moves (a temp dir,
a pid- or time-stamped one) never hits. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here; otherwise the cache
lives in ``<repo>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
