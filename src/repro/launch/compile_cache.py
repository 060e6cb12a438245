"""JAX's persistent compilation cache, set up once by each entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, is left for JAX to read.  Otherwise
the cache goes to ``<repo>/.jax_cache`` — a fixed path, because the path is
part of the cache key: a directory named after a temp dir, pid or time
would never hit again.  Called from ``main``, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / '.jax_cache'


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env
    jax.config.update('jax_compilation_cache_dir', str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
