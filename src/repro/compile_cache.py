"""Where JAX's persistent compilation cache lives.

The path is part of the cache key, so it must not move between runs: a
temp, pid- or time-derived directory would never hit. Entry points call
``enable_compile_cache()`` in ``main``; nothing calls it at import time.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory. When
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    is set here; otherwise the cache goes to the fixed ``<repo>/.jax_cache``
    (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
