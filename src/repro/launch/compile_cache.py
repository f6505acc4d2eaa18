"""JAX's persistent compilation cache for the launchers.

Called from each entry point's ``main`` (never at import, never from
tests).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
it and nothing here overrides it.  Otherwise the cache lives at the fixed
``<repo>/.jax_cache``: the directory is part of the cache key, so a
per-run or temporary name would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
