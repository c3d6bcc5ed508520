"""JAX's persistent compilation cache for the entry points of this repo."""

from __future__ import annotations

import os
from pathlib import Path

# fixed, inside the checkout (git-ignored): the cache is found again only
# at the same path
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across processes and runs. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there and no
    other directory is set; otherwise the cache goes to ``DEFAULT_DIR``.
    Every compile is cached, however short. Returns the directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
