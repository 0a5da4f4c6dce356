"""JAX persistent compilation cache, placed from outside or at a fixed path.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache lives at
``<repo>/.jax_cache``: a fixed path, because the path is part of the cache
key and a moving directory never hits.  Called by the entry points only
(``chip_smoke.py``, ``repro.launch.serve``), never on import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # The served path compiles many sub-second stage programs; JAX's
    # default one-second floor would cache none of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
