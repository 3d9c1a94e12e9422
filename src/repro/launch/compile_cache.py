"""JAX's persistent compilation cache, kept where the next run finds it.

Compiling the train step at paper sizes takes minutes; a cache a later
process can read turns that into seconds. A cache hit needs the same
directory, so the path is fixed and never a temporary one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/experiments/jax_cache (gitignored); this file is src/repro/launch/
DEFAULT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    leaves it alone. Otherwise the cache goes to ``experiments/jax_cache``
    inside the checkout.
    """
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
