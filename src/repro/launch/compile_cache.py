"""JAX's persistent compilation cache for the entry points.

The cache key includes the directory, so the directory must not move from
run to run: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and
nothing here overrides it; otherwise the cache lives at ``.jax_cache`` in
the checkout root (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: src/repro/launch/ -> three levels up
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
