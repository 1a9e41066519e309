"""JAX's persistent compilation cache, turned on by every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: JAX reads
it, and nothing here overrides it. Otherwise the cache lives at a fixed
path inside the checkout (``<repo>/.jax_cache``, ignored by git). The
directory is part of what makes a later run find an entry, so it is never
a temporary or per-process path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
