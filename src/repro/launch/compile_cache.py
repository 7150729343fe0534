"""JAX's persistent compilation cache, placed from outside or in the checkout.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory: JAX reads the
variable itself and nothing here overrides it.  Otherwise the cache lives
in one fixed, git-ignored directory of the checkout, so that a later run
finds what an earlier one cached: it never comes from a temp name, a pid
or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
