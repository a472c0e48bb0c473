"""JAX's persistent compilation cache at one fixed place.

A 28-layer model's programs take minutes to compile; the cache lets a later
process (or a later phase of the same one) load them instead.  Its
directory is part of each entry's lookup, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads that
variable itself), otherwise ``<checkout>/.jax_cache``, which ``.gitignore``
lists.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    CHECKOUT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
