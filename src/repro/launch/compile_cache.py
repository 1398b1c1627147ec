"""Where JAX's persistent compilation cache lives.

Entry points call ``use_compile_cache()`` before their first compile. A
cache directory that moves never hits, so the default is one fixed,
git-ignored directory inside the checkout. When the environment sets
``JAX_COMPILATION_CACHE_DIR``, JAX reads it itself and nothing is set here.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
