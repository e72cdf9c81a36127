"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself),
and otherwise the cache lives at a fixed, gitignored path in the checkout.
Entry points call ``use_compile_cache()``; importing this module changes
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = (Path(__file__).resolve().parents[3]
                      / "benchmarks" / "artifacts" / "jax_cache")


def use_compile_cache() -> str:
    """Point JAX's compilation cache at its directory and return it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        CHECKOUT_CACHE_DIR.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
