"""Persistent XLA compile cache shared by the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing is changed. Otherwise the cache is ``.jax_cache/`` at the
root of the checkout: a fixed path, so a later process of the same checkout
finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
