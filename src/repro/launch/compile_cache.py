"""Where JAX keeps its persistent compilation cache, for every entry point.

The cache key includes the directory, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` wins where it is set (JAX reads it itself),
and otherwise the cache lives at the fixed ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Call once, before the first compile."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
