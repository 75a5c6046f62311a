"""Persistent XLA compilation cache for the repo's entry points.

A cold process compiles the encoder, every serving shape bucket and
every Pallas kernel from scratch; JAX's persistent cache makes the next
process on the same machine load them instead. The cache key includes
the directory, so the directory must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set -> JAX reads it itself and this
    helper changes nothing;
  * otherwise -> ``<checkout>/.jax_cache`` (git-ignored), and every
    executable is cached however fast it compiled (the kernels compile
    in well under JAX's default one-second threshold).

Call :func:`enable_compile_cache` once, before the first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)
