"""Where JAX keeps its persistent compilation cache for this repo's entry
points (``chip_smoke.py``, ``benchmarks/run.py``).

A set ``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is configured.
Otherwise the cache lives at a fixed ``.jax_cache/`` in the checkout
(git-ignored): the path is part of a cache entry's key, so a temp-, pid- or
time-derived directory would never be hit again.
"""
from __future__ import annotations

import os

__all__ = ["use_compile_cache"]


def use_compile_cache(root: str) -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env              # JAX reads the variable itself
    import jax
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
