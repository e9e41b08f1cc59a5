"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry-point scripts (``chip_smoke.py``, ``benchmarks/run.py``, the
examples) call :func:`enable_compile_cache` once, before their first
compile; nothing calls it on import. The cache's path is part of what
JAX keys an entry on, so it is never built from a temporary name, a pid
or the time: a cache that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/compile_cache.py → the checkout root
CHECKOUT = Path(__file__).resolve().parents[2]


def compile_cache_dir(environ=os.environ) -> tuple[str, bool]:
    """``(directory, from_env)``: ``$JAX_COMPILATION_CACHE_DIR`` when set
    (JAX reads it itself), else ``<checkout>/.jax_cache``."""
    env = environ.get(ENV_VAR)
    if env:
        return env, True
    return str(CHECKOUT / ".jax_cache"), False


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. When
    ``$JAX_COMPILATION_CACHE_DIR`` is set this leaves everything to JAX
    and sets nothing; otherwise it points ``jax_compilation_cache_dir``
    at ``<checkout>/.jax_cache``."""
    path, from_env = compile_cache_dir()
    if not from_env:
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
