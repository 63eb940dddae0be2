"""Where JAX keeps its persistent compilation cache.

A cold run of the served path compiles a 32-layer trunk per shape bucket,
so entry points (``chip_smoke.py``, benchmarks) call
:func:`enable_compile_cache` once at startup — never at import. The path is
part of the cache's key, so it must not move between runs:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and no
  other cache is set in code;
* otherwise the cache lives at ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``), never at a temporary, pid- or time-derived path.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def compile_cache_dir() -> str:
    """The cache directory this process should use."""
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
