"""The persistent compilation cache is placed from outside the program:
``JAX_COMPILATION_CACHE_DIR`` where it is set, else one fixed directory in
the checkout (never a temporary, pid- or time-derived path)."""
import os

import jax

from repro.runtime import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_var_is_honoured(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: nothing else is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.compile_cache_dir()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert compile_cache.compile_cache_dir() == path
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
