"""Where the persistent compilation cache lives."""
import os
import pathlib

import jax

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_env_var_wins(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    # JAX reads the variable itself: nothing is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_and_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = ROOT / ".jax_cache"
    assert compile_cache.DEFAULT_DIR == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == str(path)
        assert jax.config.jax_compilation_cache_dir == str(path)
        # a second call lands on the same directory
        assert compile_cache.enable_compile_cache() == str(path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert os.path.commonpath([path, ROOT]) == str(ROOT)
