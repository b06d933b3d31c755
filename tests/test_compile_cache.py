"""The entry points' compile-cache helper: JAX_COMPILATION_CACHE_DIR wins
when set; otherwise one fixed, git-ignored directory inside the checkout."""
import os

import jax

from repro.launch import compile_cache


def test_fixed_checkout_dir_when_env_unset(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path   # no drift
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = compile_cache.CHECKOUT_CACHE_DIR.parent
    assert (root / "chip_smoke.py").exists()
    ignored = (root / ".gitignore").read_text().split()
    assert compile_cache.CHECKOUT_CACHE_DIR.name + "/" in ignored


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
