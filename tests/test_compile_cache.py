"""``repro.compile_cache``: one place decides where compiled programs go."""

from pathlib import Path

import jax

from repro import compile_cache


def test_env_dir_wins_and_nothing_is_set(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compilation_cache()
        second = compile_cache.enable_compilation_cache()
        assert first == second == jax.config.jax_compilation_cache_dir
        checkout = Path(__file__).resolve().parents[1]
        assert first == str(checkout / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
