"""Where ``use_compile_cache`` puts JAX's persistent compilation cache."""
from pathlib import Path

import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_variable_wins_and_nothing_is_set(monkeypatch, cache_config,
                                              tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_directory_in_the_checkout(monkeypatch,
                                                      cache_config):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    root = Path(__file__).resolve().parents[1]
    got = compile_cache.use_compile_cache()
    assert got == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.use_compile_cache() == got      # stable per call
