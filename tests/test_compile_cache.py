"""The persistent compile cache has exactly one home per run."""

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_in_repo_dir_without_env(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == str(compile_cache.REPO_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable() == path  # the same path every call


def test_the_key_holds_operation_metadata(monkeypatch, cache_dir_config):
    """Stage names live in metadata: a cached executable of a build that
    named other stages must not be served."""
    before = jax.config.jax_compilation_cache_include_metadata_in_key
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    try:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        compile_cache.enable()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          before)
