"""enable_compile_cache: JAX_COMPILATION_CACHE_DIR wins when set;
otherwise the cache goes to one fixed directory at the repository root,
the same on every call."""
import pathlib

import jax
import pytest

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def jax_cache_dir(monkeypatch):
    """JAX's cache directory setting, restored after the test."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_honoured_and_nothing_is_set(jax_cache_dir, monkeypatch,
                                                tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_fallback_is_the_repo_root_jax_cache(jax_cache_dir):
    want = str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_second_call_keeps_the_same_path(jax_cache_dir):
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
