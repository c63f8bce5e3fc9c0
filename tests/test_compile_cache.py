"""The persistent compile-cache helper (repro.compile_cache)."""
from pathlib import Path

import jax
import pytest

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_honoured(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_dir_is_fixed_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == str(REPO / ".jax_cache")
    assert compile_cache.compile_cache_dir() == first
    assert jax.config.jax_compilation_cache_dir == first


def test_importing_repro_sets_no_cache():
    """Only entry points turn the cache on — never an import: with the
    whole package imported, JAX's setting is still what the environment
    gave it."""
    import os

    import repro.launch.serve  # noqa: F401 — an entry point's module

    env = os.environ.get(compile_cache.ENV_VAR) or None
    assert (jax.config.jax_compilation_cache_dir or None) == env
