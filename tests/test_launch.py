"""Entry-point helpers of ``repro.launch``: the persistent compile cache."""
from __future__ import annotations

import os

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture()
def cache_config():
    """Restore JAX's cache directory after the test (no compile runs in
    between, so nothing is written there)."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_leaves_the_choice_to_jax(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_var_uses_the_fixed_repo_path(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same path on every call: the directory is part of what a later
    # run must find again
    assert compile_cache.enable_compile_cache() == want
