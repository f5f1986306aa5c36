"""The entry points' persistent compile cache: the environment's directory
wins, and otherwise the cache sits at one fixed path inside the checkout."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache


@pytest.fixture
def cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
    cc.reset_cache()


def test_env_dir_is_left_alone(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_fixed_repo_path(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = use_compile_cache()
    assert use_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    assert Path(first) == REPO_CACHE_DIR
    assert REPO_CACHE_DIR.parent == Path(__file__).resolve().parents[1]
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
