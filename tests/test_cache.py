"""The compile-cache rule of the entry points (``repro.cache``)."""
import pathlib

import jax
import pytest

from repro import cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    path = cache.enable_compile_cache()
    assert path == cache.CHECKOUT / ".jax_cache"
    assert (cache.CHECKOUT / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == str(path)
    # a fixed path: the cache key includes it, so it may never move
    assert cache.enable_compile_cache() == path


def test_cache_env_var_stands(monkeypatch, restore_cache_dir, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX's own reading of it
    stands and no code sets another directory."""
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == pathlib.Path(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
