"""The compile-cache helper entry points call (``launch/cache.py``)."""
from __future__ import annotations

import jax
import pytest

from repro.launch import cache

KNOBS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restore_config():
    saved = {k: getattr(jax.config, k) for k in KNOBS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_default_dir_is_fixed_under_checkout(monkeypatch, restore_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = cache.enable_compile_cache()
    assert path == str(cache.CHECKOUT / ".jax_cache")
    assert (cache.CHECKOUT / "src" / "repro" / "launch" / "cache.py").exists()
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    assert cache.enable_compile_cache() == path       # stable across calls


def test_environment_dir_is_left_to_jax(monkeypatch, tmp_path,
                                        restore_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
