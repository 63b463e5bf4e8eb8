"""The persistent compilation cache the entry points turn on."""
import pathlib

import jax

from repro import jaxcache


def _restore(prev):
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[1])


def test_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert jaxcache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        _restore(prev)


def test_cache_dir_defaults_to_the_checkout(monkeypatch):
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = jaxcache.enable_compile_cache()
        assert path == str(jaxcache.DEFAULT_DIR)
        checkout = pathlib.Path(__file__).resolve().parents[1]
        assert jaxcache.DEFAULT_DIR == checkout / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        _restore(prev)
