"""The persistent compilation cache: one fixed directory, or the
environment's."""
import os

import jax

from repro.core import jax_cache


def _restore(prev):
    jax.config.update("jax_compilation_cache_dir", prev)


def test_default_dir_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        got = jax_cache.enable_compile_cache()
        assert got == jax_cache.DEFAULT_DIR
        assert os.path.basename(got) == ".jax_cache"
        # the checkout root: the directory that holds src/repro
        assert os.path.isdir(os.path.join(os.path.dirname(got), "src",
                                          "repro"))
        assert jax.config.jax_compilation_cache_dir == got
        assert jax_cache.enable_compile_cache() == got   # same every call
    finally:
        _restore(prev)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert jax_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        _restore(prev)
