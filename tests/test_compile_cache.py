"""Where the entry points put JAX's persistent compilation cache."""

import os

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT, use_compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_used_as_it_stands(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads the env


def test_unset_env_uses_one_fixed_dir_in_the_checkout(monkeypatch,
                                                      cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = use_compile_cache()
    assert path == os.path.join(CHECKOUT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert use_compile_cache() == path                     # no pid, no time
    assert os.path.isfile(os.path.join(CHECKOUT, "chip_smoke.py"))
    with open(os.path.join(CHECKOUT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
