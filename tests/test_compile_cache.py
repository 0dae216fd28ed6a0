"""init_compile_cache: every JAX entry point keeps its persistent
compilation cache where JAX_COMPILATION_CACHE_DIR says, else in one fixed
directory of the checkout."""

import os

import pytest

import sheep_tpu
from sheep_tpu.cli.common import init_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(sheep_tpu.__file__)))


@pytest.fixture
def cache_config():
    import jax
    prior = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", prior)


def test_env_dir_is_used_and_nothing_else_is_set(cache_config, monkeypatch,
                                                 tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cache_config.update("jax_compilation_cache_dir", None)
    assert init_compile_cache() == str(tmp_path)
    assert cache_config.jax_compilation_cache_dir is None


def test_unset_env_uses_the_fixed_checkout_dir(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = init_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert cache_config.jax_compilation_cache_dir == path
    assert init_compile_cache() == path  # fixed: the same on every call
