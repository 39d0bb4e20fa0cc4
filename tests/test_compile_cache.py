"""Where entry points put JAX's persistent compilation cache."""
import os

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT, use_compile_cache


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_dir", [None, "/srv/jax-cache"])
def test_use_compile_cache(env_dir, cache_config, monkeypatch):
    """Set: the environment's directory is used and nothing is changed.
    Unset: a fixed directory inside the checkout."""
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = use_compile_cache()
        assert path == os.path.join(CHECKOUT, ".jax_cache")
        assert os.path.isfile(os.path.join(CHECKOUT, "pyproject.toml"))
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache() == path
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert use_compile_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir == before
