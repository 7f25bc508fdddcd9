"""Where the entry points put JAX's persistent compilation cache."""
import os

import jax
import pytest

from repro.compile_cache import CHECKOUT_ROOT, enable_compile_cache


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("env", [None, "/srv/jax-cache"])
def test_cache_dir_is_env_or_fixed_checkout_path(monkeypatch,
                                                 cache_dir_config, env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    otherwise the cache sits at ``<checkout>/.jax_cache`` whatever the
    working directory, so a later process finds it again."""
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    monkeypatch.chdir(os.path.dirname(__file__))
    before = jax.config.jax_compilation_cache_dir
    path = enable_compile_cache()
    if env is None:
        assert path == os.path.join(CHECKOUT_ROOT, ".jax_cache")
        assert os.path.isfile(os.path.join(CHECKOUT_ROOT, "chip_smoke.py"))
        assert jax.config.jax_compilation_cache_dir == path
    else:
        assert path == env
        assert jax.config.jax_compilation_cache_dir == before
    assert enable_compile_cache() == path
