"""Where the entry points put JAX's persistent compilation cache."""
import pathlib

import jax
import pytest

from repro.common import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", ["set", "unset"])
def test_compile_cache_dir(monkeypatch, cache_dir_config, env):
    """JAX_COMPILATION_CACHE_DIR wins and code sets nothing; without it the
    cache goes to the fixed, gitignored ``.jax_cache`` of the checkout."""
    before = jax.config.jax_compilation_cache_dir
    if env == "set":
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/jax-cache")
        assert compile_cache.use_compile_cache() == "/elsewhere/jax-cache"
        assert jax.config.jax_compilation_cache_dir == before
    else:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        path = compile_cache.use_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
