"""Entry-point plumbing shared by the launchers: the compile cache."""
import pathlib

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_checkout(cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", None)
    got = compile_cache.enable_compile_cache()
    root = pathlib.Path(__file__).resolve().parents[1]
    assert got == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_keeps_configured_dir(cache_dir_config, tmp_path):
    """JAX_COMPILATION_CACHE_DIR lands in jax_compilation_cache_dir at
    import; a directory configured that way is used and nothing else is
    set."""
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
