"""Placement of JAX's persistent compilation cache by the entry points."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_config():
    was = jax.config.values["jax_compilation_cache_dir"]
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_stands(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.values["jax_compilation_cache_dir"]
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.values["jax_compilation_cache_dir"] == before


def test_default_is_checkout_root(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    want = compile_cache.CHECKOUT_ROOT / ".jax_cache"
    assert path == str(want)
    assert (want.parent / "pytest.ini").exists()      # the checkout root
    assert jax.config.values["jax_compilation_cache_dir"] == path
