"""The persistent compilation cache goes to $JAX_COMPILATION_CACHE_DIR when
it is set, and otherwise to one fixed directory inside the checkout."""
import os

import jax
import pytest

from repro.launch import compile_cache

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_used_as_is(monkeypatch, tmp_path, restore_cache_dir):
    want = str(tmp_path / "xla-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    assert compile_cache.enable_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_default_is_fixed_inside_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compilation_cache()
    assert first == os.path.join(CHECKOUT, ".jax_cache")
    assert compile_cache.enable_compilation_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
