"""The persistent compilation cache helper called by the entry points."""

from pathlib import Path

import jax
import pytest

from repro.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_in_place(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_default_is_the_fixed_checkout_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert DEFAULT_CACHE_DIR == CHECKOUT / ".jax_cache"
    first = enable_compile_cache()
    assert first == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert enable_compile_cache() == first
