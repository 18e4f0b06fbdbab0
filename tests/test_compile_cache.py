"""The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR says,
else to one fixed, git-ignored directory in the checkout."""

from __future__ import annotations

from pathlib import Path

import jax
import pytest

from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_checkout_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    assert CHECKOUT_CACHE_DIR.parent == REPO
    ignored = (REPO / ".gitignore").read_text().split()
    assert f"{CHECKOUT_CACHE_DIR.name}/" in ignored
