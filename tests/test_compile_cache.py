"""The entry points' persistent-compilation-cache helper."""
from __future__ import annotations

import importlib
from pathlib import Path

import jax
import pytest

from repro.runtime import compile_cache

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_enable_uses_env_dir_when_set(monkeypatch, tmp_path,
                                      restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_enable_default_is_one_fixed_dir_in_checkout(monkeypatch,
                                                     restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable()
    second = compile_cache.enable()
    assert first == second == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_import_sets_nothing(restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    importlib.reload(compile_cache)
    assert jax.config.jax_compilation_cache_dir is None
