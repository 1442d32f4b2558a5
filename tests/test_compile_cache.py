"""The compile-cache helper the entry points call: a directory placed from
outside is left to JAX, and otherwise the cache sits at one fixed path in
the checkout that git ignores."""
from pathlib import Path

import jax
import pytest

from repro import compile_cache

REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_placed_cache_dir_is_left_alone(monkeypatch, restore_cache_dir,
                                        tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unplaced_cache_dir_is_fixed_and_ignored(monkeypatch,
                                                 restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first
    assert Path(first) == REPO_ROOT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (REPO_ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
