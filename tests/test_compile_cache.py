"""Placement of JAX's persistent compilation cache by the entry points."""
from pathlib import Path

import jax
import pytest

from repro.launch.cache import REPO_CACHE_DIR, use_compile_cache

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_default_is_fixed_ignored_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = use_compile_cache()
    assert path == str(REPO / ".jax_cache") == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert use_compile_cache() == path                     # stable per call
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
