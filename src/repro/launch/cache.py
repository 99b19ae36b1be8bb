"""Persistent XLA compilation cache placement for the entry points.

Every entry point that compiles real programs (``chip_smoke.py``, the serve
and train launchers, ``benchmarks/run.py``) calls :func:`use_compile_cache`
first, so processes that run the same programs share compiled executables
instead of recompiling them.  It is not called at ``import repro``: a
library import must not change the process's JAX configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The fixed cache directory used when the environment names none.  The
#: path is part of what the cache is keyed on, so it never holds a temp
#: name, a process id or the time — a directory that moves never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory and
    return it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it; nothing is changed.  Otherwise the cache goes to the
    git-ignored ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
