"""Where jax keeps its persistent compilation cache.

Every entry point (the train and serve CLIs, each async-runtime client,
``chip_smoke.py``) calls ``use_persistent_cache()`` before it compiles.
A directory named by ``JAX_COMPILATION_CACHE_DIR`` is read by jax itself
and wins: nothing is set in code then.  Otherwise the cache lives at a
fixed ``<checkout>/.jax_cache`` — the path is part of the cache key, so
a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_persistent_cache() -> str:
    """Point jax at the persistent compilation cache; returns its path.

    Takes effect only before the process's first compile (jax decides
    once whether the cache is in use)."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
