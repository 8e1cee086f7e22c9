"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``python -m repro.serve``, the bench
drivers) call :func:`setup_compile_cache` once at start-up, before their
first compile; the library and the tests never do. A fresh process then
reads back what an earlier process on the same machine compiled instead of
paying XLA again for every program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and this
module changes nothing. Otherwise the cache lives at ``<repo>/.jax_cache``:
a fixed path, because the directory is part of where entries are found, so
a path made from a temp name, a pid or a time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its
    directory — ``$JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<repo>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
