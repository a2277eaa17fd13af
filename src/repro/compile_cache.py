"""JAX's persistent compilation cache, placed from outside or at a fixed
path in the checkout.

The cache key includes the cache's directory, so the directory never
moves: when `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it and nothing
is set here; otherwise the cache lives at `<checkout>/.jax_cache`.
Entry points (`chip_smoke.py`, `python -m repro.serve`,
`python -m repro.portal`) call `use_compile_cache()` before their first
compile; the tests do not.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
