"""Where JAX keeps its persistent compilation cache.

Entry points that run on the chip (``chip_smoke.py``, ``python -m
benchmarks.run``) call :func:`use_compile_cache` once, before their first
compile; importing ``repro`` never touches the cache.  The directory is
part of the cache key, so it is either the one ``JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself, and nothing here overrides it) or
one fixed path inside the checkout, :data:`REPO_CACHE_DIR` (ignored by
git) — never a temporary or per-process directory.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Every compile is cached, however short: a net runs one small Pallas
    kernel per op, each below JAX's default one-second threshold."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
