"""Where the entry points keep JAX's persistent compilation cache.

A machine that starts cold compiles everything again unless compiled programs are
kept on disk. The entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m benchmarks.run`` and the examples that compile) call
:func:`enable_compile_cache` once at start-up. The cache then lives in the directory
``JAX_COMPILATION_CACHE_DIR`` names, when that is set, and otherwise at one fixed
path, ``.jax_cache`` at the root of the checkout: a directory that moves never hits.
Library code and tests never turn the cache on.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path
from typing import Iterator

import jax
from jax.experimental.compilation_cache import compilation_cache

#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else :data:`REPO_CACHE_DIR`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on at :func:`cache_dir`; returns
    the directory."""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def persistent_cache_off() -> Iterator[None]:
    """Compile from scratch inside the block: the persistent cache is neither
    read nor written. JAX memoises whether the cache is in use, so the switch
    resets that memo on the way in and on the way out."""
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()
