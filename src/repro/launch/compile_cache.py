"""JAX's persistent compilation cache, configured in one place.

The entry points that compile full-size programs (``serve.main``,
``train.main`` and ``chip_smoke.py``) call :func:`enable` before their
first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory
is the cache and no other is set.  Otherwise the cache lives at the fixed
path ``<repo root>/.jax_cache`` (listed in ``.gitignore``): the path is
part of what a later run looks up, so it never depends on a temporary
name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
