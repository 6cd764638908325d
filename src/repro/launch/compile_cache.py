"""Persistent compilation cache for the entry points.

:func:`enable_compile_cache` runs before an entry point's first compile
(``chip_smoke.py``, ``serve``, ``serve_async``, ``serve_http`` and
``benchmarks/serving.py``); nothing calls it at import time, and the
tests never do.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here touches the setting.  Otherwise the cache lives in one
fixed directory inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``): the directory is part of what lets a later run find
an entry, so it never depends on a temporary name, a PID or the time.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
