"""Persistent XLA compile cache for the entry points.

Scripts, benchmarks and examples call :func:`enable` once at start-up,
before their first compile; importing the library never sets a cache.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: a fixed path, because the cache key includes it
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compile cache on and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it
    itself, and no other directory is set.  Otherwise the cache lives in
    ``<checkout>/.jax_cache`` (git-ignored).  Also keeps libtpu from
    writing log files (``TPU_LOG_DIR``, unless already set).
    """
    # libtpu would log to /tmp/tpu_logs, outside the checkout; it reads
    # this when the backend starts, which is after this call
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
