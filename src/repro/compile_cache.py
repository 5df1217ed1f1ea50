"""Where compiled programs are kept between runs.

A chip run compiles the service's power-of-two buckets, the chunked
fused solve and the scan-engine sweep.  JAX's persistent compilation
cache lets the next process in the same checkout load them instead.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set.  A
#: fixed path: the cache is keyed by it, so a moving path never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is used as it is (JAX reads
    it itself); otherwise the cache lives in ``<repo>/.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path


@contextlib.contextmanager
def compile_cache_off():
    """Compile from scratch inside the block, whatever the cache holds:
    for timing a compile, or for a compile whose target is not attached
    (it could be written to the cache but never read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    # the cache decides once per process whether it is in use
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        with contextlib.suppress(Exception):
            compilation_cache.reset_cache()
