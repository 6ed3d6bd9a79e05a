"""JAX's persistent compilation cache for the entry points.

Called by ``chip_smoke.py``, ``python -m repro.launch.serve`` and
``python -m benchmarks.run`` before their first compile; never on import.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing here picks another directory.  Otherwise the cache is the
fixed ``<checkout>/.jax_cache``: the directory is part of the cache key, so
a path that moved between runs would never hit.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "DEFAULT_DIR"]

DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # the chess_hvp kernels compile in about a second: cache every program,
    # not only the ones over jax's default one-second floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
