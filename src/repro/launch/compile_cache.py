"""JAX's persistent compilation cache for the repository's entry points.

``chip_smoke.py``, ``python -m repro.launch.serve`` and
``python -m benchmarks.run`` call ``enable_compile_cache()`` at start-up, so
a program compiled once for a shape is read back by the next process.
"""
from __future__ import annotations

import os
import pathlib

#: the checkout's own cache directory (listed in .gitignore); fixed, because
#: the directory is part of every cache key
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it and it is
    left alone; otherwise the cache goes to ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
