"""Where JAX keeps its persistent compilation cache.

Entry points (the ``serve`` CLI, ``benchmarks/run.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` once at start-up; importing a module never
touches the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout root (this file is src/repro/launch/compile_cache.py).
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is used as it is: JAX reads the
    variable itself, so no path is set in code.  Otherwise the cache lives
    in ``<checkout>/.jax_cache``.  The directory is part of what a later run
    must find again, so it never depends on a temp name, a pid or the time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
