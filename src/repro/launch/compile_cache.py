"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/serving_load.py``) call :func:`enable` once, before their first
compile.  Tests and library imports never do: a cache is a deployment
setting, not a property of the code.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"

# The checkout root (this file is <root>/src/repro/launch/compile_cache.py).
# The directory is fixed: the cache is keyed by what is compiled, so a path
# that moved between runs would never be hit.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself and
    nothing here overrides it), else ``<checkout>/.jax_cache``.  Every
    compile is cached, however short."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
