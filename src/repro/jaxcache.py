"""Where JAX keeps its persistent compilation cache.

Every entry point (``rdfize``, ``serve``, ``query``, ``benchmarks/run.py``,
``chip_smoke.py``) calls :func:`enable_compile_cache` before its first
compile, so their processes share compiled programs across runs.
"""

from __future__ import annotations

import os
import pathlib

# <checkout>/src/repro/jaxcache.py -> <checkout>
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache, and JAX reads
    it itself: nothing else is set here.  Otherwise the cache is
    ``<checkout>/.jax_cache`` — a fixed path, never a temporary, pid or
    time-stamped one, because a directory that moves never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
