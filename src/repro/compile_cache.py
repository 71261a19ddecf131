"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the ``benchmarks/`` scripts) call
:func:`enable_compile_cache` once before they compile anything; importing
``repro`` never does, so tests and library users keep JAX's own default.
"""
from __future__ import annotations

import os

#: The checkout root: ``src/repro/compile_cache.py`` -> two levels up.
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    the variable itself, so nothing else is set).  Otherwise the cache
    lives in ``.jax_cache/`` at the checkout root: a fixed path, because
    the path is part of every cache key.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
