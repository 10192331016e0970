"""JAX's persistent compilation cache, configured in one place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
nothing here overrides it.  Otherwise the cache lives in ``.jax_cache/``
at the root of the checkout (listed in ``.gitignore``).  The path is fixed
on purpose: it is part of what a later process looks up, so a directory
named after a temp dir, a pid or the time would never be hit again.

Entry points that compile for the chip (``chip_smoke.py``,
``benchmarks/run.py``) call ``enable_compile_cache()`` before their first
compile.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
