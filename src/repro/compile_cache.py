"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points that compile (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first jit.  A cache is keyed
by its path among other things, so the path is fixed: the directory
``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads the
variable itself, and nothing is set in code), else ``.jax_cache/`` at
the root of the checkout.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
