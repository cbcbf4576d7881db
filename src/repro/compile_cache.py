"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
``use_compile_cache()`` once, before their first compile:

  * ``JAX_COMPILATION_CACHE_DIR`` set -> JAX reads it itself; nothing is set
    in code, so a machine that provides a cache keeps it;
  * otherwise the cache goes to ``.jax_cache/`` at the root of the checkout.
    The path is fixed -- never a temporary name, process id or time -- since
    it is part of the cache key, and a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
