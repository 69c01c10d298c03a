"""JAX's persistent compilation cache, kept in one fixed place.

Entry points that run on an accelerator (``chip_smoke.py``,
``benchmarks.common.bench_main``) call ``enable_compile_cache()`` before
their first compile; importing the library never does.  The cache key
includes the directory, so the directory never moves between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing is
  set here;
* otherwise: ``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
