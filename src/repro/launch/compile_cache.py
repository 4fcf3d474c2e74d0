"""Persistent compilation cache for the entry points.

Called at the start of ``main`` by every script that compiles (the training
launcher, the examples, the benchmarks and ``chip_smoke.py``), never at
import.  A 32-layer step program takes tens of seconds to compile; with the
cache a second process that builds the same program loads it instead.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed directory inside the checkout: the same path in every process, so a
# later run finds what an earlier one stored.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing here overrides it; otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
