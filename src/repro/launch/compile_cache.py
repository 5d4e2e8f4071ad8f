"""Where JAX keeps its persistent compilation cache.

A cold process recompiles every step program (a full-width decode step
takes tens of seconds), so each entry point that serves or measures —
``launch/serve.py``, ``benchmarks/run.py``, ``chip_smoke.py`` — calls
``place_compile_cache()`` once, before its first compile.  Tests never
call it.
"""
from __future__ import annotations

import os
import pathlib

import jax

# a fixed path: the directory is part of what a cache entry is found
# by, so a path built from a temp name, a pid or the time never hits
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Return the directory JAX's persistent compilation cache uses.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself
    and nothing is set here.  Otherwise the cache goes to ``.jax_cache``
    at the root of the checkout (git ignores it)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
