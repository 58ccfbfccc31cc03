"""JAX persistent compilation cache, placed by the entry point.

Entry points (`chip_smoke.py`, the benchmarks, the examples) call
`configure_compile_cache` once before their first compile; importing the
library never turns the cache on, so the test suite compiles as before.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it by itself and this
sets nothing.  Otherwise the cache lives at `.jax_cache` in the root of
the checkout this package sits in (gitignored).  The directory is part of
the cache's key, so it is a fixed path, never derived from a temporary
name, a process id or the time.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
