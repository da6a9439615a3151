"""Where jax's persistent compilation cache lives.

A cold TPC-H Q1 on the chip is mostly XLA/Mosaic compile time, and every
process starts with no compiled code unless the persistent cache is on. The
directory is part of the cache's contract with whoever runs the program, so
it is placed from outside: when ``JAX_COMPILATION_CACHE_DIR`` is set jax
reads it itself and this module touches nothing. Otherwise the cache goes to
``<checkout>/.jax_cache`` — a fixed path (the path is part of how a later
process finds the entries; a temp dir, pid or timestamp would never hit).

CPU backends are left uncached: tier-1 runs thousands of tiny CPU compiles
and must not grow the checkout.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The directory the persistent cache uses on an accelerator backend."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


@functools.lru_cache(maxsize=None)
def configure_compile_cache() -> Optional[str]:
    """Called before the first compile on every device path (once per
    process; children that compile call it again on their own first
    compile and resolve the same directory). Returns the directory in use,
    or None when this process compiles for the CPU backend only."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed  # jax reads the variable itself
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only those past jax's 1 s compile-time
    # floor: a program that compiles in 0.9 s one run and 1.1 s the next
    # would otherwise enter the cache on the second run, and "a warm
    # process adds no entries" could not be checked
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
