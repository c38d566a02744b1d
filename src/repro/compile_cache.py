"""Where JAX's persistent compilation cache lives for this checkout."""
from __future__ import annotations

import os
import pathlib

#: root of the checkout this package runs from (``src/repro/..``)
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Give JAX's persistent compilation cache a fixed home; return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is done.  Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout, the directory the tests use.  The path never
    comes from a temporary name, a process id or the clock: a cache that
    moves between runs is never found again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
