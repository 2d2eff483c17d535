"""Where JAX keeps its persistent compilation cache.

One owner for the placement: every entry point (App, the bridge worker,
bench.py's measurement child, chip_smoke.py, the test suite) calls
`enable_compile_cache()` before its first compile.  An operator or the
chip machine places the cache from outside with
$JAX_COMPILATION_CACHE_DIR, which JAX reads itself — then nothing here
is set.  Otherwise the cache lives at a fixed path inside the checkout
(git-ignored), so a rerun from the same checkout finds its programs
again: the path is part of the cache key's context, and a directory
that moved would never hit.
"""

from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place; returns
    the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
