"""JAX's persistent compilation cache for the repository's entry points.

A cold process compiles every phase program again, and at deployment size
that is minutes (each pool capacity recompiles the whole phase set).  The
cache lets the next process load those programs instead.  Its path is part
of every entry's key, so it must not move between runs: the directory is
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, and otherwise
the fixed ``<checkout>/.jax_cache`` (gitignored).  No other cache location
is ever set.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def use_persistent_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory.  Call before the first compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT, ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path
