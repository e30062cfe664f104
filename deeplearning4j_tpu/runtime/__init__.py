"""Runtime services: metrics, checkpointing, console, compile engine.

:func:`ensure_compile_cache` is the ONE place in the tree that names a
persistent XLA compile-cache directory.  The directory is placed from
outside with JAX's own ``JAX_COMPILATION_CACHE_DIR``; when that is unset
the cache lives at ``<checkout>/.jax_cache``.  The path is part of the
cache key, so it is derived from this package's location and from
nothing that moves between runs (home directory, temporary names, pids,
the clock).  Entry points (``chip_smoke.py``, ``benchmark/run.py``, the
CLI) call it before their first compile; importing this package touches
no JAX configuration.  The minimum compile time worth persisting is
JAX's ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``.

This is the cross-PROCESS analog of the in-process cross-network cache
in ``runtime/compile_cache.py``.
"""

from __future__ import annotations

import os

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — the parent of the package directory
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Make sure this process has a persistent compile cache and return
    its directory.  Call before the first compile: JAX decides once per
    process whether the cache is in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX has already read it, nothing
    is touched.  Unset: ``jax_compilation_cache_dir`` is pointed at
    :data:`DEFAULT_COMPILE_CACHE_DIR`."""
    placed = os.environ.get(COMPILE_CACHE_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
