"""JAX's persistent compilation cache, placed from outside or at one fixed
path — the only place in the tree that sets it.

Every frame-geometry shape is a compile of seconds on the chip, and a
sealed machine starts cold. Whoever runs the program places the cache by
setting ``JAX_COMPILATION_CACHE_DIR`` (JAX reads the variable itself, so
nothing is set in code); unset, the cache is ``.jax_cache`` at the root of
the checkout — git-ignored, and a fixed path because the path is part of
the cache's key.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/.jax_cache (this file is gome_tpu/utils/jaxcache.py).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on (call before the first
    compile) and return the directory in use."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def cache_entries(path: str) -> int:
    """Executables cached under `path`: 0 means the next compiles are cold."""
    try:
        return sum(1 for e in os.scandir(path) if e.is_file())
    except FileNotFoundError:
        return 0
