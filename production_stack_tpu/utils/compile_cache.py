"""Where JAX's persistent compilation cache lives.

Called from the entry points only (the engine server's ``main``,
``chip_smoke.py``, ``bench.py``) and never at import: a library that
picked a cache directory on import would fight whoever runs it.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache (git-ignored). The path is part of the cache's
# key, so it is fixed: no temporary name, pid or time in it.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def configure_compile_cache() -> str:
    """Place the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and nothing is set here; where it is not, the cache is
    :data:`CHECKOUT_CACHE_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
