"""Where the process keeps JAX's persistent compilation cache.

Called from process entry points only (the server's ``main()``,
``chip_smoke.py``, ``benchmark/run.py``) — never from
``connect()`` or at import, so embedding programs and tests keep whatever
they configured. The directory is part of the cache key, so it is a fixed
path: never a temporary name, a pid or a time.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """-> the directory in use. Where ``JAX_COMPILATION_CACHE_DIR`` is set
    JAX reads it itself and nothing is set here; otherwise the cache lives
    in ``<repo>/.jax_cache``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
