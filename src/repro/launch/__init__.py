"""Launchers: production mesh, multi-pod dry-run, training driver."""
from __future__ import annotations

import os

#: the repository root (``src/repro/launch/__init__.py`` -> three up)
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is changed here. Otherwise the cache lives at
    ``<repo>/.jax_cache``: a path fixed per checkout (no temp name, pid or
    time in it), so a second run of the same checkout finds what the first
    one compiled. Entry points call this once, before their first compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
