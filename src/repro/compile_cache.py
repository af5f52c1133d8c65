"""Where JAX keeps its persistent compilation cache.

One rule for every entry point that compiles for the chip: if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and keeps the
cache there; otherwise the cache goes to ``.jax_cache/`` at the root of
the checkout (git-ignored).  The path is part of the cache key, so it is
fixed — never a temporary, per-process or per-run directory.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory (see
    the module docstring) and return that directory.  Call before the
    first compile."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
