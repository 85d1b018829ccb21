"""Where JAX's persistent compilation cache lives.

Entry points call ``enable_compile_cache()`` at the top of ``main``;
importing a library module never touches the cache.  The directory is
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise the
fixed, git-ignored ``<checkout>/.jax_cache``.  The path is part of the
cache key, so it is never built from a temporary name, a process id or
the time: a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$ENV_VAR``, else
    ``DEFAULT_DIR``, and return that directory."""
    import jax
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
