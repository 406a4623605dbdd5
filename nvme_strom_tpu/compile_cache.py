"""JAX's persistent compilation cache, kept in one place.

Entry points (``chip_smoke.py``, ``bench.py``, ``bench_matrix.py`` and the
CLI tools' startup) call :func:`enable_compile_cache` before their first
compile; importing the library sets nothing.  The directory is part of the
cache's key, so it never contains a temporary directory, a pid or a time.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]

#: the fixed in-repo directory used when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset (listed in .gitignore)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache(env: Optional[dict] = None) -> str:
    """Return the compile-cache directory and make it the one in use.

    A set ``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set (JAX
    reads the variable itself); otherwise the cache goes to
    :data:`REPO_CACHE_DIR`.  With *env* — the environment of child
    processes a launcher is about to start — the choice is written there
    instead, and this process's JAX is left alone."""
    src = os.environ if env is None else env
    got = src.get("JAX_COMPILATION_CACHE_DIR")
    if got:
        return got
    if env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = REPO_CACHE_DIR
        return REPO_CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
