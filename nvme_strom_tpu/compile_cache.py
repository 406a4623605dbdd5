"""JAX's persistent compilation cache, kept in one place.

Entry points (``chip_smoke.py``, ``benchmark/harness.py`` and the CLI
tools' startup) call :func:`enable_compile_cache` before their first
compile; importing the library sets nothing.  The directory is part of the
cache's key, so it never contains a temporary directory, a pid or a time.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]

#: the fixed in-repo directory used when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset (listed in .gitignore)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache() -> str:
    """Return the compile-cache directory and make it the one in use.

    A set ``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set (JAX
    reads the variable itself); otherwise the cache goes to
    :data:`REPO_CACHE_DIR`."""
    got = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if got:
        return got
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
