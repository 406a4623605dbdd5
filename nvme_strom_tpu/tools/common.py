"""Shared helpers for the CLI tools (utils/utils_common.h analog)."""

from __future__ import annotations

import os
import sys

__all__ = ["parse_size", "drop_page_cache", "elog"]


def parse_size(s: str) -> int:
    from ..config import _parse_size
    return _parse_size(s)


def drop_page_cache(path: str) -> None:
    """fsync + fadvise(DONTNEED): without the fsync, dirty pages silently
    survive the fadvise and the benchmark measures the page cache."""
    try:
        fd = os.open(path, os.O_RDWR)
    except OSError:
        fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    except OSError:
        pass
    finally:
        os.close(fd)


def elog(msg: str) -> None:
    """Die with a message (the reference's ELOG macro, utils/utils_common.h)."""
    print(f"error: {msg}", file=sys.stderr)
    raise SystemExit(1)


def tool_startup() -> None:
    """What every CLI tool does before its first device query: keep the
    compile cache where :mod:`..compile_cache` says.  The platform comes
    from ``JAX_PLATFORMS`` alone."""
    from ..compile_cache import enable_compile_cache
    enable_compile_cache()
