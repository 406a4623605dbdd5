"""Read-ahead for the buffered (VFS) scan: one reader thread fills a ring
of reused, page-aligned host buffers with the table's page batches in
order, while the caller transfers and dispatches the batch before.

The caller iterates ``(slot, pages)``, puts *pages* on the device, and
hands the device array back with :meth:`ReadAheadRing.recycle`; the
reader refills that slot only once the array is ready, so an in-flight
host->device read never sees the next batch's bytes.  ``preadv``
releases the interpreter lock, so the read of the next batches overlaps
the caller's ``device_put``, kernel call and fold.

Stages: the caller's wait for its batch is ``vfs_read`` (one entry per
batch; ``nr_vfs_read_blocked`` counts the waits whose batch was not
read yet on entry), the reader's reads are ``vfs_readahead`` on the
reader's own host line, and ``nr_vfs_readahead_fence_blocked`` counts
refills that waited on a transfer."""

from __future__ import annotations

import mmap
import queue
import threading
from typing import Iterator, Optional, Tuple

import numpy as np

from ..hbm.staging import _pending, bounded_fence
from ..stats import stats
from .heap import PAGE_SIZE

__all__ = ["ReadAheadRing"]


class ReadAheadRing:
    """Page batches of *src*, from page *first_page* to its end, read
    ahead into *depth* reused buffers.

    Use as a context manager: leaving it stops the reader and joins it,
    on every path, so the caller may close *src* right after.  A read
    error is raised in the caller at the batch it belongs to."""

    def __init__(self, src, batch_pages: int, depth: int, *,
                 first_page: int = 0):
        self._src = src
        self._first = int(first_page)
        self._n_pages = src.size // PAGE_SIZE
        self._batch = max(int(batch_pages), 1)
        self._n_batches = max(-(-(self._n_pages - self._first)
                                // self._batch), 0)
        self._depth = max(1, min(int(depth), self._n_batches))
        self._slot_bytes = self._batch * PAGE_SIZE
        self._mm: Optional[mmap.mmap] = None
        # (slot, device array made from it or None), and what the
        # reader hands over: (slot, pages), an exception, in batch order
        self._free: queue.SimpleQueue = queue.SimpleQueue()
        self._ready: queue.SimpleQueue = queue.SimpleQueue()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def __enter__(self) -> "ReadAheadRing":
        if self._n_batches:
            # anonymous mmap: page-aligned, so an accelerator's
            # device_put can DMA straight from it; faulted in by the
            # reader's first fill, off the caller's thread
            self._mm = mmap.mmap(-1, self._depth * self._slot_bytes,
                                 flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS)
            for slot in range(self._depth):
                self._free.put((slot, None))
            self._thread = threading.Thread(
                target=self._read_loop, name="strom-vfs-readahead",
                daemon=True)
            self._thread.start()
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def close(self) -> None:
        """Stop the reader and join it (a read in progress finishes)."""
        t, self._thread = self._thread, None
        if t is not None:
            self._stop.set()
            self._free.put((None, None))     # wakes a reader waiting a slot
            t.join()
        if self._mm is not None:
            try:
                self._mm.close()
            except BufferError:
                # a view is still exported (a transfer's source or the
                # caller's last batch): the mapping goes when it dies
                pass
            self._mm = None

    def _read_loop(self) -> None:
        src, bb = self._src, self._slot_bytes
        mv = memoryview(self._mm)
        try:
            for p0 in range(self._first, self._n_pages, self._batch):
                slot, held = self._free.get()
                if self._stop.is_set():
                    return
                if held is not None:
                    if _pending(held):
                        stats.add("nr_vfs_readahead_fence_blocked")
                    bounded_fence(held, "scan-readahead")
                n = min(self._batch, self._n_pages - p0)
                off = slot * bb
                with stats.stage("vfs_readahead"):
                    src.read_buffered(p0 * PAGE_SIZE,
                                      mv[off:off + n * PAGE_SIZE])
                self._ready.put((slot, n))
        except BaseException as e:  # noqa: BLE001 - raised in the caller
            self._ready.put(e)
        finally:
            mv.release()

    def __iter__(self) -> Iterator[Tuple[int, np.ndarray]]:
        """``(slot, pages)`` per batch, in order: *pages* is a
        ``(n, PAGE_SIZE)`` uint8 view of the slot, valid until the slot
        is recycled."""
        for _ in range(self._n_batches):
            blocked = self._ready.empty()
            with stats.stage("vfs_read"):
                got = self._ready.get()
                if isinstance(got, BaseException):
                    raise got
            if blocked:
                stats.add("nr_vfs_read_blocked")
            slot, n = got
            yield slot, np.frombuffer(
                self._mm, np.uint8, n * PAGE_SIZE,
                slot * self._slot_bytes).reshape(n, PAGE_SIZE)

    def recycle(self, slot: int, dev_pages) -> None:
        """Give *slot* back; the reader refills it once *dev_pages* (the
        device array made from it) is ready."""
        self._free.put((slot, dev_pages))
