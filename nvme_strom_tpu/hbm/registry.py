"""Device (HBM) memory registration.

Capability analog of the reference's GPU memory mapper (``MAP_GPU_MEMORY``
et al., `kmod/pmemmap.c:19-495`): pinning CUDA device memory for third-party
DMA, a refcounted 64-slot handle table, UID ownership checks, and a
driver-initiated revocation callback that blocks until in-flight DMA drains.

On TPU there is no BAR1 to pin — device buffers live behind PJRT and XLA
arrays are immutable.  The idiomatic equivalent is a *mutable holder* of a
``jax.Array`` destination: registration creates (or adopts) a device array,
hands out an integer handle, refcounts in-flight transfers against it, and
supports revocation (``unmap``) that blocks until transfers drain — the same
lifecycle contract, with functional array updates (donated buffers) standing
in for writes to mapped memory.
"""

from __future__ import annotations

import errno as _errno
import os
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..api import BufferInfo, StromError

__all__ = ["HbmBuffer", "HbmRegistry", "LandingBuffer", "registry"]

# TPU page granularity reported in INFO; purely informational here (the
# reference decodes 4K/64K/128K GPU page sizes, kmod/pmemmap.c:264-282)
_DEVICE_PAGE = 4096


class LandingBuffer:
    """Owned, page-aligned destination buffer for zero-copy landing.

    The ownership split the staging ring cannot express (LMB's buffer-
    ownership motivation, PAPERS.md arXiv:2406.02039): the ring's slots
    are REUSED, so its bytes must be copied off before the next SSD DMA;
    a LandingBuffer belongs to exactly one destination, so the engine's
    O_DIRECT/io_uring reads land here and the device array is an ALIAS
    of this memory — the TPU analog of the reference mapping BAR1 pages
    into the SSD's PRP lists (`kmod/pmemmap.c`).

    Allocation rides the session's DmaBuffer machinery, so the buffer is
    pinned, registered as an io_uring fixed buffer, and — because fixed
    registrations are carried per DmaBuffer — RE-registered on the new
    engine whenever a lane rebuild swaps engines mid-task.  ``release()``
    detaches it from the session; the underlying mmap defers its munmap
    until the last adopting array drops its buffer-protocol reference
    (``DmaBuffer.close`` tolerates ``BufferError`` for exactly this), so
    an :class:`HbmBuffer` holding an adopted alias keeps the memory
    alive for as long as the array is reachable."""

    def __init__(self, session, nbytes: int):
        if nbytes <= 0:
            raise StromError(_errno.EINVAL,
                             "landing buffer size must be positive")
        self.nbytes = int(nbytes)
        self._session = session
        self.handle, self._dma = session.alloc_dma_buffer(self.nbytes)
        self._released = False

    def view(self) -> memoryview:
        return self._dma.view()[:self.nbytes]

    def adopt_array(self, dtype, device) -> jax.Array:
        """The landed bytes as a device array ALIASING this buffer where
        the backend zero-copies (CPU), else as a device copy."""
        from .backend import aliased_device_put
        host = np.frombuffer(self.view(), dtype=dtype)
        return aliased_device_put(host, device)

    def release(self) -> None:
        """Unmap from the session and drop the pinned mapping.  Safe to
        call while adopted arrays are alive: fixed-buffer unregistration
        and munlock run now; the munmap itself defers to the arrays'
        refcount.  Idempotent."""
        if self._released:
            return
        self._released = True
        try:
            self._session.unmap_buffer(self.handle)
        except StromError:
            pass        # session already closed / handle already gone
        self._dma.close()


class HbmBuffer:
    """Mutable holder for a device-resident destination array."""

    def __init__(self, handle: int, array: jax.Array, owner_uid: int):
        self.handle = handle
        self._array = array
        self.owner_uid = owner_uid
        self.refcount = 0
        self.revoke_reason: Optional[str] = None   # set by revoke_all
        self._lock = threading.Lock()
        # Signalled whenever refcount drops; unmap() waits on it instead of
        # polling (same CV drain Session.unmap_buffer uses in engine.py).
        self._drained = threading.Condition(self._lock)
        self._revoked = False
        # LandingBuffer the current array aliases (zero-copy landing);
        # owned by this holder once adopted, released on unmap/revoke
        self._landing: Optional[LandingBuffer] = None

    @property
    def array(self) -> jax.Array:
        with self._lock:
            if self._revoked:
                raise StromError(_errno.ENODEV, f"buffer {self.handle} revoked")
            return self._array

    def swap(self, new_array: jax.Array) -> None:
        """Install the successor array produced by a donated update.
        An attached LandingBuffer stays attached: a donated update of an
        aliasing array may reuse the very same memory, so ownership only
        transfers at :meth:`adopt` / unmap / revoke boundaries."""
        with self._lock:
            if self._revoked:
                raise StromError(_errno.ENODEV, f"buffer {self.handle} revoked")
            self._array = new_array

    def adopt(self, new_array: jax.Array, landing: "LandingBuffer") -> None:
        """Install a directly-landed successor array together with the
        LandingBuffer it aliases.  The holder owns *landing* from here
        on; a previously adopted buffer is released (its memory survives
        as long as arrays still alias it)."""
        with self._lock:
            if self._revoked:
                raise StromError(_errno.ENODEV, f"buffer {self.handle} revoked")
            prev, self._landing = self._landing, landing
            self._array = new_array
        if prev is not None:
            prev.release()

    def _release_landing(self) -> None:
        with self._lock:
            landing, self._landing = self._landing, None
        if landing is not None:
            landing.release()

    @property
    def nbytes(self) -> int:
        return self._array.nbytes

    @property
    def device(self) -> str:
        ds = list(self._array.devices())
        return str(ds[0]) if ds else "?"


class HbmRegistry:
    """Handle table for registered device buffers (64-hash-slot analog,
    kmod/pmemmap.c:75-78 — here a dict; the slot count was a kernel
    implementation detail, not a capability)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._buffers: Dict[int, HbmBuffer] = {}
        self._next = 1

    # -- MAP_GPU_MEMORY ----------------------------------------------------
    def map_device_memory(self, size_or_array, *, dtype=jnp.uint8,
                          device: Optional[jax.Device] = None) -> int:
        """Register a destination: either adopt an existing ``jax.Array`` or
        allocate ``size`` elements of ``dtype`` on *device* (default: first
        addressable device)."""
        if isinstance(size_or_array, jax.Array):
            arr = size_or_array
        else:
            n = int(size_or_array)
            if n <= 0:
                raise StromError(_errno.EINVAL, "buffer size must be positive")
            dev = device or jax.local_devices()[0]
            arr = jnp.zeros((n,), dtype=dtype, device=dev)
        with self._lock:
            handle = self._next
            self._next += 1
            self._buffers[handle] = HbmBuffer(handle, arr, os.getuid())
        return handle

    def get(self, handle: int) -> HbmBuffer:
        """Look up + ownership check (reference kmod/pmemmap.c:104-105)."""
        with self._lock:
            buf = self._buffers.get(handle)
        if buf is None:
            raise StromError(_errno.ENOENT, f"no device buffer {handle}")
        if buf.owner_uid != os.getuid():
            raise StromError(_errno.EPERM, "device buffer owned by another uid")
        return buf

    def acquire(self, handle: int) -> HbmBuffer:
        buf = self.get(handle)
        with buf._lock:
            if buf._revoked:
                raise StromError(_errno.ENODEV, f"buffer {handle} revoked")
            buf.refcount += 1
        return buf

    def release(self, buf: HbmBuffer) -> None:
        with buf._lock:
            buf.refcount -= 1
            if buf.refcount == 0:
                buf._drained.notify_all()

    # -- UNMAP_GPU_MEMORY (revocation) -------------------------------------
    def unmap(self, handle: int, *, timeout: float = 30.0) -> None:
        """Revoke a handle, blocking until in-flight transfers drain — the
        ``callback_release_mapped_gpu_memory`` contract
        (kmod/pmemmap.c:149-208).  A buffer already revoked by backend
        loss unregisters immediately (its transfers died with the
        backend; there is nothing left to drain)."""
        buf = self.get(handle)
        deadline = time.monotonic() + timeout
        with buf._lock:
            already = buf._revoked
        if already:   # outside buf._lock: registry lock nests self->buf
            with self._lock:
                self._buffers.pop(handle, None)
            buf._release_landing()
            return
        with buf._lock:
            # standard CV idiom: re-test the predicate after every wake,
            # including a timed-out one — a release landing exactly at the
            # deadline must still win.  A concurrent revoke_all also ends
            # the drain: the refcount can never drop once the backend died
            while buf.refcount != 0 and not buf._revoked:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise StromError(
                        _errno.ETIMEDOUT,
                        f"buffer {handle} busy past revocation timeout")
                buf._drained.wait(timeout=remaining)
            buf._revoked = True
        with self._lock:
            self._buffers.pop(handle, None)
        buf._release_landing()

    def revoke_all(self, why: str) -> int:
        """Backend-loss revocation (VERDICT r3 #5): mark every registered
        buffer revoked with ENODEV semantics — WITHOUT waiting for
        refcounts (the in-flight transfers died with the backend), waking
        any ``unmap`` drains so they observe the revocation instead of
        waiting out a refcount that can no longer drop.  Buffers stay in
        the table (listed, ``info`` works) until their owner unmaps them;
        ``array``/``swap``/``acquire`` fail with ENODEV.  Returns the
        number of buffers revoked."""
        with self._lock:
            bufs = list(self._buffers.values())
        n = 0
        for buf in bufs:
            with buf._lock:
                if not buf._revoked:
                    buf._revoked = True
                    buf.revoke_reason = why
                    n += 1
                buf._drained.notify_all()
            try:
                # the alias is dead with the array (ENODEV on access);
                # unpin its memory now rather than waiting for unmap
                buf._release_landing()
            except Exception:  # noqa: BLE001 - loss path must not throw
                pass
        return n

    # -- LIST / INFO -------------------------------------------------------
    def list(self) -> List[int]:
        with self._lock:
            return sorted(self._buffers)

    def info(self, handle: int) -> BufferInfo:
        buf = self.get(handle)
        return BufferInfo(handle=handle, length=buf.nbytes,
                          page_size=_DEVICE_PAGE,
                          n_pages=(buf.nbytes + _DEVICE_PAGE - 1) // _DEVICE_PAGE,
                          owner_uid=buf.owner_uid, refcount=buf.refcount,
                          kind="hbm", device=buf.device)


#: process-global registry (one per process, like the module's handle table)
registry = HbmRegistry()

# backend loss revokes the global table's buffers (VERDICT r3 #5); private
# registries opt in via monitor.register_registry
from .backend import monitor as _monitor  # noqa: E402 - needs HbmRegistry

_monitor.register_registry(registry)
