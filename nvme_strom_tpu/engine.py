"""Core data-path engine: sources, buffers, chunk planner, async task table.

This is the capability heart of the framework — everything the reference's
kernel module does (`kmod/nvme_strom.c`), rebuilt as an in-process engine:

* **eligibility check** — ``check_file`` (reference ``ioctl_check_file``,
  kmod/nvme_strom.c:188-583): O_DIRECT capability probe, fs classification,
  block size, NUMA node, DMA request cap.
* **sources** — plain files, PostgreSQL-style segmented relations, and
  RAID-0-striped member sets, all resolving logical ranges to physical
  extents (the in-kernel ``strom_get_block`` + ``strom_raid0_map_sector``
  resolution, :174-186, :823-910, moved to userspace).
* **chunk planner** — page-cache arbitration (hot chunks take the write-back
  path, reference :1639-1663, probed here with ``mincore``) and merging of
  physically-contiguous reads into up to ``dma_max_size`` requests
  (reference merge condition :1473-1505).
* **async task table** — one task per memcpy command; 512-slot hash with
  per-slot condition variables (so spurious wakeups are real and *counted*,
  reference ``nr_wrong_wakeup`` :1303-1304); per-request refcounting; first
  error latched; **failed tasks retained until reaped by a wait or by
  session close** (reference design memo :612-626, reap at :2138-2166).
* **stats** — every stage timed into the count+clock registry (SS5.1).

Two interchangeable I/O backends execute the planned requests: the native
C++ engine (io_uring, ``nvme_strom_tpu._native``) and a portable thread-pool
fallback defined here.  Both consume the same plan, so they are
differentially testable.
"""

from __future__ import annotations

import ctypes
import ctypes.util
import errno as _errno
import mmap
import os
import random
import struct
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple, Union

from .api import (BufferInfo, DmaTaskState, ErrorClass, FileInfo, FsKind,
                  MemCopyResult, StromError)
from .config import config
from . import blockmap
from .fault import (DirtyExtentJournal, HealthState, MemberHealthMachine,
                    RetryPolicy)
from .log import pr_info, pr_warn
from .eligibility import probe_backing
from .stats import stats
from .trace import recorder as _trace
from .autotune import AutoTuner
from .tiering import extent_space as _tiers
from .integrity import domain as _integrity, Scrubber as _Scrubber
from . import numa as _numa

#: live sessions, for the stat exporter's pre-publish fold (weak: the
#: registry must never keep a closed session alive)
import weakref as _weakref

_live_sessions: "_weakref.WeakSet" = _weakref.WeakSet()


def _fold_live_native_stats() -> None:
    for s in list(_live_sessions):
        try:
            if getattr(s, "_native", None) is not None \
                    and not s._closed:
                s._fold_native_stats()
        except Exception:   # noqa: BLE001 — observability, not control
            pass
from .stripe import StripeMap

__all__ = [
    "check_file", "Source", "PlainSource", "SegmentedSource", "StripedSource",
    "DmaBuffer", "Session", "Request", "plan_requests", "open_source",
    "plan_shard_ownership",
]

PAGE_SIZE = mmap.PAGESIZE
_libc = ctypes.CDLL(None, use_errno=True)

# statfs magics (reference checks these at kmod/nvme_strom.c:477-486)
_EXT4_SUPER_MAGIC = 0xEF53
_XFS_SUPER_MAGIC = 0x58465342


def _fs_magic(path: str) -> int:
    """f_type from statfs(2)."""
    class _Statfs(ctypes.Structure):
        _fields_ = [("f_type", ctypes.c_long), ("f_bsize", ctypes.c_long),
                    ("_pad", ctypes.c_byte * 256)]
    buf = _Statfs()
    if _libc.statfs(os.fsencode(path), ctypes.byref(buf)) != 0:
        return 0
    return buf.f_type & 0xFFFFFFFF


def _probe_odirect(path: str) -> bool:
    try:
        fd = os.open(path, os.O_RDONLY | os.O_DIRECT)
    except OSError:
        return False
    os.close(fd)
    return True


def _pread_exact(fd: int, dest: memoryview, offset: int) -> None:
    """Fill *dest* from *fd* at *offset*; a read may return short (a
    network filesystem caps one reply), so loop until EOF."""
    done = 0
    while done < len(dest):
        n = os.preadv(fd, [dest[done:]], offset + done)
        if n <= 0:
            raise StromError(_errno.EIO, f"short buffered read {done} != "
                                         f"{len(dest)}")
        done += n


def check_file(path: str, *, dma_max_size: Optional[int] = None,
               strict: Optional[bool] = None,
               sysfs_root: str = "/sys") -> FileInfo:
    """CHECK_FILE: classify *path* for the direct-load path.

    Reference semantics (`kmod/nvme_strom.c:188-583`): read permission, fs
    identity, blocksize <= PAGE_SIZE, file at least one page (inline files
    excluded), raw-NVMe-or-RAID0 backing, NUMA node, DMA64, request cap.

    The TPU engine's hard requirement is an O_DIRECT-capable regular file;
    the backing-device verdict (``backing_supported`` / ``backing_reason``,
    from :229-438's raw-NVMe/md-RAID0 walk redone over sysfs) is always
    reported, and with ``strict=True`` (or config ``require_nvme_backing``)
    an unverified backing makes the file UNSUPPORTED outright — the
    reference's behavior, where a SATA or network fs could never be
    green-lit for the fast path."""
    st = os.stat(path)
    if not os.access(path, os.R_OK):
        raise StromError(_errno.EACCES, f"no read permission: {path}")
    magic = _fs_magic(path)
    if magic == _EXT4_SUPER_MAGIC:
        kind = FsKind.EXT4
    elif magic == _XFS_SUPER_MAGIC:
        kind = FsKind.XFS
    elif _probe_odirect(path):
        kind = FsKind.OTHER_DIRECT
    else:
        kind = FsKind.UNSUPPORTED
    if kind in (FsKind.EXT4, FsKind.XFS) and not _probe_odirect(path):
        kind = FsKind.UNSUPPORTED
    backing = probe_backing(path, sysfs_root=sysfs_root)
    if strict is None:
        strict = config.get("require_nvme_backing")
    # strict policy is a separate verdict, NOT an fs_kind clobber: fs_kind
    # stays an honest fact so cached probes + a live policy check compose.
    # The predicate itself lives in FileInfo.strict_eligible (backing
    # verified AND dma64) so tools and planner share one definition.
    policy_rejected = bool(strict and not (backing.supported
                                           and backing.support_dma64))
    # reference excludes files smaller than one page (inline data risk,
    # kmod/nvme_strom.c:503-518)
    if st.st_size < PAGE_SIZE:
        kind = FsKind.UNSUPPORTED
    cap = dma_max_size or config.get("dma_max_size")
    if backing.dma_max_size:
        # min(hw ceiling, admin soft limit), resolved by the classifier
        # (:297-314 analog) — no second walk of the real /sys here, so
        # fake-tree probes stay hermetic
        cap = min(cap, backing.dma_max_size)
    # numa -1 is a *verdict* for RAID0 spanning nodes (kmod :322-326) and
    # honest "unknown" otherwise; consumers guard negative nodes
    return FileInfo(path=path, file_size=st.st_size, fs_kind=kind,
                    logical_block_size=backing.logical_block_size or 512,
                    dma_max_size=cap,
                    numa_node_id=backing.numa_node_id,
                    support_dma64=backing.support_dma64,
                    n_members=max(1, len(backing.members)),
                    stripe_chunk_size=backing.stripe_chunk_size,
                    backing_kind=backing.kind,
                    backing_supported=backing.supported,
                    backing_reason=backing.reason,
                    policy_rejected=policy_rejected)


# ---------------------------------------------------------------------------
# Sources
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Extent:
    """Physically contiguous piece of a logical range on one member fd."""

    member: int
    file_off: int
    length: int
    logical_off: int


class Source:
    """A logical byte stream resolvable to physical extents.

    Read-oriented by default; opened with ``writable=True`` it also
    carries the RAM→SSD write legs (a capability the read-only reference
    lacks — its engine only builds NVMe READ commands,
    kmod/nvme_strom.c:1136-1224)."""

    size: int
    block_size: int
    writable: bool = False

    def extents(self, offset: int, length: int) -> List[Extent]:
        raise NotImplementedError

    def member_fds(self) -> List[int]:
        """O_DIRECT fds, one per member."""
        raise NotImplementedError

    def mirror_of(self, member: int) -> Optional[int]:
        """Member holding a byte-identical replica of *member* (same
        member offsets), or None when the source has no redundancy.
        Striped sources opened with ``mirror='paired'`` override this;
        it is the basis for degraded-mode striping and hedged reads."""
        return None

    def cached_fraction(self, offset: int, length: int) -> float:
        """Fraction of the range resident in the host page cache
        (reference probes with find_lock_page, kmod/nvme_strom.c:1639-1645;
        here with mincore(2))."""
        return 0.0

    # -- hot-data signal (the PageDirty analog) ----------------------------
    # The reference scores a dirty page at threshold+1 — ONE dirty page
    # tips the whole chunk to write-back (kmod/nvme_strom.c:1639-1645),
    # because a dirty page makes the on-disk block stale and a direct read
    # would either return stale data or stall on a forced flush.  Userspace
    # cannot see PageDirty directly, so the signal is rebuilt from two
    # sides: an explicit hint API for writers that know their hot ranges,
    # plus (where /proc/kpageflags is readable) a best-effort probe.

    def hint_hot_range(self, offset: int, length: int) -> None:
        """Declare [offset, offset+length) hot (being written / recently
        written): chunks overlapping it take the write-back path instead
        of forcing a flush stall on the direct path."""
        if length <= 0:
            return
        hints = getattr(self, "_hot_hints", None)
        if hints is None:
            hints = self._hot_hints = []
        hints.append((offset, offset + length))

    def clear_hot_hints(self) -> None:
        self._hot_hints = []

    def hot_fraction(self, offset: int, length: int) -> float:
        """Fraction of the range covered by hot hints (subclasses may add
        measured dirtiness).  Any value > 0 routes the chunk write-back,
        mirroring the reference's one-dirty-page rule."""
        hints = getattr(self, "_hot_hints", None)
        if not hints or length <= 0:
            return 0.0
        covered = 0
        for h0, h1 in hints:
            lo, hi = max(offset, h0), min(offset + length, h1)
            if hi > lo:
                covered += hi - lo  # hints may overlap; fraction is advisory
        return min(covered / length, 1.0)

    def residency(self, spans: Sequence[Tuple[int, int]]
                  ) -> List[Tuple[float, float]]:
        """Per-span ``(cached_fraction, hot_fraction)`` for a batch of
        ``(offset, length)`` ranges — the cache-arbitration probe for one
        whole task.  The default defers to the scalar probes so subclass
        overrides (test fakes, forced verdicts) keep deciding arbitration;
        real file sources override this with a single batched mincore(2)
        scan to keep the probe off the submission critical path."""
        return [(self.cached_fraction(o, l), self.hot_fraction(o, l))
                for o, l in spans]

    def read_buffered(self, offset: int, dest: memoryview) -> None:
        """Page-cache copy path (reference memcpy_pgcache_to_ubuffer,
        kmod/nvme_strom.c:1344-1401)."""
        raise NotImplementedError

    def read_member_buffered(self, member: int, file_off: int, dest: memoryview) -> None:
        """Buffered read addressed by (member, member offset) — used for
        misaligned tails that O_DIRECT cannot express."""
        raise NotImplementedError

    def read_member_direct(self, member: int, file_off: int, dest: memoryview) -> None:
        """O_DIRECT read of one planned request (the async-engine read leg).
        Overridable by test fakes for latency/fault injection."""
        fd = self.member_fds()[member]
        if fd < 0:
            raise StromError(_errno.EINVAL, "member has no O_DIRECT fd")
        done, length = 0, len(dest)
        while done < length:
            n = os.preadv(fd, [dest[done:length]], file_off + done)
            if n <= 0:
                raise StromError(_errno.EIO, f"short direct read at {file_off + done}")
            done += n

    def read_member_direct_v(self, member: int, file_off: int,
                             dests: Sequence[memoryview]) -> None:
        """Vectored O_DIRECT read: ONE file-contiguous span scattered into
        several destination segments (the coalesced form of stripe-adjacent
        extents — reference request merging, kmod/nvme_strom.c:1473-1505).

        When a subclass (or test fake) overrides the scalar read leg, fall
        back to per-segment scalar reads so latency/fault injection still
        sees every segment; the real source issues a single preadv."""
        if type(self).read_member_direct is not Source.read_member_direct:
            off = file_off
            for d in dests:
                self.read_member_direct(member, off, d)
                off += len(d)
            return
        fd = self.member_fds()[member]
        if fd < 0:
            raise StromError(_errno.EINVAL, "member has no O_DIRECT fd")
        remaining = list(dests)
        pos = file_off
        while remaining:
            n = os.preadv(fd, remaining, pos)
            if n <= 0:
                raise StromError(_errno.EIO, f"short direct read at {pos}")
            pos += n
            while remaining and n >= len(remaining[0]):
                n -= len(remaining[0])
                remaining.pop(0)
            if n:
                remaining[0] = remaining[0][n:]

    # -- write legs (RAM→SSD; requires writable=True) ----------------------
    def member_buffered_fds(self) -> List[int]:
        raise NotImplementedError

    def _check_writable(self) -> None:
        if not self.writable:
            raise StromError(_errno.EBADF, "source opened read-only; "
                             "open_source(..., writable=True)")

    def write_member_direct(self, member: int, file_off: int, src: memoryview) -> None:
        """O_DIRECT write of one planned request (the async write leg)."""
        self._check_writable()
        fd = self.member_fds()[member]
        if fd < 0:
            raise StromError(_errno.EINVAL, "member has no O_DIRECT fd")
        done, length = 0, len(src)
        while done < length:
            n = os.pwritev(fd, [src[done:length]], file_off + done)
            if n <= 0:
                raise StromError(_errno.EIO, f"short direct write at {file_off + done}")
            done += n

    def write_member_buffered(self, member: int, file_off: int, src: memoryview) -> None:
        """Buffered write — misaligned pieces O_DIRECT cannot express."""
        self._check_writable()
        fd = self.member_buffered_fds()[member]
        done, length = 0, len(src)
        while done < length:  # partial buffered writes are legal; loop
            n = os.pwritev(fd, [src[done:length]], file_off + done)
            if n <= 0:
                raise StromError(_errno.EIO,
                                 f"short buffered write at {file_off + done}")
            done += n

    def sync(self) -> None:
        """fsync every member (durability for the buffered write legs)."""
        for fd in self.member_buffered_fds():
            os.fsync(fd)

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# mincore(2) defines only bit 0 of each residency byte; translate through
# this table before counting so reserved high bits can never skew a scan
_MINCORE_LSB = bytes((i & 1) for i in range(256))


class _FileMember:
    """One underlying file: direct fd + buffered fd + mmap for cache probe."""

    def __init__(self, path: str, writable: bool = False):
        self.path = path
        self.size = os.stat(path).st_size
        self.writable = writable
        mode = os.O_RDWR if writable else os.O_RDONLY
        try:
            self.fd_direct = os.open(path, mode | os.O_DIRECT)
        except OSError:
            self.fd_direct = -1
        self.fd_buffered = os.open(path, mode)
        self._mm: Optional[mmap.mmap] = None
        self._mm_addr = 0
        self._mincore_buf = None     # per-member scratch, grown on demand
        self._mincore_cap = 0

    def mm(self) -> Optional[mmap.mmap]:
        if self._mm is None and self.size > 0:
            # MAP_PRIVATE read-write: pages stay page-cache-backed (we never
            # write), and ctypes can take the address for mincore(2)
            self._mm = mmap.mmap(self.fd_buffered, self.size,
                                 flags=mmap.MAP_PRIVATE,
                                 prot=mmap.PROT_READ | mmap.PROT_WRITE)
            self._mm_addr = ctypes.addressof(ctypes.c_char.from_buffer(self._mm))
        return self._mm

    def _mincore_scratch(self, npages: int):
        """Grow-and-return the member's shared mincore(2) residency
        vector, sized for at least *npages* entries.  Arbitration probes
        every chunk of every read: one scratch per member instead of an
        allocation per call — callers consume the result before the next
        probe on this member, and only the first npages entries are live."""
        if npages > self._mincore_cap:
            self._mincore_cap = max(npages, self._mincore_cap * 2, 256)
            self._mincore_buf = (ctypes.c_ubyte * self._mincore_cap)()
        return self._mincore_buf

    def _mincore_vec(self, offset: int, length: int):
        """(residency bytevec, start, npages) for the page-aligned range."""
        mm = self.mm()
        if mm is None or length <= 0:
            return None, 0, 0
        start = offset & ~(PAGE_SIZE - 1)
        end = min((offset + length + PAGE_SIZE - 1) & ~(PAGE_SIZE - 1), self.size)
        npages = max((end - start + PAGE_SIZE - 1) // PAGE_SIZE, 1)
        vec = self._mincore_scratch(npages)
        rc = _libc.mincore(ctypes.c_void_p(self._mm_addr + start),
                           ctypes.c_size_t(end - start), vec)
        if rc != 0:
            return None, 0, 0
        return vec, start, npages

    def cached_fraction(self, offset: int, length: int) -> float:
        vec, _start, npages = self._mincore_vec(offset, length)
        if vec is None:
            return 0.0
        # vec is the shared scratch — only the first npages entries are live
        resident = ctypes.string_at(vec, npages).translate(_MINCORE_LSB).count(1)
        return resident / npages

    def cached_spans(self, spans: Sequence[Tuple[int, int]]
                     ) -> List[Tuple[float, bool]]:
        """Per-span ``(cached_fraction, any_resident)`` from ONE mincore(2)
        over the enclosing range.  Arbitration probes every chunk of every
        task; batching turns 2 syscalls + a Python scan per chunk into one
        syscall + bytes ops per task (~5ms off a 128-chunk submit)."""
        if not spans:
            return []
        mm = self.mm()
        if mm is None:
            return [(0.0, False)] * len(spans)
        lo = min(o for o, _ in spans) & ~(PAGE_SIZE - 1)
        end = min(max(o + l for o, l in spans), self.size)
        npages = max((end - lo + PAGE_SIZE - 1) // PAGE_SIZE, 1)
        vec = self._mincore_scratch(npages)
        rc = _libc.mincore(ctypes.c_void_p(self._mm_addr + lo),
                           ctypes.c_size_t(end - lo), vec)
        if rc != 0:
            return [(0.0, False)] * len(spans)
        raw = ctypes.string_at(vec, npages).translate(_MINCORE_LSB)
        out = []
        for o, l in spans:
            p0 = ((o & ~(PAGE_SIZE - 1)) - lo) // PAGE_SIZE
            p1 = (min(o + l, self.size) - lo + PAGE_SIZE - 1) // PAGE_SIZE
            res = raw[p0:p1].count(1)
            out.append((res / max(p1 - p0, 1), res > 0))
        return out

    def dirty_fraction(self, offset: int, length: int) -> float:
        """Best-effort PageDirty probe (kmod/nvme_strom.c:1643 analog)
        via /proc/self/pagemap -> /proc/kpageflags (KPF_DIRTY).

        Only pages mincore reports resident are touched (mapping an
        already-resident page into our tables does not perturb the cache);
        unreadable proc files degrade to 0.0 — the hint API is then the
        only dirty signal."""
        vec, start, npages = self._mincore_vec(offset, length)
        if vec is None:
            return 0.0
        raw = ctypes.string_at(vec, npages)
        resident = [i for i, b in enumerate(raw) if b & 1]
        if not resident:
            return 0.0
        try:
            pm = os.open("/proc/self/pagemap", os.O_RDONLY)
        except OSError:
            return 0.0
        try:
            try:
                kf = os.open("/proc/kpageflags", os.O_RDONLY)
            except OSError:
                return 0.0
            try:
                dirty = 0
                for i in resident:
                    va = self._mm_addr + start + i * PAGE_SIZE
                    # fault the (resident) page into our tables so pagemap
                    # shows its PFN; a read fault never dirties it
                    ctypes.c_ubyte.from_address(va).value
                    ent = os.pread(pm, 8, (va // PAGE_SIZE) * 8)
                    if len(ent) != 8:
                        continue
                    word = int.from_bytes(ent, "little")
                    if not word >> 63:  # not present
                        continue
                    pfn = word & ((1 << 55) - 1)
                    if pfn == 0:
                        continue
                    flags_b = os.pread(kf, 8, pfn * 8)
                    if len(flags_b) != 8:
                        continue
                    if (int.from_bytes(flags_b, "little") >> 4) & 1:  # KPF_DIRTY
                        dirty += 1
                return dirty / npages
            finally:
                os.close(kf)
        finally:
            os.close(pm)

    def close(self) -> None:
        if self._mm is not None:
            try:
                self._mm.close()
            except BufferError:
                pass  # a ctypes view still pins it; dropped with the process
            self._mm = None
        if self.fd_direct >= 0:
            os.close(self.fd_direct)
            self.fd_direct = -1
        if self.fd_buffered >= 0:
            os.close(self.fd_buffered)
            self.fd_buffered = -1


class PlainSource(Source):
    """A single regular file."""

    def __init__(self, path: str, block_size: int = 512,
                 writable: bool = False):
        self._m = _FileMember(path, writable)
        self.path = path
        self.size = self._m.size
        self.block_size = block_size
        self.writable = writable

    def extents(self, offset: int, length: int) -> List[Extent]:
        if offset < 0 or offset + length > self.size:
            raise StromError(_errno.EINVAL,
                            f"range [{offset},{offset+length}) outside file of {self.size}")
        return [Extent(0, offset, length, offset)]

    def member_fds(self) -> List[int]:
        return [self._m.fd_direct]

    def member_buffered_fds(self) -> List[int]:
        return [self._m.fd_buffered]

    def cached_fraction(self, offset: int, length: int) -> float:
        return self._m.cached_fraction(offset, length)

    def hot_fraction(self, offset: int, length: int) -> float:
        # explicit hints plus measured page dirtiness, whichever is louder
        hinted = super().hot_fraction(offset, length)
        if hinted >= 1.0:
            return hinted
        return max(hinted, self._m.dirty_fraction(offset, length))

    def residency(self, spans: Sequence[Tuple[int, int]]
                  ) -> List[Tuple[float, float]]:
        # one batched mincore for the whole task — but only when the scalar
        # probes are OURS: a subclass that overrides either one (forced
        # verdicts in test fakes) still owns arbitration via the default
        if (type(self).cached_fraction is not PlainSource.cached_fraction
                or type(self).hot_fraction is not PlainSource.hot_fraction):
            return super().residency(spans)
        out = []
        for (off, ln), (frac, any_res) in zip(spans, self._m.cached_spans(spans)):
            hot = Source.hot_fraction(self, off, ln)   # hint coverage
            if hot < 1.0 and any_res:
                # dirtiness requires residency: skip the /proc probe on
                # ranges the batched scan showed fully cold
                hot = max(hot, self._m.dirty_fraction(off, ln))
            out.append((frac, hot))
        return out

    def read_buffered(self, offset: int, dest: memoryview) -> None:
        _pread_exact(self._m.fd_buffered, dest, offset)

    def read_member_buffered(self, member: int, file_off: int, dest: memoryview) -> None:
        n = os.preadv(self._m.fd_buffered, [dest], file_off)
        if n != len(dest):
            raise StromError(_errno.EIO, "short buffered read")

    def close(self) -> None:
        self._m.close()


class SegmentedSource(Source):
    """PostgreSQL-style segmented relation: logically one stream split across
    fixed-size segment files (reference mirrors md.c's MdfdVec per-segment fd
    table, pgsql/nvme_strom.c:124-130,692-714)."""

    def __init__(self, paths: Sequence[str], segment_size: int, block_size: int = 512,
                 writable: bool = False):
        if segment_size <= 0:
            raise StromError(_errno.EINVAL, "segment_size must be positive")
        self.members = [_FileMember(p, writable) for p in paths]
        for m in self.members[:-1]:
            if m.size != segment_size:
                raise StromError(_errno.EINVAL,
                                f"non-final segment {m.path} has size {m.size} != {segment_size}")
        self.segment_size = segment_size
        self.size = sum(m.size for m in self.members)
        self.block_size = block_size
        self.writable = writable

    def extents(self, offset: int, length: int) -> List[Extent]:
        if offset < 0 or offset + length > self.size:
            raise StromError(_errno.EINVAL, "range outside segmented relation")
        out: List[Extent] = []
        pos, rem = offset, length
        while rem > 0:
            seg, soff = divmod(pos, self.segment_size)
            take = min(self.segment_size - soff, rem)
            out.append(Extent(seg, soff, take, pos))
            pos += take
            rem -= take
        return out

    def member_fds(self) -> List[int]:
        return [m.fd_direct for m in self.members]

    def member_buffered_fds(self) -> List[int]:
        return [m.fd_buffered for m in self.members]

    def cached_fraction(self, offset: int, length: int) -> float:
        total, weight = 0.0, 0
        for e in self.extents(offset, length):
            total += self.members[e.member].cached_fraction(e.file_off, e.length) * e.length
            weight += e.length
        return total / weight if weight else 0.0

    def read_buffered(self, offset: int, dest: memoryview) -> None:
        done = 0
        for e in self.extents(offset, len(dest)):
            _pread_exact(self.members[e.member].fd_buffered,
                         dest[done:done + e.length], e.file_off)
            done += e.length

    def read_member_buffered(self, member: int, file_off: int, dest: memoryview) -> None:
        n = os.preadv(self.members[member].fd_buffered, [dest], file_off)
        if n != len(dest):
            raise StromError(_errno.EIO, "short buffered read")

    def close(self) -> None:
        for m in self.members:
            m.close()


class StripedSource(Source):
    """RAID-0 striped member set resolved with :class:`StripeMap`."""

    def __init__(self, paths: Sequence[str], stripe_chunk_size: int,
                 block_size: int = 512, writable: bool = False,
                 mirror: Optional[str] = None):
        if mirror is None:
            mirror = str(config.get("mirror"))
        # mirror='paired' + writable is first-class since ISSUE 11: the
        # engine fans each aligned write leg out to the pair partner
        # (mirror-coherent writes), so written stripes keep the degraded-
        # mode read guarantees instead of silently losing their replica
        self.members = [_FileMember(p, writable) for p in paths]
        self.map = StripeMap([m.size for m in self.members],
                             stripe_chunk_size, mirror=mirror)
        self.size = self.map.total_size
        self.block_size = block_size
        self.stripe_chunk_size = stripe_chunk_size
        self.writable = writable

    def mirror_of(self, member: int) -> Optional[int]:
        return self.map.mirror_of(member)

    def extents(self, offset: int, length: int) -> List[Extent]:
        return [Extent(e.member, e.member_offset, e.length, e.logical_offset)
                for e in self.map.map_range(offset, length)]

    def member_fds(self) -> List[int]:
        return [m.fd_direct for m in self.members]

    def member_buffered_fds(self) -> List[int]:
        return [m.fd_buffered for m in self.members]

    def cached_fraction(self, offset: int, length: int) -> float:
        total, weight = 0.0, 0
        for e in self.extents(offset, length):
            total += self.members[e.member].cached_fraction(e.file_off, e.length) * e.length
            weight += e.length
        return total / weight if weight else 0.0

    def read_buffered(self, offset: int, dest: memoryview) -> None:
        for e in self.extents(offset, len(dest)):
            rel = e.logical_off - offset
            _pread_exact(self.members[e.member].fd_buffered,
                         dest[rel:rel + e.length], e.file_off)

    def read_member_buffered(self, member: int, file_off: int, dest: memoryview) -> None:
        n = os.preadv(self.members[member].fd_buffered, [dest], file_off)
        if n != len(dest):
            raise StromError(_errno.EIO, "short buffered read")

    def close(self) -> None:
        for m in self.members:
            m.close()


def open_source(spec: Union[str, Sequence[str]], *,
                stripe_chunk_size: Optional[int] = None,
                segment_size: Optional[int] = None,
                block_size: Optional[int] = None,
                writable: bool = False,
                mirror: Optional[str] = None) -> Source:
    """Open a plain, striped, or segmented source from a path spec."""
    if isinstance(spec, str):
        info = check_file(spec)
        return PlainSource(spec, block_size or info.logical_block_size,
                           writable)
    paths = list(spec)
    if stripe_chunk_size:
        return StripedSource(paths, stripe_chunk_size, block_size or 512,
                             writable, mirror=mirror)
    if segment_size:
        return SegmentedSource(paths, segment_size, block_size or 512,
                               writable)
    raise StromError(_errno.EINVAL,
                    "multi-path source needs stripe_chunk_size or segment_size")


# ---------------------------------------------------------------------------
# DMA buffers
# ---------------------------------------------------------------------------

class DmaBuffer:
    """Pinned, page-aligned host buffer (hugepage-backed when available).

    Analog of the reference's hugepage DMA buffer (`kmod/pmemmap.c:497-649`)
    and the pgsql NUMA-aware pool chunks (`pgsql/nvme_strom.c:1454-1526`):
    anonymous mmap, MAP_HUGETLB attempted first, then mlock'd so the kernel
    cannot migrate pages mid-I/O."""

    def __init__(self, length: int, *, numa_node: int = -1, pin: Optional[bool] = None):
        if length <= 0:
            raise StromError(_errno.EINVAL, "buffer length must be positive")
        length = (length + PAGE_SIZE - 1) & ~(PAGE_SIZE - 1)
        self.length = length
        self.numa_node = numa_node
        self.hugepages = False
        mm = None
        flags = mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS
        if hasattr(mmap, "MAP_HUGETLB") and length % (2 << 20) == 0:
            try:
                mm = mmap.mmap(-1, length, flags=flags | mmap.MAP_HUGETLB)
                self.hugepages = True
            except OSError:
                mm = None
        if mm is None:
            mm = mmap.mmap(-1, length, flags=flags)
        self._mm = mm
        self.addr = ctypes.addressof(ctypes.c_char.from_buffer(mm))
        self.pinned = False
        if pin if pin is not None else config.get("pin_memory"):
            self.pinned = _libc.mlock(ctypes.c_void_p(self.addr),
                                      ctypes.c_size_t(length)) == 0
        # prefault so first DMA doesn't eat page faults (reference prefaults
        # its shm pool, pgsql/nvme_strom.c:1500-1510)
        mm[0:length:PAGE_SIZE] = b"\0" * len(range(0, length, PAGE_SIZE))
        self._close_cbs: List = []
        self._cb_lock = threading.Lock()
        self._closing = False

    def on_close(self, cb) -> bool:
        """Arrange for *cb* to run when this buffer is closed (BEFORE the
        munmap) — how a session keeps io_uring fixed-buffer registrations
        exactly coextensive with the mapping (a registration outliving the
        mmap would alias whatever lands at the address next).  Returns
        False when the buffer is already closed/closing: the caller must
        run its cleanup itself."""
        with self._cb_lock:
            if self._mm is None or self._closing:
                return False
            self._close_cbs.append(cb)
            return True

    def remove_close_cb(self, cb) -> None:
        """Detach a close callback (a closing Session removes its hooks so
        long-lived pool buffers don't accumulate dead-session closures)."""
        with self._cb_lock:
            try:
                self._close_cbs.remove(cb)
            except ValueError:
                pass

    def view(self) -> memoryview:
        return memoryview(self._mm)

    def close(self) -> None:
        with self._cb_lock:
            if self._mm is None or self._closing:
                return
            self._closing = True
            cbs, self._close_cbs = self._close_cbs, []
        for cb in cbs:
            try:
                cb()
            except Exception:
                pass
        if self.pinned:
            _libc.munlock(ctypes.c_void_p(self.addr), ctypes.c_size_t(self.length))
        try:
            self._mm.close()
        except BufferError:
            pass
        with self._cb_lock:
            self._mm = None

    def __del__(self):  # pragma: no cover - GC backstop
        # a registered-but-never-closed buffer must still release its
        # io_uring registration BEFORE the mmap finalizer unmaps the range
        # (a stale fixed slot over a recycled VA would alias silently)
        try:
            self.close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Chunk planner
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Request:
    """One merged I/O request (<= dma_max_size bytes, one member — or up
    to coalesce_limit when the second merge pass ran)."""

    member: int
    file_off: int
    length: int
    dest_off: int
    buffered: bool = False   # misaligned tail falls back to buffered read
    # stripe-coalesced vectored read: when non-empty, the (file-contiguous)
    # span scatters into these (dest_off, length) segments — dest_off above
    # is then the first segment's offset and length the span total
    dest_segs: Tuple[Tuple[int, int], ...] = ()
    # NVMe passthrough lane (PR 19): blockmap-resolved DEVICE byte offset
    # when this request rides the raw-command path; None rides O_DIRECT.
    # Set only by the plan-time per-extent split, never by plan_requests.
    passthru_off: Optional[int] = None


def plan_requests(source: Source, chunk_entries: Sequence[Tuple[int, int]],
                  chunk_size: int, dest_base: int, *,
                  dma_max_size: Optional[int] = None,
                  dest_segment_shift: Optional[int] = None,
                  coalesce_limit: Union[int, Dict[int, int], None] = None
                  ) -> List[Request]:
    """Merge chunk reads into large requests.

    *chunk_entries* is ``[(chunk_id, dest_slot), ...]``; chunk ``cid`` covers
    logical bytes ``[cid*chunk_size, ...+chunk_size)`` (clamped to source
    size) and lands at ``dest_base + dest_slot*chunk_size``.

    Merge conditions mirror the reference (`kmod/nvme_strom.c:1473-1505`):
    same member, file-contiguous, destination-contiguous, merged length
    <= ``dma_max_size``, and never across a destination segment boundary when
    ``dest_segment_shift`` is given (the reference splits at GPU BAR segment /
    hugepage boundaries; a virtually-contiguous host buffer needs no split).
    Misaligned head/tail pieces (non-block-multiple file tail) are planned as
    buffered reads since O_DIRECT cannot express them.

    ``coalesce_limit`` (opt-in) runs a SECOND merge pass beyond the
    dma_max cap: file-contiguous neighbours within one member merge up to
    that many bytes, turning into vectored reads (:attr:`Request.dest_segs`)
    when their destinations are scattered by stripe interleave.  Without it
    the output honours the classic ``length <= dma_max_size`` invariant.
    A ``{member: limit}`` dict applies a per-member cap (the per-device
    adaptive sizers, PR 5); members missing from the dict don't coalesce.
    """
    cap = dma_max_size or config.get("dma_max_size")
    bs = max(source.block_size, 512)
    pieces: List[Request] = []
    for cid, slot in chunk_entries:
        base = cid * chunk_size
        length = min(chunk_size, source.size - base)
        if length <= 0:
            raise StromError(_errno.EINVAL, f"chunk {cid} beyond EOF")
        dest = dest_base + slot * chunk_size
        for e in source.extents(base, length):
            rel = e.logical_off - base
            aligned = (e.file_off % bs == 0 and e.length % bs == 0
                       and (dest + rel) % bs == 0)
            # split oversized extents at the request cap — every request the
            # engine issues is <= dma_max_size (kmod cap, nvme_strom.c:139-146)
            # — and at destination segment boundaries when requested
            off = 0
            while off < e.length:
                take = min(cap, e.length - off)
                if dest_segment_shift is not None:
                    seg_end = (((dest + rel + off) >> dest_segment_shift) + 1)                         << dest_segment_shift
                    take = min(take, seg_end - (dest + rel + off))
                pieces.append(Request(e.member, e.file_off + off, take,
                                      dest + rel + off, buffered=not aligned))
                off += take
    # merge pass
    out: List[Request] = []
    for r in pieces:
        if out:
            p = out[-1]
            if (p.member == r.member and not p.buffered and not r.buffered
                    and p.file_off + p.length == r.file_off
                    and p.dest_off + p.length == r.dest_off
                    and p.length + r.length <= cap
                    and (dest_segment_shift is None
                         or (p.dest_off >> dest_segment_shift)
                         == ((r.dest_off + r.length - 1) >> dest_segment_shift))):
                out[-1] = Request(p.member, p.file_off, p.length + r.length,
                                  p.dest_off)
                continue
        out.append(r)
    if coalesce_limit:
        if isinstance(coalesce_limit, dict):
            if any(v > cap for v in coalesce_limit.values()):
                out = _coalesce_requests(out, coalesce_limit,
                                         dest_segment_shift)
        elif coalesce_limit > cap:
            out = _coalesce_requests(out, coalesce_limit, dest_segment_shift)
    return out


def _coalesce_requests(reqs: List[Request], limit: Union[int, Dict[int, int]],
                       dest_segment_shift: Optional[int]) -> List[Request]:
    """Second merge pass (the reference's request-merge window applied
    beyond the per-command cap, kmod/nvme_strom.c:1473-1505): direct
    requests that are file-contiguous WITHIN one member merge up to
    *limit* bytes even when the stripe interleave scatters their
    destinations.  Dest-contiguous merges stay plain requests (a single
    big read the native engine executes unchanged — nstpu_req.len is
    64-bit); a destination gap turns the merge into a vectored read
    carried in :attr:`Request.dest_segs`.

    Requests read into disjoint destination ranges, so pulling a later
    request forward into an earlier one never reorders observable
    writes.  *limit* may be a ``{member: limit}`` dict — each member's
    run then merges under its own cap (per-member adaptive sizing)."""
    caps = limit if isinstance(limit, dict) else None
    out: List[Request] = []
    last: dict = {}  # member -> index in out of its last direct request
    for r in reqs:
        idx = last.get(r.member)
        if idx is not None and not r.buffered:
            lim = caps.get(r.member, 0) if caps is not None else limit
            p = out[idx]
            if (p.file_off + p.length == r.file_off
                    and p.length + r.length <= lim):
                segs = p.dest_segs or ((p.dest_off, p.length),)
                d, ln = segs[-1]
                if d + ln == r.dest_off and (
                        dest_segment_shift is None
                        or (d >> dest_segment_shift)
                        == ((r.dest_off + r.length - 1)
                            >> dest_segment_shift)):
                    segs = segs[:-1] + ((d, ln + r.length),)
                else:
                    segs = segs + ((r.dest_off, r.length),)
                out[idx] = Request(p.member, p.file_off,
                                   p.length + r.length, p.dest_off,
                                   dest_segs=segs if len(segs) > 1 else ())
                continue
        out.append(r)
        if r.buffered:
            # a buffered piece breaks the member's run: merging across it
            # would submit the direct span before the sync copy lands
            last.pop(r.member, None)
        else:
            last[r.member] = len(out) - 1
    return out


class AdaptiveChunkSizer:
    """Adaptive coalesced-request cap (the SSD-side analog of
    hbm.staging.AdaptiveH2DDepth): holds the effective merge cap at
    ``limit`` (optimistic start — large requests are what close the
    vs-raw-O_DIRECT gap), halves it toward ``floor`` whenever a request's
    observed service time blows the latency budget (an oversized request
    monopolizes its ring and starves the submission window), and doubles
    it back after ``decay_after`` consecutive in-budget completions."""

    #: per-request service-time budget; at NVMe-class bandwidth even a
    #: 64 MiB request completes well inside this, so shrink only fires
    #: when the device is genuinely slow at the current size
    LAT_BUDGET_NS = 100_000_000

    def __init__(self, floor: int, limit: int, decay_after: int = 4):
        self.floor = max(int(floor), 1)
        self.limit = max(int(limit), self.floor)
        self.decay_after = decay_after
        self._eff = self.limit
        self._streak = 0

    @property
    def effective(self) -> int:
        return self._eff

    def observe(self, service_ns: int) -> None:
        if service_ns > self.LAT_BUDGET_NS:
            self._streak = 0
            if self._eff > self.floor:
                self._eff = max(self._eff >> 1, self.floor)
        else:
            self._streak += 1
            if self._streak >= self.decay_after and self._eff < self.limit:
                self._eff = min(self._eff << 1, self.limit)
                self._streak = 0


def reorder_chunks(raw: "np.ndarray", chunk_size: int,
                   got_ids: Sequence[int],
                   want_ids: Sequence[int]) -> "np.ndarray":
    """Rearrange a chunk-strided buffer from the engine's completion order
    (direct-I/O chunks fronted, write-back chunks tailed — the reference's
    chunk_ids contract, kmod/nvme_strom.h:99-101) back to the caller's
    requested order.  Returns *raw* unchanged when the orders already
    match, else an owned copy."""
    import numpy as np
    got = list(got_ids)
    want = list(want_ids)
    if got == want:
        return raw
    pos = {cid: j for j, cid in enumerate(want)}
    blocks = raw.reshape(len(got), chunk_size)
    ordered = np.empty_like(blocks)
    ordered[[pos[c] for c in got]] = blocks
    return ordered.reshape(raw.shape)


def read_chunk_ids(sess: "Session", source: Source,
                   chunk_ids: Sequence[int], chunk_size: int,
                   buf_handle: int, buf_view: memoryview) -> "np.ndarray":
    """One synchronous read of *chunk_ids* through a mapped pinned
    buffer, returned in CALLER order — the submit/wait/reorder protocol
    shared by the point-lookup fetch and the checkpoint restore (one
    copy, so a fix to the read protocol lands everywhere)."""
    import numpy as np
    ids = [int(c) for c in chunk_ids]
    res = sess.memcpy_ssd2ram(source, buf_handle, ids, chunk_size)
    sess.memcpy_wait(res.dma_task_id)
    return reorder_chunks(
        np.frombuffer(buf_view[:len(ids) * chunk_size], np.uint8),
        chunk_size, res.chunk_ids, ids)


def plan_shard_ownership(source: Source, chunk_ids: Sequence[int],
                         chunk_size: int, n_hosts: int
                         ) -> Dict[int, List[int]]:
    """Partition a chunk list by host ownership for the multi-host
    sharded loader (ISSUE 17): host -> the chunks whose first extent
    lives on a member that host's local NVMe set holds, under the
    :func:`..stripe.host_of` member%n_hosts map.  Each host then submits
    ONLY its own list through its own engine session, so a striped
    deployment divides the file across per-host device queues the way
    the reference divides it across one host's md-RAID-0 members
    (`kmod/nvme_strom.c:823-910`).

    Single-member (plain/segmented-to-one-fd) sources have no placement
    to follow, so the split degrades to contiguous near-equal chunk
    ranges — still disjoint and exhaustive, which is all the gather
    step needs.  Every input chunk lands in exactly one host's list;
    hosts owning no member of a narrow stripe get empty lists.
    """
    from .stripe import host_of
    n_hosts = max(int(n_hosts), 1)
    ids = [int(c) for c in chunk_ids]
    owned: Dict[int, List[int]] = {h: [] for h in range(n_hosts)}
    n_members = len(source.member_fds())
    if n_members < 2 or n_hosts < 2:
        if n_hosts < 2:
            owned[0] = ids
            return owned
        # contiguous near-equal ranges: host h takes ids[h*q+...:...]
        q, r = divmod(len(ids), n_hosts)
        pos = 0
        for h in range(n_hosts):
            take = q + (1 if h < r else 0)
            owned[h] = ids[pos:pos + take]
            pos += take
        return owned
    for cid in ids:
        off = cid * chunk_size
        length = min(chunk_size, max(source.size - off, 0))
        if length <= 0:
            owned[host_of(0, n_hosts)].append(cid)
            continue
        member = source.extents(off, length)[0].member
        owned[host_of(member, n_hosts)].append(cid)
    return owned


# ---------------------------------------------------------------------------
# Async task table
# ---------------------------------------------------------------------------

_N_TASK_SLOTS = 512  # reference uses 512 hash slots (kmod/nvme_strom.c:639-644)

#: write-back runs of at least this many bytes are copied out of the page
#: cache on the session's I/O pool, off the caller's thread; shorter runs
#: (KV blocks, single pages) stay inline in the submit.  On an 8-core
#: Xeon host a pool handoff (queue, wake a worker, signal completion)
#: took 30-43 us, against 4.2 us for an inline 64 KiB copy, 12 us for
#: 256 KiB and 57-70 us for 1 MiB
_WRITEBACK_ASYNC_MIN = 1 << 20


class DmaTask:
    __slots__ = ("task_id", "state", "errno_", "errmsg", "pending", "frozen",
                 "result", "t_submit", "buf_handle", "deadline", "expired",
                 "verify_src", "verify_dest", "verify_reqs", "trace_id",
                 "cache_fill", "cache_invalidate", "write_verify", "passthru")

    def __init__(self, task_id: int, deadline_s: float = 0.0):
        self.task_id = task_id
        self.state = DmaTaskState.RUNNING
        self.errno_ = 0
        self.errmsg = ""
        self.pending = 1       # creator's reference (dropped when frozen)
        self.frozen = False    # set after the submission loop; no new refs
        self.result: Optional[MemCopyResult] = None
        self.t_submit = time.monotonic_ns()
        self.buf_handle: Optional[int] = None
        # zero-copy checksum plan: native-executed direct requests whose
        # verification runs AT WAIT TIME on the retired slot (off the
        # submission critical path) instead of inline in a pool thread
        self.verify_src: Optional[Source] = None
        self.verify_dest: Optional[memoryview] = None
        self.verify_reqs: Optional[List[Request]] = None
        # watchdog deadline (monotonic seconds; 0 = none) — overdue tasks
        # are latched ETIMEDOUT so memcpy_wait can never hang (PR 1)
        self.deadline = (time.monotonic() + deadline_s) if deadline_s > 0 \
            else 0.0
        self.expired = False   # set by the watchdog; chunks check and bail
        self.trace_id = 0      # nonzero when the flight recorder sampled
        #                        this task (trace.recorder.task_begin)
        # residency-cache work deferred to wait time (ISSUE 9): miss
        # extents to install from the healed destination, and written
        # extents to re-invalidate once the write has retired
        self.cache_fill: Optional[tuple] = None
        self.cache_invalidate: Optional[tuple] = None
        # write_verify (ISSUE 11): (sink, reqs, src view) for the wait-time
        # read-back crc32c check on retired write tasks
        self.write_verify: Optional[tuple] = None
        # NVMe passthrough channel (PR 19): set when this task carries
        # blockmap-resolved requests; the pool's direct leg serves their
        # passthru_off through it, falling back down the fault ladder
        self.passthru = None


def _resolve_passthru_dev() -> Optional[str]:
    """NVMe char device for the passthrough rung: exact path from env
    NSTPU_PASSTHRU_DEV, else the first match of config passthru_dev_glob
    (absent on CI hosts — the ladder then refuses with reason 'nodev')."""
    dev = os.environ.get("NSTPU_PASSTHRU_DEV")
    if dev:
        return dev
    import glob as _glob
    matches = sorted(_glob.glob(str(config.get("passthru_dev_glob"))))
    return matches[0] if matches else None


def _member_path(source, member: int) -> Optional[str]:
    """Filesystem path of one stripe member, or None when the source has
    no path-bearing member (RAM fakes) — blockmap needs a real path."""
    members = getattr(source, "members", None)
    if members:
        if 0 <= member < len(members):
            p = getattr(members[member], "path", None)
            return str(p) if p else None
        return None
    m = getattr(source, "_m", None)
    p = getattr(m, "path", None) if m is not None and member == 0 else None
    return str(p) if p else None


class _NativePassthruChannel:
    """Channel marker for the REAL passthrough rung: requests carrying a
    blockmap-resolved ``passthru_off`` are flagged NSTPU_REQ_PASSTHRU on
    the native submit and become URING_CMD NVMe READs in the engine
    (csrc/strom_engine.cc); ``pool_ok=False`` because the Python pool has
    no char-device access — its fallback legs use plain O_DIRECT."""

    pool_ok = False
    native = True

    def __init__(self, lba_shift: int):
        self.lba_shift = lba_shift
        self.lba_size = 1 << lba_shift


def _passthru_left_lane(task, r) -> None:
    """A blockmap-resolved extent is being served OFF the passthrough
    lane (mirror/buffered recovery rung, or a hedge win): count the lane
    exit so the lane's effectiveness stays observable."""
    stats.add("nr_passthru_fallback")
    if _trace.active and task.trace_id:
        _trace.instant("passthru_fallback", tid=task.trace_id,
                       member=r.member, offset=r.file_off,
                       length=r.length, args={"reason": "ladder"})


class Session:
    """Engine session: buffer registry + task table + error-retention domain.

    Maps the reference's ioctl-fd lifecycle onto an object: failed DMA tasks
    are retained for reaping by a later wait and force-reaped when the
    session closes (reference ``strom_proc_release``, kmod/nvme_strom.c:
    2138-2166)."""

    def __init__(self, *, max_workers: Optional[int] = None,
                 io_backend: Optional[str] = None):
        self._buffers: Dict[int, Tuple[object, BufferInfo]] = {}
        # Condition, not bare Lock: unmap_buffer waits on it and _put_buffer
        # signals, mirroring the refcount+wakeup drain of the driver
        # revocation callback (kmod/pmemmap.c:149-208) with no sleep-poll
        self._buf_lock = threading.Condition(threading.Lock())
        self._next_handle = 1
        self._next_task = 1
        # zero-cooperation observability (round 5): any process opening
        # a Session becomes visible to `tpu_stat -l` / `-p PID` without
        # opting in, the way every workload shows in the reference's
        # /proc counters (utils/nvme_stat.c:168-175); STROM_STAT_EXPORT=0
        # gates it off
        stats.default_export_start()
        _live_sessions.add(self)
        stats.add_export_hook(_fold_live_native_stats)
        # flight recorder (PR 7): trace_policy is read here, once — event
        # sites then cost one `_trace.active` branch when tracing is off
        _trace.configure()
        # unified extent space (ISSUE 20): one configure for the whole
        # capacity hierarchy — tier_ram_bytes/tier_hbm_bytes are read
        # here and every tier transition is rewired; hit/miss sites then
        # cost one `_tiers.lookup_active` branch when all tiers are off
        _tiers.configure()
        # resident-data integrity domain (ISSUE 16): `integrity` is read
        # here; fill/verify sites cost one `_integrity.active` branch off
        _integrity.configure()
        self._slots: List[Dict[int, DmaTask]] = [dict() for _ in range(_N_TASK_SLOTS)]
        self._slot_cv = [threading.Condition() for _ in range(_N_TASK_SLOTS)]
        self._id_lock = threading.Lock()
        nworkers = max_workers or min(config.get("queue_depth"), 32)
        self._pool = ThreadPoolExecutor(max_workers=nworkers,
                                        thread_name_prefix="strom-io")
        self._closed = False
        self._abandon_native = False
        self._members_used: set = set()  # members seen by native submits
        # io_uring fixed-buffer registrations: id(backing) -> slot (-1 =
        # attempted, unsupported).  The PRP-pool analog: register once,
        # every request into the region skips per-request page pinning.
        self._fixed_regs: Dict[int, int] = {}
        self._fixed_lock = threading.Lock()
        # fault-tolerance layer (PR 1): retry policy, per-member health,
        # and the task watchdog
        self._retry = RetryPolicy.from_config()
        self._member_health = MemberHealthMachine()
        self._retry_rng = random.Random(os.getpid() ^ id(self))
        # mirror-coherent writes (ISSUE 11): extents a degraded member
        # missed, replayed mirror->rejoiner by the canary thread before
        # the health machine lets the member back to HEALTHY
        self._resync = DirtyExtentJournal()
        self._member_health.attach_resync(self._resync)
        # resilience tier (PR 6): striped sources seen by submits, probed
        # by the background canary thread while any member is FAILED or
        # REJOINING (weak: canaries must never keep a closed source alive)
        self._canary_sources: "_weakref.WeakSet" = _weakref.WeakSet()
        self._canary_buf = None
        self._canary_stop = threading.Event()
        self._canary = threading.Thread(target=self._canary_loop,
                                        daemon=True,
                                        name="strom-canary")
        self._canary.start()
        # background scrubber (ISSUE 16): walks resident extents of all
        # tiers verifying stored crc32c, rate-limited by
        # scrub_bytes_per_sec (re-read each tick, canary-style); idles on
        # one Event wait per tick while disabled
        self._scrubber = _Scrubber(self)
        # self-driving data path (ISSUE 18): the per-session controller.
        # `autotune`/`readahead` are read at its construction (configure()
        # convention); hot paths test `self._tuner.enabled`/`.ra_active`
        # — one predicted branch each when off.  It also hosts the PR 4/5
        # adaptive chunk sizers as its chunk-cap policy, so there is
        # exactly one writer of the effective cap; the alias below keeps
        # the sizer dict reachable under its historical name (tests,
        # _fold_native_stats).  The thread starts at the end of __init__,
        # once the engine/backend choice is final.
        self._tuner = AutoTuner(self)
        # adaptive chunk sizing (PR 4, per-member since PR 5): one sizer
        # per stripe member so the effective request cap converges per
        # DEVICE — a slow member shrinks its own merges without throttling
        # healthy siblings.  Created lazily on the first adaptive memcpy;
        # single-file sources live under member 0.
        self._chunk_sizers: Dict[int, AdaptiveChunkSizer] = \
            self._tuner.chunk_sizers
        # lane scale-out (PR 5): the engine starts single-lane and is
        # rebuilt with one queue pair per stripe member at the first
        # striped submit (one-shot); swapped-out engines stay alive until
        # close() so in-flight waits complete against the engine that
        # accepted them
        self._lane_lock = threading.Lock()
        self._lanes_sized = False
        self._old_engines: List[object] = []
        # per-member executor lanes for the Python fallback path
        self._member_pools: Dict[int, ThreadPoolExecutor] = {}
        self._watchdog_stop = threading.Event()
        self._watchdog = threading.Thread(target=self._watchdog_loop,
                                          daemon=True,
                                          name="strom-task-watchdog")
        self._watchdog.start()
        # native engine: the GIL-free executor for planned request batches
        self._native = None
        self._passthru_dev: Optional[str] = None
        self._pt_channel: Optional[_NativePassthruChannel] = None
        want = io_backend or config.get("io_backend")
        fallback_ok = bool(config.get("io_fallback"))
        if want != "python":
            from . import _native as _nat
            if _nat.native_available():
                # NSTPU_RINGS env keeps working as the experiment
                # override; the config var is the durable setting.
                # Malformed values fall back (the C side's atol was
                # just as tolerant) — a typo must not kill Session().
                try:
                    rings = int(os.environ.get("NSTPU_RINGS", ""))
                except ValueError:
                    rings = int(config.get("engine_rings"))
                # engine_backend (PR 19) picks the rung when the legacy
                # io_backend var left the choice to the ladder; an explicit
                # io_backend=io_uring/threadpool keeps its pre-v4 meaning
                # (no passthru probe at all — bit-for-bit the old path)
                eng_backend = config.get("engine_backend")
                if want in ("io_uring", "threadpool"):
                    native_want = want
                else:
                    native_want = {"auto": "auto",
                                   "passthru": "nvme_passthru",
                                   "uring": "io_uring",
                                   "threadpool": "threadpool"}[eng_backend]
                if native_want in ("auto", "nvme_passthru"):
                    self._passthru_dev = _resolve_passthru_dev()
                try:
                    self._native = _nat.NativeEngine(
                        native_want, config.get("queue_depth"), rings=rings,
                        passthru_dev=self._passthru_dev)
                except (StromError, KeyError) as e:
                    # degrade one tier at a time: a refused passthru rung
                    # falls back to the AUTO ladder (refusal counted), an
                    # io_uring setup failure falls back to the native
                    # threadpool, a dead native engine falls back to the
                    # Python pool (io_fallback gates all; explicit
                    # non-auto without fallback keeps fail-fast)
                    if native_want == "nvme_passthru" and fallback_ok:
                        stats.add("nr_passthru_fallback")
                        if _trace.active:
                            _trace.instant("passthru_fallback",
                                           args={"reason": "create_failed"})
                        pr_warn("nvme passthru backend refused (%s); "
                                "falling back down the ladder", e)
                        try:
                            self._native = _nat.NativeEngine(
                                "auto", config.get("queue_depth"),
                                rings=rings,
                                passthru_dev=self._passthru_dev)
                        except StromError:
                            pass
                    elif want == "io_uring" and fallback_ok:
                        stats.add("nr_backend_fallback")
                        pr_warn("io_uring setup failed (%s); falling back "
                                "to threadpool backend", e)
                        try:
                            self._native = _nat.NativeEngine(
                                "threadpool", config.get("queue_depth"),
                                rings=rings)
                        except StromError:
                            pass
                    if self._native is None and want != "auto" \
                            and not fallback_ok:
                        raise
                if self._native is not None:
                    self._count_passthru_reason(_nat, native_want)
            elif want != "auto":
                if not fallback_ok:
                    raise StromError(
                        _errno.ENOSYS,
                        f"io_backend={want} requires the native engine")
                stats.add("nr_backend_fallback")
                pr_warn("io_backend=%s unavailable (no native engine); "
                        "falling back to python path", want)
        self.backend_name = (self._native.backend_name if self._native
                             else "python")
        stats.set_backend(self.backend_name)
        if _trace.active and self._native is not None:
            # per-lane native event ring: device submit->complete windows
            # are MEASURED by the engine and drained into the recorder
            self._native.trace_enable(True)
        self._tuner.start()
        pr_info("session open: backend=%s workers=%d",
                self.backend_name, nworkers)

    # -- NVMe passthrough lane (PR 19) -------------------------------------
    def _count_passthru_reason(self, nat, native_want: str) -> None:
        """Resolve how the engine ladder's passthrough rung landed.  A
        live rung gets the native channel (requests are then flagged
        through URING_CMD lanes); a refusal on a ladder that INCLUDED the
        rung is counted per reason.  Ladders that never had the rung
        (explicit io_uring/threadpool) count NOTHING — the
        zero-passthru-counters guarantee of engine_backend=uring|threadpool."""
        if native_want not in ("auto", "nvme_passthru"):
            return
        reason = self._native.passthru_reason()
        if reason is None:       # pre-v4 library: the rung does not exist
            return
        if reason == 0:
            # second probe for the LBA geometry the split math needs; the
            # engine already validated the format, so a failure here only
            # means "no split", never wrong SLBA math
            shift = None
            if self._passthru_dev:
                probed = nat.passthru_probe(self._passthru_dev)
                if isinstance(probed, int) and probed >= 9:
                    shift = probed
            if shift is not None:
                self._pt_channel = _NativePassthruChannel(shift)
            return
        name = nat.PASSTHRU_REASONS.get(reason, "nodev")
        stats.add("nr_passthru_refusal_" + name)
        if _trace.active:
            _trace.instant("passthru_fallback", args={"reason": name})

    def _passthru_channel(self, source):
        """The passthrough channel a task on ``source`` splits through:
        None when engine_backend pins a lower rung (zero-counters
        guarantee: off = bit-for-bit today's path), else the source's own
        channel (the CI emulator attaches one), else the native channel
        when the engine came up on the passthrough rung."""
        if config.get("engine_backend") in ("uring", "threadpool"):
            return None
        chan = getattr(source, "passthru_channel", None)
        if chan is not None:
            return chan
        return self._pt_channel

    def _passthru_split(self, task: DmaTask, source: Source,
                        reqs: List[Request], chan,
                        mirror_remap: Dict[int, int]) -> List[Request]:
        """Split planned requests onto the passthrough lane (the PR 9
        hit/miss split, per extent): each plain direct request whose span
        blockmap-resolves to LBA-aligned device ranges becomes one
        sub-request per physical extent carrying ``passthru_off``;
        everything else — buffered tails, vectored stripe merges,
        mirror-remapped members, unresolvable/ineligible spans — rides
        the O_DIRECT lanes of the SAME task untouched."""
        out: List[Request] = []
        lba = chan.lba_size
        for r in reqs:
            if r.buffered or r.dest_segs or r.passthru_off is not None \
                    or r.member in mirror_remap:
                out.append(r)
                continue
            path = _member_path(source, r.member)
            runs = blockmap.resolve_split(path, r.file_off, r.length, lba) \
                if path is not None else [(r.file_off, r.length, None)]
            if all(dev is None for (_f, _l, dev) in runs):
                stats.add("nr_passthru_refused_extent")
                if _trace.active and task.trace_id:
                    _trace.instant("passthru_refuse", tid=task.trace_id,
                                   member=r.member, offset=r.file_off,
                                   length=r.length)
                out.append(r)
                continue
            for foff, ln, dev_off in runs:
                doff = r.dest_off + (foff - r.file_off)
                if dev_off is None:
                    stats.add("nr_passthru_refused_extent")
                    if _trace.active and task.trace_id:
                        _trace.instant("passthru_refuse",
                                       tid=task.trace_id, member=r.member,
                                       offset=foff, length=ln)
                else:
                    stats.add("bytes_passthru", ln)
                out.append(Request(member=r.member, file_off=foff,
                                   length=ln, dest_off=doff,
                                   passthru_off=dev_off))
        return out

    # -- buffer registry (MAP/UNMAP/LIST/INFO analogs) ---------------------
    def alloc_dma_buffer(self, length: int, *, numa_node: int = -1) -> Tuple[int, DmaBuffer]:
        """ALLOC_DMA_BUFFER — declared but unimplemented in the reference
        (kmod/nvme_strom.c:2199-2201 returns -ENOTSUPP); implemented here."""
        buf = DmaBuffer(length, numa_node=numa_node)
        handle = self.map_buffer(buf.view(), kind="pinned_host", backing=buf)
        return handle, buf

    def map_buffer(self, view: memoryview, *, kind: str = "user",
                   backing: object = None, device: Optional[str] = None) -> int:
        view = view.cast("B")
        if (kind == "pinned_host" and self._native is not None
                and isinstance(backing, DmaBuffer)):
            self._register_fixed(backing)
        with self._buf_lock:
            handle = self._next_handle
            self._next_handle += 1
            info = BufferInfo(handle=handle, length=len(view), page_size=PAGE_SIZE,
                              n_pages=(len(view) + PAGE_SIZE - 1) // PAGE_SIZE,
                              owner_uid=os.getuid(), refcount=0, kind=kind,
                              device=device)
            self._buffers[handle] = ((view, backing), info)
        return handle

    def _register_fixed(self, backing: "DmaBuffer") -> None:
        """Register *backing* as an io_uring fixed buffer, once per buffer
        per session; the registration is released by the buffer's own
        close (so it can never outlive the mapping and alias a reuse of
        the address range).  Failed attempts are cached as slot -1 but
        still evicted on buffer close — ``id()`` recycles after GC, and a
        sticky sentinel would silently deny a NEW buffer the fast path."""
        key = id(backing)
        with self._fixed_lock:
            if key in self._fixed_regs:
                return
            slot = self._native.buf_register(backing.addr, backing.length)
            cb = lambda: self._unregister_fixed(key)  # noqa: E731
            self._fixed_regs[key] = (-1 if slot is None else slot,
                                     backing, cb)
        if not backing.on_close(cb):
            # buffer closed between register and hook-up: release now
            self._unregister_fixed(key)

    def _unregister_fixed(self, key: int) -> None:
        with self._fixed_lock:
            entry = self._fixed_regs.pop(key, None)
        if entry and entry[0] >= 0 and self._native is not None:
            try:
                self._native.buf_unregister(entry[0])
            except Exception:   # engine already closed: kernel freed it
                pass

    def _get_buffer(self, handle: int, need: int = 0) -> memoryview:
        with self._buf_lock:
            try:
                (view, _backing), info = self._buffers[handle]
            except KeyError:
                raise StromError(_errno.ENOENT, f"no mapped buffer {handle}") from None
            # UID ownership check (reference kmod/pmemmap.c:104-105,375-376)
            if info.owner_uid != os.getuid():
                raise StromError(_errno.EPERM, "buffer owned by another uid")
            if need > info.length:
                raise StromError(_errno.ERANGE,
                                f"buffer {handle} too small: {need} > {info.length}")
            self._buffers[handle] = ((view, _backing),
                                     BufferInfo(**{**info.__dict__,
                                                   "refcount": info.refcount + 1}))
            return view

    def _put_buffer(self, handle: int) -> None:
        with self._buf_lock:
            if handle in self._buffers:
                (vb, info) = self._buffers[handle]
                info = BufferInfo(**{**info.__dict__,
                                     "refcount": info.refcount - 1})
                self._buffers[handle] = (vb, info)
                if info.refcount == 0:
                    self._buf_lock.notify_all()

    def unmap_buffer(self, handle: int, *, wait: bool = True,
                     timeout: float = 30.0) -> None:
        """Blocks until in-flight DMA drains, like the driver revocation
        callback (kmod/pmemmap.c:149-208)."""
        deadline = time.monotonic() + timeout
        with self._buf_lock:
            while True:
                if handle not in self._buffers:
                    raise StromError(_errno.ENOENT, f"no mapped buffer {handle}")
                _, info = self._buffers[handle]
                if info.refcount == 0:
                    del self._buffers[handle]
                    return
                if not wait:
                    raise StromError(_errno.EBUSY, f"buffer {handle} has in-flight DMA")
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise StromError(_errno.ETIMEDOUT, f"buffer {handle} busy")
                self._buf_lock.wait(remaining)

    def list_buffers(self) -> List[int]:
        with self._buf_lock:
            return sorted(self._buffers)

    def info_buffer(self, handle: int) -> BufferInfo:
        with self._buf_lock:
            try:
                return self._buffers[handle][1]
            except KeyError:
                raise StromError(_errno.ENOENT, f"no mapped buffer {handle}") from None

    # -- task table --------------------------------------------------------
    def _slot_of(self, task_id: int) -> int:
        return task_id % _N_TASK_SLOTS

    def _create_task(self) -> DmaTask:
        with self._id_lock:
            tid = self._next_task
            self._next_task += 1
        task = DmaTask(tid, deadline_s=float(config.get("task_deadline_s")))
        if _trace.active:
            task.trace_id = _trace.task_begin(tid)
        s = self._slot_of(tid)
        with self._slot_cv[s]:
            self._slots[s][tid] = task
        return task

    def _watchdog_loop(self) -> None:
        """Latch ETIMEDOUT on tasks RUNNING past their deadline (PR 1).

        The reference can only hang forever when DMA never completes
        (its wait is interruptible but the task stays RUNNING); here the
        watchdog force-fails overdue tasks — waiters wake immediately,
        not-yet-started chunks see the latched error and cancel, and
        in-flight native waits abandon (``_await_native``)."""
        while not self._watchdog_stop.wait(0.05):
            now = time.monotonic()
            expired: List[str] = []
            for s, cv in enumerate(self._slot_cv):
                with cv:
                    for task in self._slots[s].values():
                        if (task.state is not DmaTaskState.RUNNING
                                or not task.deadline
                                or now <= task.deadline):
                            continue
                        task.expired = True
                        if task.errno_ == 0:
                            task.errno_ = _errno.ETIMEDOUT
                            task.errmsg = (
                                f"dma task {task.task_id} exceeded its "
                                f"{config.get('task_deadline_s')}s deadline "
                                f"({task.pending} chunks outstanding)")
                            stats.add("nr_task_timeout")
                            if _trace.active and task.trace_id:
                                _trace.instant(
                                    "task_timeout", tid=task.trace_id,
                                    args={"pending": task.pending})
                        # latch FAILED now (pending chunks drain later and
                        # cannot flip it back: errno_ is already set)
                        task.state = DmaTaskState.FAILED
                        cv.notify_all()
                        expired.append(task.errmsg)
            for msg in expired:   # outside the locks: slow stderr must
                pr_warn("watchdog: %s", msg)   # not stall completions

    def _canary_loop(self) -> None:
        """Background canary prober (PR 6): every ``canary_interval_s``,
        members the health machine flags (FAILED: detect recovery;
        REJOINING: advance warmup without client traffic) get one small
        direct read against each registered striped source.  A FAILED
        member that answers moves to REJOINING; warmup successes ramp a
        REJOINING member back to HEALTHY through the token bucket instead
        of a recovery cliff."""
        while True:
            interval = float(config.get("canary_interval_s"))
            if self._canary_stop.wait(interval if interval > 0 else 0.5):
                return
            if interval <= 0:
                continue
            cands = self._member_health.canary_candidates()
            if not cands:
                continue
            # dirty-extent resync first (ISSUE 11): drain what a
            # REJOINING member owes before the probes below advance its
            # warmup — the machine refuses HEALTHY while bytes are owed,
            # so ordering is a latency nicety, not a correctness hinge
            self._resync_replay(cands)
            for src in list(self._canary_sources):
                nmem = len(getattr(src, "members", ()))
                for m in cands:
                    if m >= nmem or self._canary_stop.is_set():
                        continue
                    self._canary_probe(src, m)

    def _canary_probe(self, source: Source, member: int) -> None:
        """One canary: a small direct read at member offset 0 (O_DIRECT
        needs an aligned buffer, so the scratch page is mmap-backed)."""
        try:
            size = getattr(source.members[member], "size", 0)
            blk = max(int(getattr(source, "block_size", 512)), 512)
            length = min(PAGE_SIZE, size // blk * blk)
            if length <= 0:
                return
            if self._canary_buf is None:
                self._canary_buf = mmap.mmap(-1, PAGE_SIZE)
            source.read_member_direct(
                member, 0, memoryview(self._canary_buf)[:length])
        except (StromError, OSError) as e:
            if getattr(e, "errno", None) == _errno.EBADF:
                return   # source closed under the prober: not a verdict
            self._member_health.record_canary(member, False)
        except Exception:
            return       # a broken probe must never kill the thread
        else:
            self._member_health.record_canary(member, True)

    def _scrub_refill(self, source: Optional[Source], base: int,
                      length: int) -> Optional[bytes]:
        """Scrub heal (ISSUE 16): re-read one resident extent's bytes
        from SSD through the normal submit path — the full fault ladder
        (retry/hedge/mirror/checksum re-read) heals them, and the
        wait-time cache_fill hook reinstalls the extent under the same
        key (the corrupt entry was already dropped, so the read is a
        clean miss).  Returns the healed bytes, or None when the source
        is gone or the extent no longer maps onto its chunk grid."""
        if source is None or getattr(source, "closed", False):
            return None
        size = getattr(source, "size", 0)
        # recover the chunk grid from (base, length): a full chunk is its
        # own pow2 grid; a tail chunk's grid is the smallest pow2 that
        # both covers it and divides base
        cs = length
        if cs & (cs - 1):
            cs = 1 << (length - 1).bit_length()
        while cs < size and base % cs:
            cs <<= 1
        if cs <= 0 or base % cs or min(cs, size - base) != length:
            return None
        handle = None
        try:
            handle, buf = self.alloc_dma_buffer(max(length, PAGE_SIZE))
            res = self.memcpy_ssd2ram(source, handle, [base // cs], cs)
            self.memcpy_wait(res.dma_task_id)
            return bytes(buf.view()[:length])
        except (StromError, OSError):
            return None
        finally:
            if handle is not None:
                try:
                    self.unmap_buffer(handle)
                except StromError:  # pragma: no cover - closing session
                    pass

    def _journal_skipped(self, sink: Source, member: int, file_off: int,
                         length: int, trace_id: int = 0) -> None:
        """Record an extent a degraded member missed (the write landed
        only on its mirror partner) in the resync journal."""
        self._resync.record(sink, member, file_off, length)
        if _trace.active:
            _trace.instant("resync_skip", tid=trace_id,
                           member=member, offset=file_off, length=length)

    def _resync_replay(self, members: Sequence[int]) -> None:
        """Replay journaled dirty extents onto REJOINING members:
        read-from-mirror -> write-to-rejoiner, throttled by the member's
        rejoin token bucket (the resync budget).  Runs on the canary
        thread; a replay failure re-journals the extent and debits the
        failing member, so debt never silently evaporates."""
        health = self._member_health
        jrn = self._resync
        for member in members:
            if member not in jrn.members():
                continue
            if health.state(member) is not HealthState.REJOINING:
                continue
            for ref in jrn.sink_refs(member):
                sink = ref()
                if sink is None:
                    continue
                mirror = sink.mirror_of(member)
                if mirror is None:    # mirror map changed under the debt:
                    jrn.drop_sink(ref)  # nothing to replay from
                    continue
                while not self._canary_stop.is_set():
                    if not health.take_rejoin_token(member):
                        break          # budget spent; next canary tick
                    ext = jrn.take_extent(ref, member)
                    if ext is None:
                        break
                    off, length = ext
                    if not self._replay_extent(sink, mirror, member,
                                               off, length):
                        break

    def _replay_extent(self, sink: Source, mirror: int, member: int,
                       file_off: int, length: int) -> bool:
        """One resync extent: mirror's bytes -> rejoiner.  Aligned spans
        ride the direct legs; misaligned (buffered-leg) debt rides the
        buffered legs.  Returns False when replay must pause."""
        t0 = time.monotonic_ns()
        # per-extent anonymous scratch (page-aligned, so the direct legs
        # accept it); its cost is noise next to the replayed I/O, and a
        # local avoids sharing a cached buffer across threads
        sz = max(length, PAGE_SIZE)
        sz = (sz + PAGE_SIZE - 1) // PAGE_SIZE * PAGE_SIZE
        scratch = mmap.mmap(-1, sz)
        mv = memoryview(scratch)[:length]
        try:
            return self._replay_extent_into(
                sink, mirror, member, file_off, length, mv, t0)
        finally:
            mv.release()
            scratch.close()

    def _replay_extent_into(self, sink: Source, mirror: int, member: int,
                            file_off: int, length: int, buf: memoryview,
                            t0: int) -> bool:
        bs = max(int(getattr(sink, "block_size", 512)), 512)
        aligned = file_off % bs == 0 and length % bs == 0
        try:
            if aligned:
                sink.read_member_direct(mirror, file_off, buf)
            else:
                sink.read_member_buffered(mirror, file_off, buf)
        except (StromError, OSError) as e:
            if getattr(e, "errno", None) == _errno.EBADF:
                return False   # sink closed under the replay
            se = e if isinstance(e, StromError) else \
                StromError(e.errno or _errno.EIO, str(e))
            self._member_health.record_failure(
                mirror, fatal=se.error_class is ErrorClass.PERSISTENT)
            stats.member_error(mirror)
            self._resync.put_back(sink, member, file_off, length)
            return False
        except Exception:
            self._resync.put_back(sink, member, file_off, length)
            return False
        try:
            if aligned:
                sink.write_member_direct(member, file_off, buf)
            else:
                sink.write_member_buffered(member, file_off, buf)
        except (StromError, OSError) as e:
            if getattr(e, "errno", None) == _errno.EBADF:
                return False
            se = e if isinstance(e, StromError) else \
                StromError(e.errno or _errno.EIO, str(e))
            self._member_health.record_failure(
                member, fatal=se.error_class is ErrorClass.PERSISTENT)
            stats.member_error(member)
            self._resync.put_back(sink, member, file_off, length)
            return False
        except Exception:
            self._resync.put_back(sink, member, file_off, length)
            return False
        stats.add("nr_resync_extent")
        stats.member_add(member, length, time.monotonic_ns() - t0)
        if _trace.active:
            _trace.span("resync", t0, time.monotonic_ns(), member=member,
                        offset=file_off, length=length,
                        args={"mirror": mirror})
        return True

    def _task_get(self, task: DmaTask) -> None:
        s = self._slot_of(task.task_id)
        with self._slot_cv[s]:
            assert not task.frozen, "get on frozen dtask (use-after-submit)"
            task.pending += 1

    def _task_put(self, task: DmaTask, err: Optional[StromError] = None) -> None:
        s = self._slot_of(task.task_id)
        latched = None
        with self._slot_cv[s]:
            if err is not None and task.errno_ == 0:
                # first error wins (reference strom_put_dma_task, :770-776)
                task.errno_ = err.errno
                task.errmsg = str(err)
                latched = err
            task.pending -= 1
            done = task.pending == 0
            if done:
                task.state = (DmaTaskState.FAILED if task.errno_
                              else DmaTaskState.DONE)
                stats.count_clock("ssd2dev", time.monotonic_ns() - task.t_submit)
                self._slot_cv[s].notify_all()
        if latched is not None:
            if _trace.active and task.trace_id:
                _trace.instant("task_failed", tid=task.trace_id,
                               args={"errno": latched.errno,
                                     "error": str(latched)[:160]})
            # outside the lock: a slow stderr must not stall completions
            pr_warn("dma task %d latched error: %s", task.task_id, latched)
        if done and task.buf_handle is not None:
            self._put_buffer(task.buf_handle)

    def memcpy_wait(self, task_id: int, timeout: Optional[float] = None) -> MemCopyResult:
        """MEMCPY_WAIT: block until the task completes; reap it.

        Raises :class:`StromError` with the latched first error for failed
        tasks (which are *retained* until this reap or session close).  The
        waiter loop mirrors the reference's spurious-wakeup handling
        (``strom_dma_task_wait``, kmod/nvme_strom.c:1230-1316), counting
        wrong wakeups."""
        s = self._slot_of(task_id)
        cv = self._slot_cv[s]
        deadline = None if timeout is None else time.monotonic() + timeout
        # a poll that times out (or an unknown id) raises out of the stage
        # uncounted; the blocking part alone is the stage
        with stats.stage("ioctl_memcpy_wait", ring="wait") as st, cv:
            while True:
                task = self._slots[s].get(task_id)
                if task is None:
                    raise StromError(_errno.ENOENT, f"unknown dma task {task_id}")
                if task.state in (DmaTaskState.DONE, DmaTaskState.FAILED):
                    del self._slots[s][task_id]  # reap
                    break
                remain = None if deadline is None else deadline - time.monotonic()
                if remain is not None and remain <= 0:
                    raise StromError(_errno.ETIMEDOUT, f"dma task {task_id} timeout")
                if not cv.wait(remain):
                    raise StromError(_errno.ETIMEDOUT, f"dma task {task_id} timeout")
                if task.state == DmaTaskState.RUNNING:
                    stats.add("nr_wrong_wakeup")
            st.tid = task.trace_id
            if task.errno_:
                st.args = {"errno": task.errno_}
        if task.errno_:
            if _trace.active:
                # the flight-recorder moment: dump what the engine did in
                # the window before this task latched (bounded per process)
                _trace.dump_on_failure(
                    f"task {task_id} errno {task.errno_}")
            raise StromError(task.errno_, task.errmsg or "async DMA failed")
        if task.verify_reqs:
            # zero-copy landing: the native engine read straight into the
            # caller's (staging) buffer, so checksum verification runs
            # HERE on the retired slot — off the submission critical path
            # — with the same re-read-then-latch-EBADMSG ladder the pool
            # path applies inline (mismatches heal via read_member_direct,
            # so fault injection on that leg still exercises the ladder)
            for r in task.verify_reqs:
                self._verify_request_checksums(task.verify_src, r,
                                               task.verify_dest)
        if task.cache_fill is not None:
            # demand-fault fills run HERE, on the retired task: the
            # destination bytes have been healed by the full fault
            # ladder (retry/hedge/mirror/checksum re-read), so a
            # degraded member still populates the hierarchy via its
            # surviving legs — and a latched failure never fills
            skey, fills, fdest, lscale, src_ref, spec = task.cache_fill
            task.cache_fill = None
            for base, length, doff in fills:
                tf0 = time.monotonic_ns()
                if _tiers.fault_fill(skey, base, length,
                                     fdest[doff:doff + length],
                                     logical_length=int(length * lscale),
                                     source_ref=src_ref, speculative=spec) \
                        and _trace.active and task.trace_id:
                    _trace.span("cache_fill", tf0, time.monotonic_ns(),
                                tid=task.trace_id, offset=base,
                                length=length)
        if task.cache_invalidate is not None:
            # re-run the write path's invalidation after the write has
            # retired: a racing read may have re-filled a written extent
            # from pre-write bytes between submit and completion
            skey, extents = task.cache_invalidate
            task.cache_invalidate = None
            _tiers.invalidate_extents(skey, extents)
        if task.write_verify is not None:
            # write_verify (ISSUE 11): read each retired write leg back
            # and compare crc32c against the submitted bytes — a torn or
            # misdirected write surfaces HERE, at the durability boundary,
            # instead of on some future read.  Runs on the reaped slot,
            # off the submission critical path, like verify_reqs above.
            wsink, wreqs, wsrc = task.write_verify
            task.write_verify = None
            self._verify_writes(wsink, wreqs, wsrc, task)
        assert task.result is not None
        return task.result

    def pending_tasks(self) -> List[int]:
        out: List[int] = []
        for s, cv in enumerate(self._slot_cv):
            with cv:
                out.extend(self._slots[s])
        return sorted(out)

    # -- memcpy commands ---------------------------------------------------
    def memcpy_ssd2ram(self, source: Source, buf_handle: int,
                       chunk_ids: Sequence[int], chunk_size: int, *,
                       dest_offset: int = 0,
                       wb_buffer: Optional[memoryview] = None,
                       speculative: bool = False) -> MemCopyResult:
        """MEMCPY_SSD2RAM/SSD2GPU submit path.

        Plans + submits asynchronously, returning a :class:`MemCopyResult`
        whose ``chunk_ids`` is the reordered array (direct-I/O chunks first,
        page-cache write-back chunks at the tail — reference contract
        kmod/nvme_strom.h:99-101).  When *wb_buffer* is given, write-back
        chunks are copied there (tail-packed) instead of the destination,
        exactly the SSD2GPU contract where the caller performs the
        RAM->device copy itself (kmod/nvme_strom.c:1647-1663); otherwise they
        are copied straight into the destination (SSD2RAM behaviour,
        :1926-1934).

        Write-back bytes, like direct bytes, are valid only after
        :meth:`memcpy_wait` returns: runs of at least
        ``_WRITEBACK_ASYNC_MIN`` bytes are copied on the session's I/O
        pool, and a failed copy surfaces from the wait with its errno.

        ``speculative`` marks a readahead prefetch (ISSUE 18): the task
        skips the residency-tier hit split (a prefetch of resident data
        has nothing to do), does not train the readahead predictor, and
        its wait-time cache fills carry provenance so ARC's ghost lists
        stay blind to speculation."""
        # the caller's whole submit, the inline page-cache copies included
        with stats.stage("ioctl_memcpy_submit") as st:
            return self._submit_read(st.t0, source, buf_handle, chunk_ids,
                                     chunk_size, dest_offset, wb_buffer,
                                     speculative)

    def _submit_read(self, t0: int, source: Source, buf_handle: int,
                     chunk_ids: Sequence[int], chunk_size: int,
                     dest_offset: int, wb_buffer: Optional[memoryview],
                     speculative: bool) -> MemCopyResult:
        """The body of :meth:`memcpy_ssd2ram`, entered at *t0*."""
        if self._closed:
            raise StromError(_errno.EBADF, "session closed")
        if chunk_size <= 0 or (chunk_size & (chunk_size - 1)):
            raise StromError(_errno.EINVAL, f"chunk_size {chunk_size} must be pow2")
        chunk_ids = list(chunk_ids)
        n = len(chunk_ids)
        if n == 0:
            raise StromError(_errno.EINVAL, "no chunks")
        # exact-size destinations (zero-copy landing, tail slots): a
        # single-chunk task only needs the chunk's TRUE length, which may
        # be a partial tail shorter than chunk_size
        need = dest_offset + n * chunk_size
        if n == 1:
            tail = min(chunk_size, source.size - chunk_ids[0] * chunk_size)
            if tail > 0:
                need = dest_offset + tail
        dest = self._get_buffer(buf_handle, need=need)
        task = self._create_task()
        if _trace.active and task.trace_id:
            _trace.instant("submit", tid=task.trace_id, ts_ns=t0,
                           length=n * chunk_size,
                           args={"task": task.task_id, "chunks": n})
        cache_hits: List[tuple] = []  # (cid, base, length, lease)
        try:
            spans_all: List[Tuple[int, int]] = []
            for cid in chunk_ids:
                base = cid * chunk_size
                length = min(chunk_size, source.size - base)
                if length <= 0:
                    raise StromError(_errno.EINVAL, f"chunk {cid} beyond EOF")
                spans_all.append((base, length))
            if self._tuner.ra_active and not speculative:
                # readahead training tap (ISSUE 18): every demand span
                # feeds the per-source predictor — including spans the
                # hit split below serves entirely from cache, so a
                # cache-warm stream keeps its pattern model current
                self._tuner.observe_submit(source, chunk_size, chunk_ids)
            # --- residency-tier split (ISSUE 9) ---------------------------
            # hits take a pinned lease and are served by memcpy below —
            # no submission, no mincore probe; only the misses go on to
            # page-cache arbitration and the member lanes
            skey = None
            miss_ids, spans = chunk_ids, spans_all
            if _tiers.lookup_active and not speculative:
                skey = _tiers.source_key(source)
                miss_ids, spans = [], []
                nr_hbm = 0
                for cid, (base, length) in zip(chunk_ids, spans_all):
                    # ONE top-down lookup over the unified space
                    # (ISSUE 20): the HBM tier outranks RAM — a device-
                    # resident extent costs one device→dest copy and
                    # never touches a host slab
                    hit = _tiers.lookup(skey, base, length)
                    if hit is not None:
                        lease, tname = hit
                        hbm = tname == "hbm"
                        if hbm:
                            nr_hbm += 1
                        cache_hits.append((cid, base, length, lease, hbm))
                    else:
                        miss_ids.append(cid)
                        spans.append((base, length))
                if nr_hbm:
                    stats.add("nr_hbm_hit", nr_hbm)
                if len(cache_hits) > nr_hbm:
                    stats.add("nr_cache_hit", len(cache_hits) - nr_hbm)
                if cache_hits:
                    stats.add("bytes_cache_hit",
                              sum(h[2] for h in cache_hits))
                if miss_ids:
                    stats.add("nr_cache_miss", len(miss_ids))
                if not _tiers.fill_active:
                    skey = None  # no RAM tier: nothing to fill at wait
            elif _tiers.fill_active:
                # speculative prefetch (ISSUE 18): no hit split — the
                # issue loop already peeked residency — but the misses
                # must still demand-fault into the RAM tier at wait time
                skey = _tiers.source_key(source)

            # --- cache arbitration (write-back vs direct) -----------------
            threshold = config.get("cache_threshold")
            arbitrate = config.get("cache_arbitration")
            direct_ids: List[int] = []
            wb_ids: List[int] = []
            if arbitrate and miss_ids:
                # one batched residency probe for the whole task (real file
                # sources fold it into a single mincore scan); hot/dirty
                # data is decisive, not weighted: the reference scores one
                # dirty page at threshold+1 (:1643), because a direct read
                # of a dirty range either stalls on a forced flush or reads
                # stale blocks
                for cid, (cached, hot) in zip(miss_ids,
                                              source.residency(spans)):
                    if hot > 0.0 or cached > threshold:
                        wb_ids.append(cid)
                    else:
                        direct_ids.append(cid)
            else:
                direct_ids = list(miss_ids)
            # hits tail-pack after the write-back slots so the result's
            # RAM-sourced region stays one contiguous tail
            # (MemCopyResult contract: ssd chunks first)
            new_order = direct_ids + wb_ids + [h[0] for h in cache_hits]
            nr_ssd = len(direct_ids)

            # --- plan + submit direct requests (sliding window) -----------
            # the chunk list is planned and submitted in slices of
            # submit_window chunks: the first slice's I/O is in flight
            # while later slices are still being planned, so queue
            # occupancy never drains at a chunk-plan boundary (the
            # reference keeps every device queue full the same way,
            # kmod/nvme_strom.c:1136-1224)
            # the native engine executes batches GIL-free when the source
            # reads through plain fds (test fakes that override the read
            # leg take the Python path so injection still works); with
            # checksum_verify on, verification moves to wait time on the
            # retired zero-copy slot instead of disabling the native path
            use_native = (self._native is not None and direct_ids
                          and type(source).read_member_direct
                          is Source.read_member_direct)
            if use_native:
                self._ensure_member_lanes(source)
            if len(getattr(source, "members", ())) > 1:
                # resilience tier (PR 6): striped sources become canary
                # targets so FAILED members are re-probed in background
                self._canary_sources.add(source)
            dma_max = int(config.get("dma_max_size"))
            if self._tuner.enabled:
                # effective-knob indirection (ISSUE 18): with the
                # controller on, the tuned per-member cap owns the
                # request split/merge size on both paths (still inside
                # dma_max_size's declared bounds)
                dma_max = self._tuner.dma_cap(dma_max)
            # coalescing beyond dma_max is the native-queue saturation
            # lever; the pool path keeps classic per-extent planning so
            # fault injection and the retry ladder see every extent
            climit = int(config.get("coalesce_limit")) if use_native else 0
            if climit and config.get("chunk_adaptive"):
                nmem_src = len(getattr(source, "members", ())) or 1
                if nmem_src > 1:
                    climit = {m: self._adaptive_cap(dma_max, climit, member=m)
                              for m in range(nmem_src)}
                else:
                    climit = self._adaptive_cap(dma_max, climit)
            verify = bool(config.get("checksum_verify"))
            window = max(int(config.get("submit_window")), 1)
            if self._tuner.enabled:
                window = max(self._tuner.submit_window(window), 1)
            entries = [(cid, i) for i, cid in enumerate(direct_ids)]
            fds = source.member_fds() if use_native else None
            # degraded-mode striping on the native path (PR 6): extents of
            # a member the health machine routes away (QUARANTINED/FAILED)
            # are submitted against the mirror partner's fd — and lane —
            # at direct speed, instead of collapsing to the buffered path
            mirror_remap: Dict[int, int] = {}
            if use_native:
                for m in range(len(fds)):
                    if self._member_health.routes_away(m):
                        mir = source.mirror_of(m)
                        if mir is not None and \
                                not self._member_health.routes_away(mir):
                            mirror_remap[m] = mir
            # NVMe passthrough split (PR 19): one channel per task; each
            # planned window then splits per extent below.  The channel
            # must match the executing path — native tasks need the real
            # URING_CMD rung, pool tasks need a pool-capable (emulator)
            # channel with Python-side command service.
            pt_chan = self._passthru_channel(source) if direct_ids else None
            if pt_chan is not None:
                pt_ok = getattr(pt_chan, "native", False) if use_native \
                    else getattr(pt_chan, "pool_ok", False)
                if not pt_ok:
                    pt_chan = None
            if pt_chan is not None:
                task.passthru = pt_chan
            native_failed = False
            for w in range(0, len(entries), window):
                with stats.stage("setup_prps", ring="plan",
                                 tid=task.trace_id) as st:
                    reqs = plan_requests(source, entries[w:w + window],
                                         chunk_size, dest_offset,
                                         coalesce_limit=climit or None)
                    if st.tid:
                        st.args = {"window": w // window,
                                   "requests": len(reqs)}
                if pt_chan is not None:
                    reqs = self._passthru_split(task, source, reqs,
                                                pt_chan, mirror_remap)
                if not use_native or native_failed:
                    self._submit_pool_requests(task, source, reqs, dest)
                    continue
                native_reqs = []
                native_members = []
                native_rs = []
                native_pt = []
                for r in reqs:
                    if r.buffered or fds[r.member] < 0:
                        # misaligned tails: synchronous buffered copy, like
                        # the reference's in-ioctl page-cache memcpy —
                        # accounted like the pool path so per-member stats
                        # agree regardless of which branch executed
                        tb = time.monotonic_ns()
                        source.read_member_buffered(
                            r.member, r.file_off,
                            dest[r.dest_off:r.dest_off + r.length])
                        stats.member_add(r.member, r.length,
                                         time.monotonic_ns() - tb)
                        stats.count_clock("submit_dma", 0)
                        stats.add("total_dma_length", r.length)
                        if verify:
                            # sync legs verify here: they never reach the
                            # wait-time hook (only native_rs do)
                            self._verify_request_checksums(source, r, dest)
                    elif r.dest_segs:
                        # vectored (stripe-coalesced) reads split back into
                        # per-segment submissions for the native engine —
                        # its deep per-ring queue already holds them all;
                        # the vectored form pays off on the preadv pool path
                        m_eff = mirror_remap.get(r.member, r.member)
                        if m_eff != r.member:
                            stats.add("nr_mirror_read")
                            if _trace.active and task.trace_id:
                                _trace.instant(
                                    "mirror_read", tid=task.trace_id,
                                    member=r.member, offset=r.file_off,
                                    length=r.length,
                                    args={"mirror": m_eff})
                        foff = r.file_off
                        for dseg, lseg in r.dest_segs:
                            native_reqs.append((fds[m_eff], foff, lseg,
                                                dseg))
                            native_members.append(m_eff)
                            native_pt.append(False)
                            foff += lseg
                        native_rs.append(r)
                    else:
                        m_eff = mirror_remap.get(r.member, r.member)
                        if m_eff != r.member:
                            stats.add("nr_mirror_read")
                            if _trace.active and task.trace_id:
                                _trace.instant(
                                    "mirror_read", tid=task.trace_id,
                                    member=r.member, offset=r.file_off,
                                    length=r.length,
                                    args={"mirror": m_eff})
                        if r.passthru_off is not None:
                            # raw-command lane: the engine reads the char
                            # device at the blockmap-resolved offset; the
                            # member fd rides along for bookkeeping only
                            native_reqs.append((fds[m_eff], r.passthru_off,
                                                r.length, r.dest_off))
                            native_pt.append(True)
                        else:
                            native_reqs.append((fds[m_eff], r.file_off,
                                                r.length, r.dest_off))
                            native_pt.append(False)
                        native_members.append(m_eff)
                        native_rs.append(r)
                if not native_reqs:
                    continue
                try:
                    self._members_used.update(native_members)
                    addr = ctypes.addressof(
                        ctypes.c_char.from_buffer(dest))
                    # capture the engine: a concurrent lane scale-out may
                    # swap self._native, and the wait must run against
                    # the engine that accepted the batch
                    nat = self._native
                    if any(native_pt):
                        nid = nat.submit(addr, native_reqs,
                                         members=native_members,
                                         passthru=native_pt)
                    else:
                        nid = nat.submit(addr, native_reqs,
                                         members=native_members)
                    if _trace.active and task.trace_id:
                        _trace.instant(
                            "native_submit", tid=task.trace_id,
                            length=sum(q[2] for q in native_reqs),
                            args={"requests": len(native_reqs),
                                  "batch": nid})
                    self._task_get(task)
                    try:
                        self._pool.submit(self._await_native, task, nat, nid)
                    except BaseException as e:
                        self._task_put(task, StromError(
                            _errno.ESHUTDOWN, str(e)))
                        raise
                    if verify:
                        if task.verify_reqs is None:
                            task.verify_src = source
                            task.verify_dest = dest
                            task.verify_reqs = []
                        task.verify_reqs.extend(native_rs)
                except StromError as e:
                    # native submit failure degrades to the Python
                    # pool path instead of failing the whole memcpy
                    # (tentpole degradation tier 3); later windows skip
                    # straight to the pool
                    if not config.get("io_fallback"):
                        raise
                    stats.add("nr_backend_fallback")
                    pr_warn("native submit failed (%s); batch falls "
                            "back to the python pool path", e)
                    native_failed = True
                    self._submit_pool_requests(task, source, native_rs,
                                               dest)

            # --- write-back copies, AFTER direct submission so the device
            #     queue fills first.  A run of file-consecutive chunks sits
            #     in consecutive slots, so it is one read of up to
            #     coalesce_limit bytes: a syscall per chunk of small chunks
            #     (the sharded loader's 8 KiB pages) overran the task
            #     deadline on a cached GiB file.  A large run is a task
            #     reference queued on the I/O pool (the copy drops the
            #     interpreter lock, so the caller plans and dispatches
            #     beside it) and completes at memcpy_wait like a direct
            #     read; a short one costs less than the handoff and is
            #     copied here, like the reference's in-ioctl memcpy
            run_max = max(chunk_size, int(config.get("coalesce_limit")))
            target = wb_buffer if wb_buffer is not None else dest
            r0 = 0
            while r0 < len(wb_ids):
                r1 = r0 + 1
                while (r1 < len(wb_ids) and wb_ids[r1] == wb_ids[r1 - 1] + 1
                       and (r1 - r0 + 1) * chunk_size <= run_max):
                    r1 += 1
                base = wb_ids[r0] * chunk_size
                length = min((r1 - r0) * chunk_size, source.size - base)
                off = ((dest_offset if wb_buffer is None else 0)
                       + (nr_ssd + r0) * chunk_size)
                piece = target[off:off + length]
                if length >= _WRITEBACK_ASYNC_MIN:
                    self._task_get(task)
                    try:
                        self._pool.submit(self._writeback_copy, task,
                                          source, base, piece)
                    except BaseException as e:
                        self._task_put(task, StromError(
                            _errno.ESHUTDOWN, str(e)))
                        raise
                else:
                    with stats.stage("pagecache_copy", ring="writeback",
                                     tid=task.trace_id, offset=base,
                                     length=length):
                        source.read_buffered(base, piece)
                r0 = r1

            # --- residency-tier hit serving (tail-packed after the
            #     write-back slots): memcpy out of the pinned slab, no
            #     submission — a fully-resident task reaches here with
            #     nothing submitted at all
            j = 0
            while cache_hits:
                cid, base, length, lease, hbm = cache_hits.pop(0)
                slot = nr_ssd + len(wb_ids) + j
                j += 1
                target = wb_buffer if wb_buffer is not None else dest
                off = (dest_offset if wb_buffer is None else 0) \
                    + slot * chunk_size
                th0 = time.monotonic_ns()
                try:
                    if not lease.copy_into(target[off:off + length]):
                        # invalidated between lookup and serve: the
                        # write that staled the slab wins — read fresh
                        source.read_buffered(base,
                                             target[off:off + length])
                finally:
                    lease.release()
                if _trace.active and task.trace_id:
                    _trace.span("cache_hit", th0, time.monotonic_ns(),
                                tid=task.trace_id, offset=base,
                                length=length,
                                args=({"tier": "hbm"} if hbm else None))

            # --- record the miss fills, consumed at wait time once the
            #     fault ladder has healed the destination bytes (direct
            #     chunks land in `dest` even when wb_buffer is given)
            if skey is not None and direct_ids:
                fills = []
                for i, cid in enumerate(direct_ids):
                    base = cid * chunk_size
                    fills.append((base,
                                  min(chunk_size, source.size - base),
                                  dest_offset + i * chunk_size))
                task.cache_fill = (skey, fills, dest,
                                   getattr(source, "logical_scale", 1.0),
                                   _weakref.ref(source), speculative)
        except BaseException:
            while cache_hits:  # leases not yet served: unpin them
                cache_hits.pop()[3].release()
            self._task_put(task, StromError(_errno.ECANCELED, "submit aborted"))
            # reference waits out in-flight DMA on submit error (:1781-1784)
            try:
                self.memcpy_wait(task.task_id, timeout=30.0)
            except StromError:
                pass
            self._put_buffer(buf_handle)
            raise
        result = MemCopyResult(dma_task_id=task.task_id, nr_chunks=n,
                               nr_ssd2dev=nr_ssd, nr_ram2dev=n - nr_ssd,
                               chunk_ids=new_order)
        task.result = result
        # freeze: submission loop done, no further refs (reference :1766-1767)
        sidx = self._slot_of(task.task_id)
        with self._slot_cv[sidx]:
            task.frozen = True
        task.buf_handle = buf_handle
        self._task_put(task)  # drop creator ref; releases the buffer ref on completion
        return result

    # SSD->device is the same submit path; the HBM leg lives in hbm.staging.
    memcpy_ssd2dev = memcpy_ssd2ram

    def memcpy_ram2ssd(self, sink: Source, buf_handle: int,
                       chunk_ids: Sequence[int], chunk_size: int, *,
                       src_offset: int = 0) -> MemCopyResult:
        """RAM→SSD write submit path (exceeds the read-only reference).

        Buffer slot *i* (``src_offset + i*chunk_size``) is written to sink
        chunk ``chunk_ids[i]``.  Planning reuses the read-side merge logic
        (same extents, same ≤dma_max requests, buffered legs for
        misaligned pieces); writes are always direct — there is no cache
        to arbitrate against.  Aligned legs run GIL-free on the native
        engine (IORING_OP_WRITE) when available, mirroring the read path;
        misaligned tails take a synchronous buffered write.  Durability of
        buffered legs needs a ``sink.sync()`` after the wait."""
        t0 = time.monotonic_ns()
        if self._closed:
            raise StromError(_errno.EBADF, "session closed")
        if chunk_size <= 0 or (chunk_size & (chunk_size - 1)):
            raise StromError(_errno.EINVAL, f"chunk_size {chunk_size} must be pow2")
        sink._check_writable()
        chunk_ids = list(chunk_ids)
        n = len(chunk_ids)
        if n == 0:
            raise StromError(_errno.EINVAL, "no chunks")
        src = self._get_buffer(buf_handle, need=src_offset + n * chunk_size)
        task = self._create_task()
        try:
            # passthrough coherency (PR 19): a write-back may relocate
            # extents (CoW filesystems); drop the cached file->LBA maps at
            # the same site the resident cache invalidates, so the next
            # passthrough split re-resolves against post-write reality
            blockmap.invalidate_source(sink)
            if _tiers.lookup_active:
                # write-back coherency (ISSUE 9): ONE invalidation
                # contract over the whole hierarchy (ISSUE 20) — drop
                # every tier's resident extents the write touches before
                # any byte moves, and again at wait time
                # (task.cache_invalidate) in case a racing read
                # re-filled from pre-write bytes mid-flight
                wkey = _tiers.source_key(sink)
                extents = [(cid * chunk_size, chunk_size)
                           for cid in chunk_ids]
                _tiers.invalidate_extents(wkey, extents)
                task.cache_invalidate = (wkey, extents)
            with stats.stage("setup_prps"):
                reqs = plan_requests(sink, [(cid, i) for i, cid in enumerate(chunk_ids)],
                                     chunk_size, src_offset)
            if len(getattr(sink, "members", ())) > 1:
                # written striped sinks become canary targets too
                # (ISSUE 11): the canary thread replays their dirty-extent
                # resync journal while a degraded member rejoins
                self._canary_sources.add(sink)
            if config.get("write_verify"):
                # wait-time read-back verification rides the retired task
                task.write_verify = (sink, list(reqs), src)
            # GIL-free write leg, mirroring the read path's native branch
            # (fakes overriding the write leg keep the Python path so
            # fault injection still works)
            use_native = (self._native is not None and reqs
                          and type(sink).write_member_direct
                          is Source.write_member_direct)
            pool_reqs = list(reqs) if not use_native else []
            if use_native:
                self._ensure_member_lanes(sink)
                fds = sink.member_fds()
                health = self._member_health
                native_reqs = []
                native_members = []
                native_rs = []      # unique planned requests riding native
                n_mirror_legs = 0
                for r in reqs:
                    mirror = sink.mirror_of(r.member)
                    if r.buffered or fds[r.member] < 0 or \
                            (mirror is not None and fds[mirror] < 0):
                        # misaligned tails (and legs without a direct fd)
                        # ride the pool ladder (ISSUE 11) — transient
                        # retry, cancellation-on-latch and mirror fan-out
                        # instead of the old unpoliced synchronous write
                        pool_reqs.append(r)
                        continue
                    # mirror-coherent fan-out: each aligned leg lands on
                    # primary + pair partner; a member the health machine
                    # routes away is skipped and journaled for resync
                    legs = [(r.member, None)]
                    if mirror is not None:
                        away_p = health.routes_away(r.member)
                        away_m = health.routes_away(mirror)
                        if away_p and not away_m:
                            self._journal_skipped(sink, r.member,
                                                  r.file_off, r.length,
                                                  task.trace_id)
                            legs = [(mirror, r.member)]
                        elif away_m and not away_p:
                            self._journal_skipped(sink, mirror,
                                                  r.file_off, r.length,
                                                  task.trace_id)
                        else:
                            legs.append((mirror, r.member))
                    native_rs.append(r)
                    for m, covered in legs:
                        if covered is not None:
                            n_mirror_legs += 1
                            if _trace.active and task.trace_id:
                                _trace.instant("mirror_write",
                                               tid=task.trace_id,
                                               member=covered,
                                               offset=r.file_off,
                                               length=r.length,
                                               args={"mirror": m})
                        native_reqs.append((fds[m], r.file_off,
                                            r.length, r.dest_off))
                        native_members.append(m)
                if native_reqs:
                    try:
                        self._members_used.update(native_members)
                        addr = ctypes.addressof(
                            ctypes.c_char.from_buffer(src))
                        nat = self._native
                        nid = nat.submit(addr, native_reqs,
                                         write=True,
                                         members=native_members)
                        self._task_get(task)
                        try:
                            self._pool.submit(
                                self._await_native, task, nat, nid,
                                (sink, native_rs, src, n_mirror_legs))
                        except BaseException as e:
                            self._task_put(task, StromError(
                                _errno.ESHUTDOWN, str(e)))
                            raise
                    except StromError as e:
                        if not config.get("io_fallback"):
                            raise
                        stats.add("nr_backend_fallback")
                        pr_warn("native write submit failed (%s); batch "
                                "falls back to the python pool path", e)
                        pool_reqs.extend(native_rs)
            for r in pool_reqs:
                self._task_get(task)
                cur = stats.gauge_add("cur_dma_count", 1)
                stats.gauge_max("max_dma_count", cur)
                stats.count_clock("submit_dma", 0)
                stats.add("total_dma_length", r.length)
                try:
                    self._pool.submit(self._do_write_request, task, sink, r, src)
                except BaseException as e:
                    stats.gauge_add("cur_dma_count", -1)
                    self._task_put(task, StromError(_errno.ESHUTDOWN, str(e)))
                    raise
        except BaseException:
            self._task_put(task, StromError(_errno.ECANCELED, "submit aborted"))
            try:
                self.memcpy_wait(task.task_id, timeout=30.0)
            except StromError:
                pass
            self._put_buffer(buf_handle)
            raise
        result = MemCopyResult(dma_task_id=task.task_id, nr_chunks=n,
                               nr_ssd2dev=n, nr_ram2dev=0,
                               chunk_ids=chunk_ids)
        task.result = result
        sidx = self._slot_of(task.task_id)
        with self._slot_cv[sidx]:
            task.frozen = True
        task.buf_handle = buf_handle
        self._task_put(task)
        stats.count_clock("ioctl_memcpy_submit", time.monotonic_ns() - t0)
        return result

    def _do_write_request(self, task: DmaTask, sink: Source,
                          r: Request, src: memoryview) -> None:
        if task.errno_:
            stats.add("nr_chunk_cancelled")
            stats.gauge_add("cur_dma_count", -1)
            self._task_put(task, None)
            return
        err = self._write_request_resilient(task, sink, r, src)
        stats.gauge_add("cur_dma_count", -1)
        self._task_put(task, err)

    def _write_request_resilient(self, task: DmaTask, sink: Source,
                                 r: Request, src: memoryview
                                 ) -> Optional[StromError]:
        """One write request through the full ladder (ISSUE 11, the
        write-side peer of :meth:`_read_direct_resilient`): paired sinks
        fan out to primary + mirror partner — both must land before the
        task retires; a member the health machine routes away (or that
        fails mid-stream and latches off the direct path) degrades the
        write to mirror-only with the missed extent journaled for rejoin
        resync.  Returns the error to latch, or None."""
        err: Optional[StromError] = None
        t0 = time.monotonic_ns()
        try:
            piece = src[r.dest_off:r.dest_off + r.length]
            mirror = sink.mirror_of(r.member)
            if mirror is None:
                self._write_leg(task, sink, r, r.member, piece)
            else:
                err = self._write_mirrored(task, sink, r, mirror, piece)
        except StromError as e:
            err = e
        except BaseException as e:
            err = StromError(_errno.EIO, f"unexpected write failure: {e!r}")
        finally:
            elapsed = time.monotonic_ns() - t0
            if _trace.active and task.trace_id:
                eargs: dict = {"write": True}
                if r.buffered:
                    eargs["buffered"] = True
                if err is not None:
                    eargs["errno"] = err.errno
                _trace.span("extent", t0, t0 + elapsed, tid=task.trace_id,
                            member=r.member, offset=r.file_off,
                            length=r.length, args=eargs)
        return err

    def _write_leg(self, task: DmaTask, sink: Source, r: Request,
                   member: int, piece: memoryview) -> None:
        """One write leg with transient retry; failures debit the health
        machine with the read-side taxonomy (ENOSPC/EDQUOT/EROFS are
        PERSISTENT: first-error latch, never a retry storm) and successes
        feed latency into suspect detection + the member's adaptive
        sizer, so write-only traffic drives the ladder too."""
        health = self._member_health
        attempt = 0
        t0 = time.monotonic_ns()
        try:
            while True:
                try:
                    if r.buffered:
                        sink.write_member_buffered(member, r.file_off,
                                                   piece)
                    else:
                        sink.write_member_direct(member, r.file_off,
                                                 piece)
                    break
                except (StromError, OSError) as e:
                    se = e if isinstance(e, StromError) else \
                        StromError(e.errno or _errno.EIO, str(e))
                    # transient write errors retry under the same policy;
                    # no buffered degradation (a half-direct half-buffered
                    # write would need a sync to be durable)
                    if not se.transient or r.buffered \
                            or attempt >= self._retry.attempts \
                            or task.errno_:
                        health.record_failure(
                            member,
                            fatal=se.error_class is ErrorClass.PERSISTENT)
                        stats.member_error(member)
                        raise se
                    stats.add("nr_io_retry")
                    stats.add("nr_write_retry")
                    stats.member_error(member, retried=True)
                    if _trace.active and task.trace_id:
                        _trace.instant("retry", tid=task.trace_id,
                                       member=member,
                                       args={"attempt": attempt + 1,
                                             "errno": se.errno,
                                             "write": True})
                    self._retry.sleep(attempt, self._retry_rng)
                    attempt += 1
        finally:
            elapsed = time.monotonic_ns() - t0
            stats.member_add(member, r.length, elapsed)
        if not r.buffered:
            stats.observe_latency(elapsed)
            health.observe_latency(member, elapsed)
            # write latencies feed the member's adaptive sizer too —
            # created here under the same config gates as the read
            # planner, so write-only traffic still shapes the next
            # native plan's coalescing cap
            if config.get("chunk_adaptive"):
                climit = int(config.get("coalesce_limit"))
                if climit:
                    self._adaptive_cap(int(config.get("dma_max_size")),
                                       climit, member)
            szr = self._chunk_sizers.get(member)
            if szr is not None:
                szr.observe(elapsed)
        health.record_success(member)

    def _write_mirrored(self, task: DmaTask, sink: Source, r: Request,
                        mirror: int, piece: memoryview
                        ) -> Optional[StromError]:
        """Mirror fan-out for one request on a paired sink.  Both legs
        must land for a clean retire; a leg whose member routes away is
        skipped up front and journaled, and a leg that fails mid-stream
        *and* leaves its member routed away (quarantined/failed) degrades
        the same way — the stream stays alive on the surviving replica.
        A failure on a member still serving the direct path latches:
        swallowing it would leave readable stale bytes with no resync
        owner."""
        health = self._member_health
        away_p = health.routes_away(r.member)
        away_m = health.routes_away(mirror)
        do_p = do_m = True
        if away_p and not away_m:
            self._journal_skipped(sink, r.member, r.file_off, r.length,
                                  task.trace_id)
            do_p = False
        elif away_m and not away_p:
            self._journal_skipped(sink, mirror, r.file_off, r.length,
                                  task.trace_id)
            do_m = False
        p_err = m_err = None
        if do_p:
            try:
                self._write_leg(task, sink, r, r.member, piece)
            except StromError as e:
                p_err = e
        if do_m:
            tm = time.monotonic_ns()
            try:
                self._write_leg(task, sink, r, mirror, piece)
            except StromError as e:
                m_err = e
            else:
                stats.add("nr_mirror_write")
                if _trace.active and task.trace_id:
                    _trace.span("mirror_write", tm, time.monotonic_ns(),
                                tid=task.trace_id, member=r.member,
                                offset=r.file_off, length=r.length,
                                args={"mirror": mirror})
        if p_err is not None and m_err is None and do_m \
                and health.routes_away(r.member):
            self._journal_skipped(sink, r.member, r.file_off, r.length,
                                  task.trace_id)
            p_err = None
        if m_err is not None and p_err is None and do_p \
                and health.routes_away(mirror):
            self._journal_skipped(sink, mirror, r.file_off, r.length,
                                  task.trace_id)
            m_err = None
        return p_err or m_err

    def _verify_writes(self, sink: Source, reqs: List[Request],
                       src: memoryview, task: DmaTask) -> None:
        """write_verify (ISSUE 11): read every retired write leg back
        and compare crc32c against the submitted bytes.  Legs whose
        member routes away were degraded + journaled for resync (the
        bytes there are known-stale until replay), so they are skipped;
        everything else must match or EBADMSG (CORRUPTION) raises — a
        torn or misdirected write caught at the durability boundary
        instead of on some future read."""
        from .scan.heap import crc32c
        health = self._member_health
        scratch: Optional[mmap.mmap] = None
        try:
            for r in reqs:
                want = crc32c(src[r.dest_off:r.dest_off + r.length])
                members = [r.member]
                mirror = sink.mirror_of(r.member)
                if mirror is not None:
                    members.append(mirror)
                for m in members:
                    if health.routes_away(m):
                        continue
                    if r.buffered:
                        back = bytearray(r.length)
                        sink.read_member_buffered(m, r.file_off,
                                                  memoryview(back))
                        got = crc32c(back)
                    else:
                        if scratch is None or len(scratch) < r.length:
                            if scratch is not None:
                                scratch.close()
                            sz = -(-r.length // mmap.PAGESIZE) \
                                * mmap.PAGESIZE
                            scratch = mmap.mmap(-1, sz)
                        mv = memoryview(scratch)[:r.length]
                        try:
                            sink.read_member_direct(m, r.file_off, mv)
                            got = crc32c(mv)
                        finally:
                            # release before any raise: an exported view
                            # would make scratch.close() throw and mask
                            # the verification error
                            mv.release()
                    stats.add("bytes_verify_reread", r.length)
                    if got != want:
                        stats.add("nr_write_verify_fail")
                        if _trace.active and task.trace_id:
                            _trace.instant("csum_fail", tid=task.trace_id,
                                           member=m, offset=r.file_off,
                                           length=r.length,
                                           args={"write_verify": True})
                        raise StromError(
                            _errno.EBADMSG,
                            f"write_verify: crc32c mismatch on member {m}"
                            f" at file offset {r.file_off} ({r.length} "
                            f"bytes): wrote {want:#010x}, read back "
                            f"{got:#010x}")
        finally:
            if scratch is not None:
                scratch.close()

    def _writeback_copy(self, task: DmaTask, source: Source, base: int,
                        dest: memoryview) -> None:
        """One write-back run copied out of the page cache on a pool
        thread; a failed copy latches on the task and surfaces from
        memcpy_wait."""
        if task.errno_:
            # task already failed (first-error latch or watchdog expiry)
            stats.add("nr_chunk_cancelled")
            self._task_put(task, None)
            return
        err: Optional[StromError] = None
        try:
            with stats.stage("writeback_copy", ring="writeback",
                             tid=task.trace_id, offset=base,
                             length=len(dest)):
                source.read_buffered(base, dest)
        except StromError as e:
            err = e
        except OSError as e:
            err = StromError(e.errno or _errno.EIO, str(e))
        except BaseException as e:  # any failure must latch, never silently DONE
            err = StromError(_errno.EIO, f"{type(e).__name__}: {e}")
        finally:
            self._task_put(task, err)

    def _do_request(self, task: DmaTask, source: Source,
                    r: Request, dest: memoryview) -> None:
        if task.errno_:
            # task already failed (first-error latch or watchdog expiry):
            # cancel this chunk instead of reading into a buffer whose
            # waiter has already been woken with an error
            stats.add("nr_chunk_cancelled")
            stats.gauge_add("cur_dma_count", -1)
            self._task_put(task, None)
            return
        err: Optional[StromError] = None
        t0 = time.monotonic_ns()
        try:
            if r.buffered:
                piece = dest[r.dest_off:r.dest_off + r.length]
                source.read_member_buffered(r.member, r.file_off, piece)
            else:
                self._read_direct_resilient(task, source, r, dest)
        except StromError as e:
            err = e
        except OSError as e:
            err = StromError(e.errno or _errno.EIO, str(e))
        except BaseException as e:  # any failure must latch, never silently DONE
            err = StromError(_errno.EIO, f"{type(e).__name__}: {e}")
        finally:
            elapsed = time.monotonic_ns() - t0
            stats.member_add(r.member, r.length, elapsed)
            if _trace.active and task.trace_id:
                eargs = {}
                if r.buffered:
                    eargs["buffered"] = True
                if err is not None:
                    eargs["errno"] = err.errno
                _trace.span("extent", t0, t0 + elapsed, tid=task.trace_id,
                            member=r.member, offset=r.file_off,
                            length=r.length, args=eargs or None)
            if not r.buffered:
                stats.observe_latency(elapsed)
                if err is None:
                    # health-machine latency feed (PR 6): per-member p99
                    # drift past suspect_ratio x the stripe median marks
                    # the member SUSPECT (hedge-eligible)
                    self._member_health.observe_latency(r.member, elapsed)
                szr = self._chunk_sizers.get(r.member)
                if szr is not None:
                    szr.observe(elapsed)
            stats.gauge_add("cur_dma_count", -1)
            self._task_put(task, err)

    def _read_direct_resilient(self, task: DmaTask, source: Source,
                               r: Request, dest: memoryview) -> None:
        """One direct-read extent with the full recovery ladder (PR 1,
        extended PR 6): members the health machine routes away serve from
        their mirror partner at direct speed (degraded-mode striping),
        falling back to the buffered path; TRANSIENT errors retry under
        the RetryPolicy (backoff + jitter) then degrade mirror-first;
        PERSISTENT errors drive the member to FAILED and fail over the
        same way, so a mid-task fail-stop stays byte-identical; with
        ``hedge_policy`` armed, a plain extent still in flight past the
        hedge latch races a mirror/buffered hedge leg, first completion
        wins; optional crc32c verification re-reads on mismatch and
        latches a CORRUPTION error after ``checksum_retries`` failed
        heals.

        Coalesced (vectored) requests read all destination segments in one
        preadv; the recovery ladder treats the whole vectored extent as one
        unit, exactly as a plain extent."""
        health = self._member_health
        mirror = source.mirror_of(r.member)
        if r.dest_segs:
            views = [dest[d:d + l] for d, l in r.dest_segs]

            def _direct() -> None:
                source.read_member_direct_v(r.member, r.file_off, views)

            def _mirror_read() -> None:
                source.read_member_direct_v(mirror, r.file_off, views)

            def _buffered() -> None:
                foff = r.file_off
                for v in views:
                    source.read_member_buffered(r.member, foff, v)
                    foff += len(v)
        else:
            piece = dest[r.dest_off:r.dest_off + r.length]
            # passthrough lane (PR 19): a blockmap-resolved sub-request's
            # direct leg issues the raw NVMe READ through the task's
            # channel; every recovery rung below (mirror, buffered) leaves
            # the lane and counts the exit — the ladder itself is UNCHANGED
            pt = task.passthru if (r.passthru_off is not None and
                                   getattr(task.passthru, "pool_ok", False)) \
                else None

            if pt is not None:
                def _direct() -> None:
                    pt.read(r.member, r.file_off, r.passthru_off, piece)
                    stats.add("nr_passthru_dma")
            else:
                def _direct() -> None:
                    source.read_member_direct(r.member, r.file_off, piece)

            def _mirror_read() -> None:
                if pt is not None:
                    _passthru_left_lane(task, r)
                source.read_member_direct(mirror, r.file_off, piece)

            def _buffered() -> None:
                if pt is not None:
                    _passthru_left_lane(task, r)
                source.read_member_buffered(r.member, r.file_off, piece)

        fallback_ok = bool(config.get("io_fallback"))

        def _try_mirror() -> bool:
            """Degraded-mode striping: serve the extent from the pair
            partner at direct speed.  A mirror failure counts against the
            mirror and falls through to the next rung of the ladder."""
            if mirror is None or not health.allow_direct(mirror):
                return False
            tm = time.monotonic_ns()
            try:
                _mirror_read()
            except (StromError, OSError) as e:
                me = e if isinstance(e, StromError) else \
                    StromError(e.errno or _errno.EIO, str(e))
                health.record_failure(
                    mirror, fatal=me.error_class is ErrorClass.PERSISTENT)
                stats.member_error(mirror)
                return False
            stats.add("nr_mirror_read")
            if _trace.active and task.trace_id:
                # attributed to the member being covered FOR, so the
                # degraded read shows on the failing member's track
                _trace.span("mirror_read", tm, time.monotonic_ns(),
                            tid=task.trace_id, member=r.member,
                            offset=r.file_off, length=r.length,
                            args={"mirror": mirror})
            health.record_success(mirror)
            health.observe_latency(mirror, time.monotonic_ns() - tm)
            return True

        done = False
        if (mirror is not None or fallback_ok) \
                and not health.allow_direct(r.member):
            # routed away (QUARANTINED/FAILED, or REJOINING beyond its
            # warmup tokens): mirror at direct speed first, buffered next
            if _trace.active and task.trace_id:
                _trace.instant("route_away", tid=task.trace_id,
                               member=r.member, offset=r.file_off,
                               length=r.length)
            if _try_mirror():
                done = True
            elif fallback_ok:
                stats.add("nr_io_fallback")
                _buffered()
                done = True
        if not done and not r.dest_segs:
            hd = health.hedge_delay_s(r.member)
            if hd is not None and self._tuner.enabled:
                # effective-knob indirection (ISSUE 18): the tuned
                # per-member latch replaces the static hedge_ms floor;
                # the policy decision (None = hedging off) stays with
                # the health machine
                hd = self._tuner.hedge_delay(r.member, hd)
            if hd is not None and len(getattr(source, "members", ())) > 1:
                done = self._read_hedged(task, source, r, piece, hd, mirror)
        attempt = 0
        while not done:
            try:
                _direct()
                health.record_success(r.member)
                break
            except (StromError, OSError) as e:
                se = e if isinstance(e, StromError) else \
                    StromError(e.errno or _errno.EIO, str(e))
                if not se.transient:
                    # fail-stop: the member is gone.  Its mirror keeps the
                    # task alive at direct speed (byte identity across
                    # mid-task member loss); otherwise latch the error.
                    health.record_failure(
                        r.member,
                        fatal=se.error_class is ErrorClass.PERSISTENT)
                    stats.member_error(r.member)
                    if _try_mirror():
                        break
                    raise se
                health.record_failure(r.member)
                # stop burning attempts once the task already failed or
                # expired — the result can no longer be delivered
                if attempt < self._retry.attempts and not task.errno_:
                    stats.add("nr_io_retry")
                    stats.member_error(r.member, retried=True)
                    if _trace.active and task.trace_id:
                        _trace.instant("retry", tid=task.trace_id,
                                       member=r.member, offset=r.file_off,
                                       length=r.length,
                                       args={"attempt": attempt + 1,
                                             "errno": se.errno})
                    self._retry.sleep(attempt, self._retry_rng)
                    attempt += 1
                    continue
                stats.member_error(r.member)
                if task.errno_:
                    raise se
                if _try_mirror():
                    break
                if fallback_ok:
                    # retries exhausted: degrade this extent to the
                    # buffered path (the reference's page-cache
                    # arbitration, reused as an error path)
                    stats.add("nr_io_fallback")
                    if _trace.active and task.trace_id:
                        _trace.instant("fallback_buffered",
                                       tid=task.trace_id, member=r.member,
                                       offset=r.file_off, length=r.length)
                    _buffered()
                    break
                raise se
        if config.get("checksum_verify"):
            self._verify_request_checksums(source, r, dest)

    def _read_hedged(self, task: DmaTask, source: Source, r: Request,
                     piece: memoryview, delay_s: float,
                     mirror: Optional[int]) -> bool:
        """Hedged read of one plain extent (Python pool path): the primary
        direct read races a hedge leg armed after *delay_s* — the mirror
        partner at direct speed when one exists, else the buffered path.
        Both legs land in private scratch buffers and the first completion
        copies into the destination under the winner lock; the loser is
        discarded (safe cancellation: a torn destination is impossible and
        a late loser never overwrites the winner).

        Returns True when either leg delivered the extent, False when
        there is nothing to hedge onto (the caller runs the plain ladder);
        raises when the primary failed and the hedge could not save it."""
        health = self._member_health
        use_mirror = mirror is not None and health.allow_direct(mirror)
        fallback_ok = bool(config.get("io_fallback"))
        if not use_mirror and not fallback_ok:
            return False
        # passthrough lane (PR 19): the primary leg of a resolved
        # sub-request stays on the raw-command path; the hedge leg is by
        # construction off-lane (mirror/buffered), so its win is an exit
        pt = task.passthru if (r.passthru_off is not None and
                               getattr(task.passthru, "pool_ok", False)) \
            else None
        lock = threading.Lock()
        won = threading.Event()            # a winner has landed in dest
        hedge_settled = threading.Event()  # the hedge leg has exited
        prim_settled = threading.Event()   # the primary leg has exited
        state = {"winner": None, "prim_ok": False, "prim_err": None}

        def _finish(who: str, scratch) -> bool:
            with lock:
                if state["winner"] is None and not task.errno_:
                    state["winner"] = who
                    piece[:] = scratch
                    won.set()
                    return True
            return False

        def _hedge_leg() -> None:
            scratch = mv = None
            try:
                if won.wait(delay_s) or task.errno_:
                    return            # primary beat the latch: never issued
                with lock:
                    if state["winner"] is not None:
                        return
                stats.add("nr_hedge_issued")
                # the race reads this extent twice — one leg's bytes are
                # pure overhead whoever wins (bytes-touched gate metric)
                stats.add("bytes_hedge_dup", r.length)
                th0 = time.monotonic_ns()
                if _trace.active and task.trace_id:
                    # hedge events ride the PRIMARY member's track: the
                    # race is a fact about the slow/failing member, the
                    # serving leg is an attribute
                    _trace.instant("hedge_issued", tid=task.trace_id,
                                   member=r.member,
                                   offset=r.file_off, length=r.length,
                                   args={"leg": f"mirror:{mirror}"
                                         if use_mirror else "buffered"})
                # page-aligned scratch: the direct leg is an O_DIRECT
                # pread and a heap bytearray would EINVAL it
                scratch = mmap.mmap(-1, max(r.length, 1))
                mv = memoryview(scratch)[:r.length]
                try:
                    if use_mirror:
                        source.read_member_direct(mirror, r.file_off, mv)
                    else:
                        source.read_member_buffered(r.member, r.file_off, mv)
                except (StromError, OSError):
                    if use_mirror:
                        health.record_failure(mirror)
                    stats.add("nr_hedge_cancelled")
                    if _trace.active and task.trace_id:
                        _trace.instant("hedge_cancelled",
                                       tid=task.trace_id, member=r.member,
                                       offset=r.file_off, length=r.length,
                                       args={"reason": "leg_failed"})
                    return
                if use_mirror:
                    health.record_success(mirror)
                    stats.add("nr_mirror_read")
                if _finish("hedge", scratch):
                    stats.add("nr_hedge_won")
                    if pt is not None:
                        _passthru_left_lane(task, r)
                    if _trace.active and task.trace_id:
                        _trace.span("hedge_won", th0, time.monotonic_ns(),
                                    tid=task.trace_id, member=r.member,
                                    offset=r.file_off, length=r.length,
                                    args={"leg": f"mirror:{mirror}"
                                          if use_mirror else "buffered"})
                else:
                    stats.add("nr_hedge_cancelled")
                    if _trace.active and task.trace_id:
                        _trace.instant("hedge_cancelled",
                                       tid=task.trace_id, member=r.member,
                                       offset=r.file_off, length=r.length,
                                       args={"reason": "primary_won"})
            finally:
                if mv is not None:
                    mv.release()
                if scratch is not None:
                    scratch.close()
                hedge_settled.set()

        def _primary_leg() -> None:
            scratch = mmap.mmap(-1, max(r.length, 1))   # O_DIRECT-aligned
            mv = memoryview(scratch)[:r.length]
            attempt = 0
            try:
                while True:
                    try:
                        if pt is not None:
                            pt.read(r.member, r.file_off, r.passthru_off, mv)
                            stats.add("nr_passthru_dma")
                        else:
                            source.read_member_direct(r.member, r.file_off,
                                                      mv)
                        health.record_success(r.member)
                        break
                    except (StromError, OSError) as e:
                        se = e if isinstance(e, StromError) else \
                            StromError(e.errno or _errno.EIO, str(e))
                        if se.transient and attempt < self._retry.attempts \
                                and not task.errno_ and not won.is_set():
                            health.record_failure(r.member)
                            stats.add("nr_io_retry")
                            stats.member_error(r.member, retried=True)
                            self._retry.sleep(attempt, self._retry_rng)
                            attempt += 1
                            continue
                        # terminal primary failure: exactly one health
                        # debit for this chunk even when the hedge already
                        # won — a hedged chunk must not double-count
                        # toward quarantine
                        health.record_failure(
                            r.member,
                            fatal=se.error_class is ErrorClass.PERSISTENT)
                        stats.member_error(r.member)
                        state["prim_err"] = se
                        return
                state["prim_ok"] = True
                _finish("primary", scratch)
            finally:
                mv.release()
                scratch.close()
                prim_settled.set()

        # both legs race off-thread so the extent completes at the FIRST
        # landing — the lane worker is not pinned behind a slow primary
        # after its hedge has already delivered (the hedge would otherwise
        # only save failed reads, never slow ones)
        self._pool.submit(_hedge_leg)
        self._pool.submit(_primary_leg)
        while not won.wait(0.05):
            if prim_settled.is_set() and hedge_settled.is_set():
                break
        with lock:
            if state["winner"] is not None:
                return True
        # no winner and both legs settled: either the task already
        # latched an error (nothing left to deliver) or the primary
        # failed terminally and the hedge could not save it
        if state["prim_ok"]:
            return True
        primary_err = state["prim_err"]
        if fallback_ok and not task.errno_:
            stats.add("nr_io_fallback")
            source.read_member_buffered(r.member, r.file_off, piece)
            return True
        raise primary_err

    def _verify_request_checksums(self, source: Source, r: Request,
                                  dest: memoryview) -> None:
        """Checksum-verify one planned request against the landed bytes.
        Plain requests verify their single extent; vectored requests
        verify each destination segment as its own sub-extent (each maps
        to a contiguous file range starting at ``file_off``)."""
        if not r.dest_segs:
            self._verify_chunk_checksums(
                source, r, dest[r.dest_off:r.dest_off + r.length])
            return
        foff = r.file_off
        for d, l in r.dest_segs:
            self._verify_chunk_checksums(
                source, Request(r.member, foff, l, d), dest[d:d + l])
            foff += l

    def _verify_chunk_checksums(self, source: Source, r: Request,
                                piece: memoryview) -> None:
        """Post-landing crc32c verification for one extent: pages that
        carry a checksum (heap header word 7) are recomputed; mismatches
        are re-read up to ``checksum_retries`` times, then latch EBADMSG
        (CORRUPTION).  File offsets must be page-aligned for pages to be
        addressable — misaligned extents are skipped (they are buffered
        legs anyway)."""
        from .scan.heap import PAGE_SIZE, verify_page_checksums
        if r.file_off % PAGE_SIZE:
            return
        bad = verify_page_checksums(piece)
        rereads = int(config.get("checksum_retries"))
        while bad:
            stats.add("nr_csum_fail", len(bad))
            if _trace.active:
                _trace.instant("csum_fail", member=r.member,
                               offset=r.file_off, length=r.length,
                               args={"bad_pages": len(bad)})
            if rereads <= 0:
                first = r.file_off + bad[0] * PAGE_SIZE
                raise StromError(
                    _errno.EBADMSG,
                    f"page checksum mismatch at file offset {first} "
                    f"({len(bad)} bad page(s), re-reads exhausted)")
            rereads -= 1
            stats.add("nr_csum_reread", len(bad))
            stats.add("bytes_verify_reread", len(bad) * PAGE_SIZE)
            for p in bad:
                off = p * PAGE_SIZE
                source.read_member_direct(
                    r.member, r.file_off + off,
                    piece[off:off + PAGE_SIZE])
            bad = verify_page_checksums(piece)

    def _await_native(self, task: DmaTask, eng, native_id: int,
                      write_ctx: Optional[tuple] = None) -> None:
        # *eng* is the engine that accepted the batch — NOT self._native,
        # which a lane scale-out may have swapped since submission
        err: Optional[StromError] = None
        while True:
            try:
                eng.wait(native_id, 500)
                break
            except StromError as e:
                if e.errno == _errno.ETIMEDOUT:
                    if self._abandon_native:
                        # close() gave up waiting; latch and let the pool
                        # thread exit so close cannot hang forever on a
                        # stuck fd (the reference's release path is bounded)
                        err = StromError(_errno.ETIMEDOUT,
                                        "native I/O abandoned at session close")
                        break
                    if task.expired:
                        # watchdog latched ETIMEDOUT already (waiters are
                        # awake); stop pinning a pool thread on the stuck
                        # batch — err stays None so the latch is untouched
                        break
                    continue
                err = e
                break
            except BaseException as e:  # pragma: no cover
                err = StromError(_errno.EIO, f"{type(e).__name__}: {e}")
                break
        if _trace.active:
            # the reaper just saw this batch complete: pull the engine's
            # per-lane event ring so the MEASURED device windows land in
            # the recorder close to their completion
            self._drain_native_trace(eng)
        if write_ctx is not None:
            sink, w_reqs, w_src, n_mirror = write_ctx
            if err is None and not task.expired:
                if n_mirror:
                    stats.add("nr_mirror_write", n_mirror)
            elif err is not None and not self._abandon_native \
                    and not task.expired and not task.errno_:
                # the native lane rejected or failed the write batch but
                # the session is still live: redrive each request through
                # the resilient pool ladder (per-leg retry, mirror
                # degradation, journaling).  One batch ref covers the
                # whole redrive; only the first residual error latches.
                stats.add("nr_backend_fallback")
                pr_warn("native write batch failed (%s); redriving %d "
                        "request(s) on the pool ladder",
                        err, len(w_reqs))
                err = None
                for r in w_reqs:
                    if task.errno_:
                        break
                    stats.add("nr_write_retry")
                    e2 = self._write_request_resilient(task, sink, r, w_src)
                    if e2 is not None and err is None:
                        err = e2
        self._task_put(task, err)

    def _drain_native_trace(self, eng=None) -> int:
        """Drain the native engine's per-lane trace ring into the flight
        recorder (device submit->complete windows, monotonic ns — same
        clock as the Python spans)."""
        eng = eng if eng is not None else self._native
        if eng is None:
            return 0
        try:
            evs = eng.trace_drain()
        except Exception:   # noqa: BLE001 — observability, not control
            return 0
        for ev in evs:
            _trace.native_event(ev["submit_ns"], ev["complete_ns"],
                                member=ev["member"], lane=ev["lane"],
                                offset=ev["file_off"], length=ev["len"],
                                result=ev["result"])
        return len(evs)

    def _adaptive_cap(self, floor: int, limit: int, member: int = 0) -> int:
        """Current effective coalescing cap from *member*'s adaptive sizer
        (created lazily; recreated when the config bounds change).
        Delegates to the controller (ISSUE 18) — the single writer of
        the effective cap; with ``autotune=off`` the tuner passes the
        static bounds through and this is the PR 4/5 behavior verbatim."""
        return self._tuner.chunk_cap(floor, limit, member)

    def _retire_member_pool(self, member: int) -> None:
        """Knob application (ISSUE 18): drop a member's executor lane so
        the next submit recreates it at the tuned width.  Queued work
        keeps running on the old pool's threads; shutdown(wait=False)
        just stops it accepting new work."""
        with self._lane_lock:
            pool = self._member_pools.pop(member, None)
        if pool is not None:
            pool.shutdown(wait=False)

    def _autotune_scale_lanes(self, want: int) -> None:
        """Engine-rebuild boundary (ISSUE 18): when the tuned window has
        outgrown the native lane count, rebuild the engine with more
        queue pairs (capped at 16, like _ensure_member_lanes).  No-op on
        the Python path or when already wide enough."""
        want = max(1, min(int(want), 16))
        with self._lane_lock:
            if self._native is None or self._native.nlanes() >= want:
                return
            self._scale_out_lanes(want, len(self._members_used) or 1)

    # -- lane scale-out (PR 5) ---------------------------------------------
    def _ensure_member_lanes(self, source: Source) -> None:
        """One-shot at the first striped submit: rebuild the native engine
        with one queue pair per stripe member (member i -> lane i % nlanes)
        so a slow member queues behind itself, never behind siblings — the
        per-NVMe-device blk-mq hardware-queue analog
        (kmod/nvme_strom.c:1201-1223).  An explicit lane count (env
        NSTPU_RINGS or config engine_rings > 0) keeps the operator's
        choice; after sizing, lanes are NUMA-pinned per numa_policy."""
        if self._native is None or self._lanes_sized:
            return
        members = getattr(source, "members", None)
        nmem = len(members) if members else 0
        if nmem <= 1:
            return
        with self._lane_lock:
            if self._lanes_sized or self._native is None:
                return
            self._lanes_sized = True
            try:
                explicit = int(os.environ.get("NSTPU_RINGS", "")) > 0
            except ValueError:
                explicit = int(config.get("engine_rings")) > 0
            want = min(nmem, 16)
            if not explicit and self._native.nlanes() < want:
                self._scale_out_lanes(want, nmem)
            self._pin_lanes(members)

    def _scale_out_lanes(self, nlanes: int, nmem: int) -> None:
        """Swap in a fresh native engine with *nlanes* queue pairs.  Fixed
        buffers are re-registered on the new engine under the fixed lock
        (so concurrent map_buffer registrations can't be lost), stats are
        folded first, and the old engine is retired to _old_engines —
        in-flight batches hold a direct reference and drain there."""
        from . import _native as _nat
        depth = int(config.get("member_queue_depth")) \
            or int(config.get("queue_depth"))
        backend = self.backend_name
        try:
            eng = _nat.NativeEngine(
                backend if backend in ("io_uring", "threadpool") else "auto",
                depth, rings=nlanes)
        except StromError as e:
            pr_warn("lane scale-out to %d lanes failed (%s); keeping the "
                    "single-lane engine", nlanes, e)
            return
        try:
            self._fold_native_stats()
        except StromError:
            pass
        with self._fixed_lock:
            for key, (_slot, backing, cb) in list(self._fixed_regs.items()):
                try:
                    nslot = eng.buf_register(backing.addr, backing.length)
                except Exception:
                    nslot = None
                self._fixed_regs[key] = (-1 if nslot is None else nslot,
                                         backing, cb)
            old, self._native = self._native, eng
        self._old_engines.append(old)
        if _trace.active:
            eng.trace_enable(True)
        self.backend_name = eng.backend_name
        pr_info("engine scaled out: %d lane(s) for %d stripe members "
                "(backend=%s depth=%d)", eng.nlanes(), nmem,
                eng.backend_name, depth)

    def _pin_lanes(self, members) -> None:
        """NUMA-pin each lane's service threads (reaper + workers) to its
        member's local node per ``numa_policy`` — the reference allocates
        DMA buffers device-locally (pgsql/nvme_strom.c:1454-1526); pinning
        the completion path keeps CQ reaping and the landing memcpy on
        local memory.  Unknown topology (no sysfs, node -1) leaves lanes
        floating rather than guessing."""
        policy = str(config.get("numa_policy"))
        if policy == "off" or self._native is None:
            return
        try:
            nlanes = self._native.nlanes()
        except Exception:
            return
        fixed_node = -1
        if policy.startswith("node:"):
            fixed_node = int(policy.split(":", 1)[1])
        pinned = 0
        for lane in range(nlanes):
            node = fixed_node
            if node < 0:
                # auto: pin to the backing-device node of the lane's first
                # member under the member % nlanes mapping (identity when
                # one lane per member)
                from .stripe import lane_members
                served = lane_members(lane, len(members), nlanes)
                if not served:
                    continue
                path = getattr(members[served[0]], "path", None)
                if not path:
                    continue
                try:
                    node = _numa.device_numa_node(path)
                except Exception:
                    node = -1
            if node < 0:
                continue
            try:
                cpus = _numa.node_cpus(node)
            except Exception:
                cpus = []
            if cpus and self._native.lane_pin(lane, cpus):
                pinned += 1
        if pinned:
            pr_info("NUMA: pinned %d/%d lane(s) (policy=%s)",
                    pinned, nlanes, policy)

    def _member_pool(self, member: int) -> ThreadPoolExecutor:
        """Per-member executor lane for the Python path: a quarantined or
        slow member's requests queue on their own workers instead of
        occupying the shared pool ahead of healthy siblings (the Python
        mirror of the native per-member lanes)."""
        pool = self._member_pools.get(member)
        if pool is None:
            with self._lane_lock:
                pool = self._member_pools.get(member)
                if pool is None:
                    width = int(config.get("member_queue_depth")) \
                        or int(config.get("queue_depth"))
                    width = max(1, min(width, 8))
                    if self._tuner.enabled:
                        # tuned submit window doubles as the member's
                        # lane width — the real concurrency bound here
                        width = self._tuner.pool_width(member, width)
                    pool = ThreadPoolExecutor(
                        max_workers=width,
                        thread_name_prefix=f"strom-io-m{member}")
                    self._member_pools[member] = pool
        return pool

    def _submit_pool_requests(self, task: DmaTask, source: Source,
                              reqs: Sequence[Request],
                              dest: memoryview) -> None:
        """Queue planned requests on the Python thread pool (the
        instrumented fallback executor; also the only path for sources
        that override the direct-read leg, i.e. test fakes).  Striped
        sources route each request to its member's own executor lane."""
        multi = len(getattr(source, "members", ())) > 1
        for r in reqs:
            self._task_get(task)
            cur = stats.gauge_add("cur_dma_count", 1)
            stats.gauge_max("max_dma_count", cur)
            stats.count_clock("submit_dma", 0)
            stats.add("total_dma_length", r.length)
            pool = self._member_pool(r.member) if multi else self._pool
            try:
                pool.submit(self._do_request, task, source, r, dest)
            except BaseException as e:
                stats.gauge_add("cur_dma_count", -1)
                self._task_put(task, StromError(_errno.ESHUTDOWN, str(e)))
                raise

    # -- stats + lifecycle -------------------------------------------------
    def _fold_native_stats(self, eng=None) -> dict:
        """Fold a native engine's counter deltas into the global
        registry (returns the raw delta dict).  Called from stat_info and
        from close() — a session must not take its I/O accounting to the
        grave just because nobody snapshotted before it closed.  *eng*
        defaults to the live engine; lane scale-out passes retired ones."""
        eng = eng if eng is not None else self._native
        d = eng.stats_delta()
        # nr/clk_ssd2dev + wait are counted per *Python* task already;
        # resubmit/sq_full ride the reference's spare debug counters
        stats.merge_native({
            "nr_submit_dma": d.get("nr_submit_dma", 0),
            "clk_submit_dma": d.get("clk_submit_dma", 0),
            "total_dma_length": d.get("total_dma_length", 0),
            "nr_enter_dma": d.get("nr_enter_dma", 0),
            "nr_debug1": d.get("nr_resubmit", 0),
            "nr_debug2": d.get("nr_sq_full", 0),
            "nr_debug4": d.get("nr_fixed_dma", 0),
            "occ_integral_ns": d.get("occ_integral_ns", 0),
            "occ_busy_ns": d.get("occ_busy_ns", 0),
        })
        # per-member deltas fold into the registry the same way
        used = sorted(self._members_used)
        for m, (nreq, nbytes, ns) in eng.member_stats_delta(used).items():
            stats.member_add(m, nbytes, ns, n=nreq)
        # service-latency histograms: fold the native deltas and feed the
        # mean service time to the adaptive sizers (native requests never
        # pass through _do_request, so this is their only observation
        # path).  Per-member histograms feed each member's own sizer; a
        # member with no histogram of its own falls back to the global mean.
        hd = eng.lat_hist_delta()
        if hd and any(hd):
            stats.merge_native_hist(hd)
            fed = False
            for m, h in eng.member_lat_hist_delta(used).items():
                stats.merge_member_hist(m, h)
                # suspect detection covers the native path too: the lane
                # reaper's per-member latency view folds into the health
                # machine's own histograms (PR 6)
                self._member_health.observe_hist(m, h)
                total = sum(h)
                if not total:
                    continue
                avg = sum(((1 << b) + ((1 << b) >> 1)) * c
                          for b, c in enumerate(h)) // total
                szr = self._chunk_sizers.get(m)
                if szr is not None:
                    szr.observe(avg)
                    fed = True
            if not fed and self._chunk_sizers:
                total = sum(hd)
                avg = sum(((1 << b) + ((1 << b) >> 1)) * c
                          for b, c in enumerate(hd)) // total
                for szr in self._chunk_sizers.values():
                    szr.observe(avg)
        # per-member queue-occupancy integrals (lane depth visibility)
        for m, (dint, dbusy) in eng.member_occ_delta(used).items():
            stats.member_occ_add(m, dint, dbusy)
        return d

    def stat_info(self, *, debug: bool = False):
        snap = None
        if self._native is not None:
            d = self._fold_native_stats()
            snap = stats.snapshot(debug=debug)
            # gauges combine at snapshot time (never merged into the registry)
            snap.counters["cur_dma_count"] += d.get("cur_dma_count", 0)
            snap.counters["max_dma_count"] = max(snap.counters["max_dma_count"],
                                                 d.get("max_dma_count", 0))
        return snap if snap is not None else stats.snapshot(debug=debug)

    def close(self, timeout: float = 30.0) -> List[int]:
        """Close the session: wait out running tasks, reap retained failures.

        Returns task ids that were force-reaped with errors (the reference
        logs these on fd close, kmod/nvme_strom.c:2138-2166)."""
        with self._id_lock:
            # atomic test-and-set: two racing closers must not both run
            # the teardown (double engine destroy, double pool shutdown)
            if self._closed:
                return []
            self._closed = True
        deadline = time.monotonic() + timeout
        reaped: List[int] = []
        for s, cv in enumerate(self._slot_cv):
            with cv:
                while any(t.state == DmaTaskState.RUNNING
                          for t in self._slots[s].values()):
                    remain = deadline - time.monotonic()
                    if remain <= 0 or not cv.wait(remain):
                        break
                for tid, t in list(self._slots[s].items()):
                    if t.state == DmaTaskState.FAILED:
                        reaped.append(tid)
                    del self._slots[s][tid]
        self._abandon_native = True  # bound pool shutdown on stuck native I/O
        self._watchdog_stop.set()
        self._watchdog.join(timeout=2.0)
        self._canary_stop.set()
        self._canary.join(timeout=2.0)
        self._scrubber.stop()
        self._tuner.stop()
        self._pool.shutdown(wait=True)
        if self._canary_buf is not None:
            try:
                self._canary_buf.close()
            except BufferError:
                pass  # a late canary still holds a view; dropped with it
        # swap the pool map out under the swap lock (scale-out mutates it
        # there), but shut the pools down outside it: a draining worker
        # may need the lane lock to finish
        with self._lane_lock:
            pools, self._member_pools = self._member_pools, {}
        for p in pools.values():
            p.shutdown(wait=True)
        # detach close hooks from long-lived (pool) buffers so a closed
        # session is not pinned in their callback lists; the engine close
        # below frees every kernel-side fixed slot wholesale
        with self._fixed_lock:
            regs, self._fixed_regs = list(self._fixed_regs.values()), {}
        for _slot, backing, cb in regs:
            try:
                backing.remove_close_cb(cb)
            except Exception:
                pass
        if self._native is not None:
            self._native.reap(timeout_ms=int(timeout * 1000))
            if _trace.active:
                self._drain_native_trace()
            try:
                self._fold_native_stats()
            except StromError:
                pass
            self._native.close()
        # engines retired by lane scale-out: every batch they accepted has
        # drained (pool shutdown above joins the awaiters), so reap any
        # residue, fold their remaining counters, and free them
        with self._lane_lock:
            olds, self._old_engines = self._old_engines, []
        for old in olds:
            try:
                old.reap(timeout_ms=2000)
                if _trace.active:
                    self._drain_native_trace(old)
                self._fold_native_stats(old)
                old.close()
            except Exception:
                pass
        return reaped

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
