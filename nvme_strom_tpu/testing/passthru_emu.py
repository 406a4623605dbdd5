"""Deterministic in-process NVMe passthrough emulator.

CI hosts have no ``/dev/ngXnY``, so the passthrough data path — blockmap
resolution, per-extent eligibility splits, SLBA/NLB command math, and the
whole fault ladder (retries, health debits, hedged legs, mirror fallback,
per-member histograms feeding the autotuner) — is exercised against this
emulator instead: a flat "namespace" image file served through the SAME
72-byte ``nvme_uring_cmd`` wire format the native backend builds
(csrc/strom_engine.cc, the userspace mirror of
``kmod/nvme_strom.c:1518-1589``).

The emulator is also its own oracle: :meth:`PassthruEmulator.provision`
copies a test file's bytes to gapped, deliberately-fragmented physical
ranges on the image and registers the matching synthetic extent map with
:mod:`nvme_strom_tpu.blockmap`.  Every command is validated against that
table — an SLBA/NLB pair that does not reverse-map to exactly the file
bytes the planner asked for is a hard error, never a wrong-bytes read.

Fault injection rides the attached source's :class:`FaultPlan` keyed by
*file* offset (reverse-mapped from the device offset), so a fault tier
fires identically whether the request went passthrough or O_DIRECT —
the property the passthrough fail-stop tests depend on.
"""

from __future__ import annotations

import os
import struct
import threading
from typing import Dict, List, Optional, Tuple

from .. import blockmap
from ..api import StromError

__all__ = ["PassthruEmulator", "NVME_CMD_READ"]

NVME_CMD_READ = 0x02

# struct nvme_uring_cmd — must stay layout-identical to the C mirror
# (nstpu_nvme_uring_cmd in csrc/strom_engine.cc)
_CMD = struct.Struct("=BBHIIIQQIIIIIIIIII")
assert _CMD.size == 72, _CMD.size


def pack_uring_cmd(*, nsid: int, slba: int, nlb0: int, data_len: int,
                   opcode: int = NVME_CMD_READ) -> bytes:
    """Build the 72-byte ``nvme_uring_cmd`` for a READ (nlb0 is 0-based)."""
    return _CMD.pack(opcode, 0, 0, nsid, 0, 0, 0, 0, 0, data_len,
                     slba & 0xFFFFFFFF, (slba >> 32) & 0xFFFFFFFF,
                     nlb0, 0, 0, 0, 0, 0)


class PassthruEmulator:
    """One emulated NVMe namespace backed by a flat image file."""

    def __init__(self, image_path: str, *, lba_shift: int = 9,
                 nsid: int = 1):
        if not 9 <= lba_shift <= 16:
            raise ValueError(f"lba_shift {lba_shift} outside NVMe range")
        self.image_path = image_path
        self.lba_shift = lba_shift
        self.lba_size = 1 << lba_shift
        self.nsid = nsid
        self._fd = os.open(image_path, os.O_RDWR | os.O_CREAT, 0o600)
        self._lock = threading.Lock()
        # provisioned ranges: dev_off -> (length, path, logical file off)
        self._table: List[Tuple[int, int, str, int]] = []
        self._paths: Dict[str, List[blockmap.Extent]] = {}
        self._alloc = self.lba_size  # LBA 0 left unprovisioned on purpose
        self.commands_served = 0
        self.bytes_served = 0

    # ---- provisioning ----------------------------------------------------

    def provision(self, path: str, *, frag: int = 1, gap: Optional[int] = None,
                  ineligible: Tuple[Tuple[int, int, int], ...] = ()) -> List[blockmap.Extent]:
        """Copy ``path``'s bytes onto the image at ``frag`` gapped physical
        ranges and register the synthetic extent map as the FIEMAP oracle.

        ``ineligible`` marks file ranges ``(logical_off, length, flags)``
        as their own extents carrying the given FIEMAP flags (e.g.
        UNWRITTEN/INLINE) — the planner must route those through O_DIRECT,
        and the emulator refuses commands touching them.
        """
        size = os.path.getsize(path)
        lba = self.lba_size
        gap = lba if gap is None else gap
        frag = max(1, min(frag, max(1, size // lba)))
        # logical cut points, LBA-aligned, then further cut at ineligible
        # range boundaries so flags apply to whole extents
        cuts = {0, size}
        step = (size // frag) & ~(lba - 1) or lba
        for c in range(step, size, step):
            cuts.add(c)
        for (off, length, _flags) in ineligible:
            cuts.add(max(0, min(off, size)))
            cuts.add(max(0, min(off + length, size)))
        points = sorted(cuts)

        def flags_for(lo: int) -> int:
            for (off, length, flags) in ineligible:
                if off <= lo < off + length:
                    return flags
            return 0

        exts: List[blockmap.Extent] = []
        with self._lock, open(path, "rb") as f:
            for lo, hi in zip(points, points[1:]):
                length = hi - lo
                if length <= 0:
                    continue
                dev_off = self._alloc
                # physical ranges stay LBA-aligned even when an ineligible
                # cut is not: eligibility, not alignment, excludes them
                self._alloc += (length + lba - 1) & ~(lba - 1)
                self._alloc += gap
                f.seek(lo)
                data = f.read(length)
                os.pwrite(self._fd, data, dev_off)
                flags = flags_for(lo)
                exts.append(blockmap.Extent(lo, dev_off, length, flags))
                if not flags:  # only eligible ranges are servable
                    self._table.append((dev_off, length, path, lo))
            self._table.sort()
            self._paths[path] = exts
        blockmap.register_synthetic(path, exts)
        return exts

    def rewrite(self, path: str, file_off: int, data: bytes) -> None:
        """Mirror an out-of-band write into the image so the oracle and
        the device stay consistent (used by write-back tests AFTER the
        blockmap invalidation they exercise)."""
        with self._lock:
            for dev_off, length, p, lo in self._table:
                if p != path or not (lo <= file_off < lo + length):
                    continue
                span = min(len(data), lo + length - file_off)
                os.pwrite(self._fd, data[:span], dev_off + (file_off - lo))
                data = data[span:]
                file_off += span
                if not data:
                    return
        if data:
            raise StromError(5, f"rewrite outside provisioned ranges "
                                f"({path}@{file_off})")

    # ---- command service -------------------------------------------------

    def _lookup(self, dev_off: int, length: int) -> Tuple[str, int]:
        """Reverse-map a device range to (path, file_off); ERROR unless it
        sits wholly inside ONE eligible provisioned range — the SLBA/NLB
        oracle check."""
        for toff, tlen, path, lo in self._table:
            if toff <= dev_off and dev_off + length <= toff + tlen:
                return path, lo + (dev_off - toff)
        raise StromError(5, f"passthru cmd outside provisioned extents "
                            f"(dev_off={dev_off:#x} len={length})")

    def execute(self, cmd: bytes, dest: memoryview) -> Tuple[str, int]:
        """Serve one URING_CMD-shaped command into ``dest``.

        Validates the full command the way the device+kernel would —
        opcode, NSID, SLBA/NLB against data_len, containment in a
        provisioned eligible extent — then serves the bytes from the
        image.  Returns the reverse-mapped (path, file_off) so callers
        can key fault plans by file offset."""
        if len(cmd) != _CMD.size:
            raise StromError(22, f"bad nvme_uring_cmd size {len(cmd)}")
        f = _CMD.unpack(cmd)
        opcode, nsid, data_len = f[0], f[3], f[9]
        cdw10, cdw11, cdw12 = f[10], f[11], f[12]
        if opcode != NVME_CMD_READ:
            raise StromError(22, f"unsupported NVMe opcode {opcode:#x}")
        if nsid != self.nsid:
            raise StromError(22, f"wrong NSID {nsid} (ns is {self.nsid})")
        slba = cdw10 | (cdw11 << 32)
        nblocks = (cdw12 & 0xFFFF) + 1
        length = nblocks << self.lba_shift
        if data_len != length or len(dest) != length:
            raise StromError(22, f"NLB/data_len mismatch: {nblocks} blocks "
                                 f"vs data_len={data_len} dest={len(dest)}")
        dev_off = slba << self.lba_shift
        with self._lock:
            path, file_off = self._lookup(dev_off, length)
            got = os.pread(self._fd, length, dev_off)
            self.commands_served += 1
            self.bytes_served += length
        if len(got) < length:  # provisioned past image EOF: zero-fill
            got = got + b"\0" * (length - len(got))
        dest[:] = got
        return path, file_off

    # ---- source attachment ----------------------------------------------

    def attach(self, source) -> "_EmuChannel":
        """Attach this emulator to a (fake) source: memcpy_ssd2ram will
        split eligible extents onto the passthrough lane served here."""
        chan = _EmuChannel(self, source)
        source.passthru_channel = chan
        return chan

    def close(self) -> None:
        with self._lock:
            for path in list(self._paths):
                blockmap.unregister_synthetic(path)
            self._paths.clear()
            self._table.clear()
            if self._fd >= 0:
                os.close(self._fd)
                self._fd = -1

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


class _EmuChannel:
    """The session-facing passthrough channel the emulator provides.

    ``pool_ok=True`` routes passthrough requests down the Python pool
    lanes (where the fault ladder lives), mirroring how fake sources ride
    the pool path; a native channel on a real host sets pool_ok=False and
    the engine submits flagged native requests instead."""

    pool_ok = True
    native = False

    def __init__(self, emu: PassthruEmulator, source):
        self.emu = emu
        self.source = source
        self.lba_size = emu.lba_size
        self.lba_shift = emu.lba_shift

    def member_path(self, member: int) -> Optional[str]:
        members = getattr(self.source, "members", None)
        if members:
            if 0 <= member < len(members):
                return str(members[member].path)
            return None
        m = getattr(self.source, "_m", None)
        return str(m.path) if m is not None and member == 0 else None

    def read(self, member: int, file_off: int, dev_off: int,
             dest: memoryview) -> None:
        """One passthrough read, byte-for-byte through the wire format,
        with the source's FaultPlan applied exactly like the O_DIRECT
        lane (same file-offset keying, same corruption hook)."""
        plan = getattr(self.source, "fault_plan", None)
        if plan is not None:
            plan.check(file_off, len(dest), member=member)
        slba = dev_off >> self.emu.lba_shift
        nlb0 = (len(dest) >> self.emu.lba_shift) - 1
        cmd = pack_uring_cmd(nsid=self.emu.nsid, slba=slba, nlb0=nlb0,
                             data_len=len(dest))
        path, mapped_off = self.emu.execute(cmd, dest)
        want = self.member_path(member)
        if want is not None and (path != want or mapped_off != file_off):
            raise StromError(5, f"SLBA math drift: cmd mapped to "
                                f"{path}@{mapped_off}, planner meant "
                                f"{want}@{file_off}")
        if plan is not None:
            plan.apply_corruption(file_off, dest)
