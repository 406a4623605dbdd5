"""Fake backends for hardware-free CI.

The reference has **no** tests or mocks (SURVEY.md SS4) — its oracles are
baked into the runtime benchmarks.  This module supplies what it lacks: a
loopback "NVMe" source with injected latency and fault plans so the planner,
merging, error-retention and corruption logic are testable on any machine,
plus helpers to build deterministic test files.
"""

from __future__ import annotations

import errno as _errno
import hashlib
import os
import time
from dataclasses import dataclass, field
from typing import Optional, Set

import numpy as np

from ..api import ErrorClass, StromError
from ..engine import PlainSource, StripedSource


def _seed_hash(seed: int) -> int:
    return int.from_bytes(hashlib.blake2b(str(seed).encode(),
                                          digest_size=8).digest(), "little")


def _pattern_words(first_word: int, n_words: int, h: int) -> np.ndarray:
    """Little-endian words ``(8*w) ^ h`` for w in [first, first+n)."""
    w = np.arange(first_word, first_word + n_words, dtype=np.uint64)
    return ((w << np.uint64(3)) ^ np.uint64(h)).astype("<u8")


def make_test_file(path: str, size: int, *, seed: int = 0) -> None:
    """Deterministic content: every 8-byte word encodes its own offset xor a
    seed hash, so corruption checks can point at the exact wrong offset."""
    h = _seed_hash(seed)
    chunk = 64 << 20
    with open(path, "wb") as f:
        for off in range(0, size, chunk):
            n = min(chunk, size - off)
            words = _pattern_words(off // 8, (n + 7) // 8, h)
            f.write(memoryview(words).cast("B")[:n])


def expected_bytes(offset: int, length: int, *, seed: int = 0) -> bytes:
    start_word = offset // 8
    end_word = (offset + length + 7) // 8
    words = _pattern_words(start_word, end_word - start_word,
                           _seed_hash(seed))
    head = offset - start_word * 8
    return words.tobytes()[head:head + length]


@dataclass
class FaultPlan:
    """Deterministic fault injection for the read path.

    Fault tiers map onto the engine's error taxonomy (PR 1):

    * ``fail_offsets`` — PERSISTENT bad regions: the direct read *and* the
      buffered fallback both fail, so retries exhaust and the task latches
      EIO (the "dead blocks" plan).
    * ``fail_every_nth`` / ``fail_rate`` — TRANSIENT periodic/randomized
      EIO on the direct path only; a retry or the buffered fallback
      succeeds (``fail_rate`` draws per-request from ``random.Random
      (seed)`` so stress runs are reproducible).
    * ``latency_s`` / ``slow_member``+``slow_s`` — slow-device and
      slow-member plans for deadline/watchdog and quarantine tests.
    * ``corrupt_offsets`` — persistent bit-flips (re-reads stay corrupt:
      exercises the latched CORRUPTION error), ``corrupt_once_offsets`` —
      torn reads that heal on re-read (each offset flips exactly once).
    * ``failstop_member`` + ``failstop_after`` [+ ``rejoin_after``] —
      deterministic fail-stop schedule (PR 6): once the global direct-read
      count reaches ``failstop_after``, every read of that member (direct
      *and* buffered — the device is gone) raises a PERSISTENT error,
      driving the health machine to FAILED; from ``rejoin_after`` reads
      onward the member answers again, so canary probes observe recovery
      and walk it through REJOINING back to HEALTHY.

    Write-side tiers (ISSUE 11) mirror the read tiers on an independent
    op counter (``_wcount``), so a mixed read/write scenario schedules
    each direction deterministically:

    * ``write_fail_every_nth`` / ``write_fail_rate`` — periodic /
      randomized write faults raising ``write_errno`` (default EIO, i.e.
      TRANSIENT; set ENOSPC for a PERSISTENT first-error-latch storm).
    * ``write_failstop_member`` + ``write_failstop_after``
      [+ ``write_rejoin_after``] — fail-stop for the write path only:
      reads (canary probes included) keep answering, writes hard-fail
      until the member 'comes back', which is how a mirror-degraded
      stream plus journal replay is exercised end to end.
    * ``torn_write_offsets`` — each listed absolute member offset has one
      byte flipped ON DISK after the covering write lands (fsynced, so
      O_DIRECT read-back sees it): a torn/misdirected write for the
      ``write_verify`` read-back oracle.  One-shot per offset.

    Resident-corruption tier (ISSUE 16) — seeded bit-rot for the
    integrity domain's scrub/heal oracles:

    * ``corrupt_member_offsets`` — ``{member: {absolute offsets}}``; one
      byte at each listed offset of that MEMBER's backing file is flipped
      on disk after a covering write lands (one-shot, `_tear_landed`
      mechanics).  Unlike ``torn_write_offsets`` it is member-scoped, so
      a mirrored KV spill rots exactly one leg and the scrubber must heal
      the primary from the surviving mirror while debiting the rotten
      member's health machine.  Host-slab and HBM-extent rot have no
      on-disk representation — seed those with
      :func:`flip_resident_host` / :func:`flip_resident_hbm`.
    """

    fail_offsets: Set[int] = field(default_factory=set)   # file_off -> EIO
    fail_every_nth: int = 0                               # every Nth direct read fails
    fail_rate: float = 0.0                                # P(transient EIO) per direct read
    seed: int = 0                                         # rng seed for fail_rate
    latency_s: float = 0.0                                # per-request injected delay
    slow_member: Optional[int] = None                     # member with extra latency
    slow_s: float = 0.0                                   # the extra latency
    corrupt_offsets: Set[int] = field(default_factory=set)  # flip a byte at offset
    corrupt_once_offsets: Set[int] = field(default_factory=set)  # flip once
    failstop_member: Optional[int] = None   # member that hard-fails...
    failstop_after: int = 0                 # ...once _count reaches this
    rejoin_after: Optional[int] = None      # ...and heals at this count
    write_fail_every_nth: int = 0           # every Nth write raises write_errno
    write_fail_rate: float = 0.0            # P(write fault) per write
    write_errno: int = _errno.EIO           # errno those write faults carry
    write_failstop_member: Optional[int] = None  # write-path fail-stop...
    write_failstop_after: int = 0                # ...from this write count
    write_rejoin_after: Optional[int] = None     # ...healing at this count
    torn_write_offsets: Set[int] = field(default_factory=set)  # flip after landing
    corrupt_member_offsets: dict = field(default_factory=dict)  # member -> {offsets}
    slow_write_member: Optional[int] = None  # member whose writes stall
    slow_write_s: float = 0.0                # the extra write latency
    _count: int = 0
    _wcount: int = 0
    _rng: object = field(default=None, repr=False)
    _wrng: object = field(default=None, repr=False)

    def failstopped(self, member: Optional[int]) -> bool:
        """Is *member* inside its fail-stop window right now?"""
        return (self.failstop_member is not None
                and member == self.failstop_member
                and self._count >= self.failstop_after
                and (self.rejoin_after is None
                     or self._count < self.rejoin_after))

    def write_failstopped(self, member: Optional[int]) -> bool:
        """Is *member* inside its WRITE fail-stop window right now?"""
        return (self.write_failstop_member is not None
                and member == self.write_failstop_member
                and self._wcount >= self.write_failstop_after
                and (self.write_rejoin_after is None
                     or self._wcount < self.write_rejoin_after))

    def check_write(self, file_off: int, length: int,
                    member: Optional[int] = None) -> None:
        """Write-path injection gate: consulted by both write legs (the
        engine's pool ladder AND the resync replay write through here)."""
        self._wcount += 1
        if self.latency_s:
            time.sleep(self.latency_s)
        if self.slow_write_s and member is not None \
                and member == self.slow_write_member:
            time.sleep(self.slow_write_s)
        if self.write_failstopped(member):
            raise StromError(_errno.EIO,
                             f"injected write fail-stop of member {member}",
                             error_class=ErrorClass.PERSISTENT)
        if self.write_fail_every_nth \
                and self._wcount % self.write_fail_every_nth == 0:
            raise StromError(self.write_errno,
                             f"injected periodic write fault #{self._wcount}")
        if self.write_fail_rate > 0.0:
            if self._wrng is None:
                import random
                self._wrng = random.Random(self.seed ^ 0x5A5A5A5A)
            if self._wrng.random() < self.write_fail_rate:
                raise StromError(self.write_errno,
                                 f"injected random write fault #{self._wcount}")

    def take_torn(self, file_off: int, length: int) -> list:
        """Pop-and-return the torn offsets a landed write covers."""
        hit = [off for off in self.torn_write_offsets
               if file_off <= off < file_off + length]
        for off in hit:
            self.torn_write_offsets.discard(off)
        return hit

    def take_member_corrupt(self, member: Optional[int], file_off: int,
                            length: int) -> list:
        """Pop-and-return this MEMBER's seeded-rot offsets a landed write
        covers (resident-corruption tier, ISSUE 16)."""
        offs = self.corrupt_member_offsets.get(member)
        if not offs:
            return []
        hit = [off for off in offs if file_off <= off < file_off + length]
        for off in hit:
            offs.discard(off)
        return hit

    def check(self, file_off: int, length: int,
              member: Optional[int] = None) -> None:
        self._count += 1
        if self.latency_s:
            time.sleep(self.latency_s)
        if self.slow_s and member is not None and member == self.slow_member:
            time.sleep(self.slow_s)
        if self.failstopped(member):
            raise StromError(_errno.EIO,
                             f"injected fail-stop of member {member}",
                             error_class=ErrorClass.PERSISTENT)
        if self.fail_every_nth and self._count % self.fail_every_nth == 0:
            raise StromError(_errno.EIO, f"injected periodic fault #{self._count}")
        if self.fail_rate > 0.0:
            if self._rng is None:
                import random
                self._rng = random.Random(self.seed)
            if self._rng.random() < self.fail_rate:
                raise StromError(_errno.EIO,
                                 f"injected random fault #{self._count}")
        self.check_buffered(file_off, length, member=member)

    def check_buffered(self, file_off: int, length: int,
                       member: Optional[int] = None) -> None:
        """The persistent tier only: consulted by the buffered fallback so
        dead regions — and fail-stopped members — stay dead on every path."""
        if self.failstopped(member):
            raise StromError(_errno.EIO,
                             f"injected fail-stop of member {member}",
                             error_class=ErrorClass.PERSISTENT)
        for off in self.fail_offsets:
            if file_off <= off < file_off + length:
                raise StromError(_errno.EIO, f"injected fault at {off}")

    def apply_corruption(self, file_off: int, dest: memoryview) -> None:
        for off in self.corrupt_offsets:
            if file_off <= off < file_off + len(dest):
                dest[off - file_off] = dest[off - file_off] ^ 0xFF
        hit = [off for off in self.corrupt_once_offsets
               if file_off <= off < file_off + len(dest)]
        for off in hit:
            dest[off - file_off] = dest[off - file_off] ^ 0xFF
            self.corrupt_once_offsets.discard(off)


def _tear_landed(member_obj, plan: FaultPlan, file_off: int,
                 length: int) -> None:
    """Apply one-shot torn-write corruption to bytes a write just landed:
    flip the listed byte directly on disk through the member's buffered fd
    and fsync, so a subsequent O_DIRECT read-back (the ``write_verify``
    oracle) observes the torn state, not a cached page."""
    hit = plan.take_torn(file_off, length)
    if not hit:
        return
    fd = member_obj.fd_buffered
    for off in hit:
        b = os.pread(fd, 1, off)
        os.pwrite(fd, bytes([b[0] ^ 0xFF]), off)
    os.fsync(fd)


def _rot_landed(member_obj, plan: FaultPlan, member: Optional[int],
                file_off: int, length: int) -> None:
    """Member-scoped on-disk bit-rot (resident-corruption tier, ISSUE 16):
    flip the listed byte of THIS member's backing file after a covering
    write lands, one-shot, same fsync discipline as `_tear_landed` — the
    seeded rot model for KV spill blocks whose mirror leg stays clean."""
    hit = plan.take_member_corrupt(member, file_off, length)
    if not hit:
        return
    fd = member_obj.fd_buffered
    for off in hit:
        b = os.pread(fd, 1, off)
        os.pwrite(fd, bytes([b[0] ^ 0xFF]), off)
    os.fsync(fd)


def flip_resident_host(skey, base: int, length: int, pos: int = 0) -> bool:
    """Seed bit-rot in a resident HOST ARC slab (no disk representation:
    the flip happens in the pinned mmap itself).  Returns False when the
    extent is not resident."""
    from ..cache import residency_cache
    return residency_cache._flip_resident_byte(skey, base, length, pos)


def flip_resident_hbm(skey, base: int, length: int, pos: int = 0) -> bool:
    """Seed bit-rot in a resident HBM extent (device array swapped for a
    corrupted copy).  Returns False when the extent is not resident."""
    from ..serving.hbm_tier import hbm_tier
    return hbm_tier._flip_resident_byte(skey, base, length, pos)


class FakeNvmeSource(PlainSource):
    """Loopback 'NVMe device': a plain file plus injected latency/faults.

    Reads go through the normal O_DIRECT fds so alignment behaviour stays
    real; latency, failures and corruption are injected at read time so
    async error latching / retention and corruption oracles are exercised.
    """

    def __init__(self, path: str, *, fault_plan: Optional[FaultPlan] = None,
                 block_size: int = 512, force_cached_fraction: Optional[float] = None,
                 writable: bool = False):
        super().__init__(path, block_size, writable=writable)
        self.fault_plan = fault_plan or FaultPlan()
        self.force_cached_fraction = force_cached_fraction

    def read_member_direct(self, member: int, file_off: int, dest: memoryview) -> None:
        self.fault_plan.check(file_off, len(dest), member=member)
        super().read_member_direct(member, file_off, dest)
        self.fault_plan.apply_corruption(file_off, dest)

    def read_member_buffered(self, member: int, file_off: int, dest: memoryview) -> None:
        # the engine's degraded tier reads through here: persistent bad
        # regions must fail it too, transient/periodic plans must not
        self.fault_plan.check_buffered(file_off, len(dest), member=member)
        super().read_member_buffered(member, file_off, dest)

    # overriding the write legs routes writes down the engine's Python
    # pool ladder (ISSUE 11), the same trick the read overrides use
    def write_member_direct(self, member: int, file_off: int, src: memoryview) -> None:
        self.fault_plan.check_write(file_off, len(src), member=member)
        super().write_member_direct(member, file_off, src)
        _tear_landed(self._m, self.fault_plan, file_off, len(src))
        _rot_landed(self._m, self.fault_plan, member, file_off, len(src))

    def write_member_buffered(self, member: int, file_off: int, src: memoryview) -> None:
        self.fault_plan.check_write(file_off, len(src), member=member)
        super().write_member_buffered(member, file_off, src)
        _tear_landed(self._m, self.fault_plan, file_off, len(src))
        _rot_landed(self._m, self.fault_plan, member, file_off, len(src))

    def cached_fraction(self, offset: int, length: int) -> float:
        if self.force_cached_fraction is not None:
            return self.force_cached_fraction
        return super().cached_fraction(offset, length)

    def hot_fraction(self, offset: int, length: int) -> float:
        # with a forced cache verdict the test owns arbitration: only
        # explicit hints count, not the ambient dirtiness of a freshly
        # written test file (which would route everything write-back and
        # bypass the direct path the fault plan instruments)
        if self.force_cached_fraction is not None:
            from ..engine import Source
            return Source.hot_fraction(self, offset, length)
        return super().hot_fraction(offset, length)


class FakeStripedNvmeSource(StripedSource):
    """Striped loopback 'NVMe set': N member files plus per-member
    injected latency/faults (PR 5).

    Same injection tiers as :class:`FakeNvmeSource`, but the member index
    flows into the plan so ``slow_member`` / per-lane quarantine scenarios
    exercise the engine's per-member submission lanes: the overridden read
    leg routes the whole task down the Python pool path, where each member
    of a striped source gets its own worker pool — a slow or failing
    member stalls only its own lane while siblings drain.
    """

    def __init__(self, paths, stripe_chunk_size: int, *,
                 fault_plan: Optional[FaultPlan] = None,
                 block_size: int = 512,
                 force_cached_fraction: Optional[float] = None,
                 mirror: Optional[str] = None,
                 writable: bool = False):
        super().__init__(paths, stripe_chunk_size, block_size,
                         writable=writable, mirror=mirror)
        self.fault_plan = fault_plan or FaultPlan()
        self.force_cached_fraction = force_cached_fraction

    def read_member_direct(self, member: int, file_off: int, dest: memoryview) -> None:
        self.fault_plan.check(file_off, len(dest), member=member)
        super().read_member_direct(member, file_off, dest)
        self.fault_plan.apply_corruption(file_off, dest)

    def read_member_buffered(self, member: int, file_off: int, dest: memoryview) -> None:
        self.fault_plan.check_buffered(file_off, len(dest), member=member)
        super().read_member_buffered(member, file_off, dest)

    # write legs through the pool ladder + write-side injection (ISSUE 11)
    def write_member_direct(self, member: int, file_off: int, src: memoryview) -> None:
        self.fault_plan.check_write(file_off, len(src), member=member)
        super().write_member_direct(member, file_off, src)
        _tear_landed(self.members[member], self.fault_plan,
                     file_off, len(src))
        _rot_landed(self.members[member], self.fault_plan, member,
                    file_off, len(src))

    def write_member_buffered(self, member: int, file_off: int, src: memoryview) -> None:
        self.fault_plan.check_write(file_off, len(src), member=member)
        super().write_member_buffered(member, file_off, src)
        _tear_landed(self.members[member], self.fault_plan,
                     file_off, len(src))
        _rot_landed(self.members[member], self.fault_plan, member,
                    file_off, len(src))

    def cached_fraction(self, offset: int, length: int) -> float:
        if self.force_cached_fraction is not None:
            return self.force_cached_fraction
        return super().cached_fraction(offset, length)

    def hot_fraction(self, offset: int, length: int) -> float:
        # forced verdicts own arbitration (see FakeNvmeSource.hot_fraction)
        if self.force_cached_fraction is not None:
            from ..engine import Source
            return Source.hot_fraction(self, offset, length)
        return super().hot_fraction(offset, length)


class backend_fault:
    """Context manager injecting a device-backend failure at the H2D
    fence (VERDICT r3 #5): ``mode="hang"`` makes the next fence exceed
    its bounded timeout (the signature of a hung device runtime);
    ``mode="error"`` raises a PJRT-style runtime error from it.  Either
    way the BackendMonitor latches loss, registered HBM buffers revoke
    with ENODEV, and in-flight staging fails instead of hanging —
    testable with no hardware at all.

    On exit the monitor latch is RESET (buffers already revoked stay
    revoked — loss is not retroactively undone, matching the reference's
    one-way revocation callback, kmod/pmemmap.c:149-208)."""

    def __init__(self, mode: str = "hang", *, hang_s: float = 30.0):
        if mode not in ("hang", "error"):
            raise ValueError(f"backend_fault mode {mode!r}")
        self.mode = mode
        self.hang_s = hang_s

    def __enter__(self):
        from ..hbm.backend import monitor

        def hook(what: str) -> None:
            if self.mode == "error":
                raise RuntimeError(f"injected PJRT failure during {what}")
            time.sleep(self.hang_s)   # the bounded fence times out first

        monitor._set_fault(hook)
        return self

    def __exit__(self, *exc):
        from ..hbm.backend import monitor
        monitor._set_fault(None)
        monitor.reset()
        return False
