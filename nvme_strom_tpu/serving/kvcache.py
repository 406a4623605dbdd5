"""SSD-backed KV-cache paging over the HBM residency tier (ISSUE 15).

Serving long contexts means the KV cache outgrows HBM; the batch story
(vLLM's PagedAttention) solves fragmentation with fixed-size blocks and
per-sequence block tables, and this module adds the tier below it: a
block that falls out of the device working set demotes to pinned host
RAM, and out of THAT to the SSD spill extent — riding the session's
write ladder, so a mirrored spill source keeps paging byte-identical
through member fail-stop (the read path heals page-ins via the mirror,
the write path keeps legs coherent).

Tier placement is exclusive — a block lives in exactly ONE of:

* **HBM** — pinned through the unified extent space
  (``extent_space.pin``/``unpin``, the ISSUE 20 placement engine),
  holding a :class:`~..tiering.TierLease` whose ``refs>0`` makes the
  HBM tier's own LRU skip it; only the pool demotes its blocks, via
  ``unpin``, which bypasses RAM-tier demotion because the pool owns
  the bytes' next home,
* **pinned RAM** — a slot in one session DMA buffer (pinned +
  io_uring-fixed, so page-out/page-in are zero-staging engine copies),
* **SSD** — a ``block_bytes``-chunk slot in the writable spill source.

Movement down is pool-LRU driven and counted/traced: ``nr_kv_pageout``
with a ``kv_page`` span per RAM→SSD write, ``nr_kv_pagein`` + span per
SSD→RAM read, and :meth:`KvBlockPool.resume` batch-prefetches a parked
sequence's spilled blocks with one async submit per block (the
``DeviceLoader`` prefetch discipline applied to sequence resumption).

Keys in the HBM tier use a per-pool synthetic source key (``#kvpool:N``
tag — the same '#'-tag convention ``cache.source_key`` uses for source
framing), so KV extents can never collide with file-backed cache
entries and path invalidation never touches them.
"""

from __future__ import annotations

import errno as _errno
import itertools
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional

from ..api import StromError
from ..config import config
from ..stats import stats
from ..trace import recorder as _trace
from ..integrity import domain as _integrity, register_pool
from ..tiering import extent_space

__all__ = ["KvBlockPool"]

_pool_ids = itertools.count(1)

#: pressure-shed priority (ISSUE 16): bulk chains demote first, the
#: latency class last — the PR 12 QoS ordering applied to residency
_SHED_ORDER = {"bulk": 0, "normal": 1, "latency": 2}


class _Block:
    __slots__ = ("seq", "idx", "gid", "tier", "slot", "lease", "crc")

    def __init__(self, seq, idx: int, gid: int) -> None:
        self.seq = seq
        self.idx = idx
        self.gid = gid      # pool-global id; HBM-tier base = gid*block_bytes
        self.tier = "ram"   # "hbm" | "ram" | "ssd"
        self.slot = -1      # ram slot or ssd slot, by tier
        self.lease = None   # TierLease pin while tier == "hbm"
        self.crc = None     # fill-time crc32c (None under integrity=off)


class KvBlockPool:
    """Fixed-size KV block pool with per-sequence block tables.

    *spill* is a writable :class:`~..engine.Source` (mirror it for
    fail-stop survival) whose size bounds the SSD tier; *ram_blocks*
    bounds the pinned-RAM tier; the HBM share defaults to half the
    device tier's capacity (``hbm_cache_bytes``), leaving room for the
    scan-promotion traffic the tier also serves."""

    def __init__(self, session, spill, *, block_bytes: Optional[int] = None,
                 ram_blocks: int = 16, hbm_blocks: Optional[int] = None,
                 durable: bool = False) -> None:
        bb = int(block_bytes or config.get("tier_kv_block_bytes"))
        if bb <= 0 or (bb & (bb - 1)):
            raise StromError(_errno.EINVAL,
                             f"block_bytes {bb} must be a power of two")
        if ram_blocks < 2:
            raise StromError(_errno.EINVAL, "need at least 2 RAM blocks")
        spill._check_writable()
        self.block_bytes = bb
        self._session = session
        self._spill = spill
        self._durable = durable
        self._handle, self._dma = session.alloc_dma_buffer(ram_blocks * bb)
        self._ram_free = list(range(ram_blocks))
        self._ssd_free = list(range(spill.size // bb))
        if not self._ssd_free:
            raise StromError(_errno.EINVAL,
                             f"spill source smaller than one {bb}B block")
        if hbm_blocks is None:
            hbm_blocks = (extent_space.tier_capacity("hbm") // 2 // bb
                          if extent_space.tier_active("hbm") else 0)
        self._hbm_budget = hbm_blocks
        self._hbm_used = 0
        self._skey = ("#kvpool:%d" % next(_pool_ids),)
        self._tables: Dict[object, List[_Block]] = {}
        self._classes: Dict[object, str] = {}  # seq -> QoS class (PR 12)
        self._lru: "OrderedDict[int, _Block]" = OrderedDict()  # ram+hbm
        self._gids = itertools.count()
        self._lock = threading.RLock()
        self._closed = False
        # integrity domain (ISSUE 16): the scrubber walks this pool's
        # spill blocks and memlock pressure can ask it to shed capacity
        register_pool(self)

    # -- introspection -------------------------------------------------

    def residency(self) -> Dict[str, int]:
        """Block counts per tier (the tpu_stat serving scoreboard reads
        this)."""
        with self._lock:
            out = {"hbm": 0, "ram": 0, "ssd": 0}
            for table in self._tables.values():
                for b in table:
                    out[b.tier] += 1
            return out

    def sequences(self) -> List[object]:
        with self._lock:
            return list(self._tables)

    def blocks(self, seq) -> int:
        with self._lock:
            return len(self._tables.get(seq, ()))

    # -- block table ops ----------------------------------------------

    def append(self, seq, data, *, qos_class: Optional[str] = None) -> int:
        """Append *data* (≤ block_bytes; short blocks are zero-padded)
        as the sequence's next block; returns its block index.
        ``qos_class`` pins the sequence's pressure-shed priority (PR 12
        classes; bulk sheds first) — defaults to ``qos_default_class``."""
        with self._lock:
            self._check_open()
            table = self._tables.setdefault(seq, [])
            if seq not in self._classes:
                self._classes[seq] = qos_class or \
                    str(config.get("qos_default_class"))
            elif qos_class:
                self._classes[seq] = qos_class
            blk = _Block(seq, len(table), next(self._gids))
            blk.slot = self._get_ram_slot()
            self._lru[blk.gid] = blk
            table.append(blk)
            self._fill_ram(blk, data)
            return blk.idx

    def write(self, seq, idx: int, data) -> None:
        """Overwrite block *idx* in place (decode-step KV updates land
        here).  An HBM-resident block demotes to RAM first — the device
        copy is immutable — and re-promotes on its next read."""
        with self._lock:
            self._check_open()
            blk = self._get_block(seq, idx)
            if blk.tier == "hbm":
                self._demote_hbm(blk)
            elif blk.tier == "ssd":
                self._page_in(blk)
            self._lru.move_to_end(blk.gid)
            self._fill_ram(blk, data)

    def read(self, seq, idx: int) -> bytes:
        """Block bytes, paged in / promoted as a side effect: an SSD
        block pages into RAM (healed via mirror when a member is down),
        a RAM block promotes into the pool's pinned HBM share while the
        budget allows."""
        with self._lock:
            self._check_open()
            blk = self._get_block(seq, idx)
            if blk.tier == "ssd":
                self._page_in(blk)
            if blk.tier == "ram":
                self._promote(blk)
            self._lru.move_to_end(blk.gid)
            if blk.tier == "hbm":
                out = bytearray(self.block_bytes)
                if not blk.lease.copy_into(memoryview(out)):
                    # invalidated between pin and copy (backend
                    # revocation): exclusive placement means the bytes
                    # have no other home — hard error
                    self._drop_hbm(blk)
                    blk.tier, blk.slot = "ram", self._get_ram_slot()
                    raise StromError(
                        _errno.EIO,
                        f"KV block {blk.idx} lost to HBM revocation")
                return bytes(out)
            return bytes(self._ram_view(blk.slot))

    def device_array(self, seq, idx: int):
        """The block as its device-resident uint8 array (attention
        kernels consume this without a host round-trip), promoting it
        if needed; None when the HBM share is exhausted or the tier is
        off."""
        with self._lock:
            self._check_open()
            blk = self._get_block(seq, idx)
            if blk.tier == "ssd":
                self._page_in(blk)
            if blk.tier == "ram":
                self._promote(blk)
            self._lru.move_to_end(blk.gid)
            return blk.lease.device_array() if blk.tier == "hbm" else None

    def resume(self, seq) -> int:
        """Prefetch-on-sequence-resume: page every spilled block of
        *seq* back into RAM with ONE async submit per block, waiting
        once at the end (the cross-epoch overlap discipline).  Returns
        the number of blocks paged in."""
        with self._lock:
            self._check_open()
            table = self._tables.get(seq, [])
            spilled = [b for b in table if b.tier == "ssd"]
            # cap at what RAM can hold without evicting this sequence
            budget = len(self._ram_free) + sum(
                1 for b in self._lru.values()
                if b.tier == "ram" and b.seq != seq)
            spilled = spilled[:max(0, budget)]
            inflight = []
            for blk in spilled:
                slot = self._get_ram_slot(avoid_seq=seq)
                ts = time.monotonic_ns()
                res = self._session.memcpy_ssd2ram(
                    self._spill, self._handle, [blk.slot],
                    self.block_bytes, dest_offset=slot * self.block_bytes)
                inflight.append((blk, slot, res, ts))
            for blk, slot, res, ts in inflight:
                self._session.memcpy_wait(res.dma_task_id)
                self._verify_landed(blk, blk.slot, self._ram_view(slot))
                self._ssd_free.append(blk.slot)
                blk.tier, blk.slot = "ram", slot
                self._lru[blk.gid] = blk
                self._lru.move_to_end(blk.gid)
                stats.add("nr_kv_pagein")
                stats.add("nr_tier_ram_fault")  # SSD→RAM demand fault
                if _trace.active:
                    _trace.span("kv_page", ts, time.monotonic_ns(),
                                offset=blk.gid * self.block_bytes,
                                length=self.block_bytes,
                                args={"dir": "in", "block": blk.idx,
                                      "resume": True})
            return len(inflight)

    def release(self, seq) -> None:
        """Drop a finished sequence: every tier slot returns to its
        free list, HBM pins release and drop."""
        with self._lock:
            table = self._tables.pop(seq, [])
            self._classes.pop(seq, None)
            for blk in table:
                if blk.tier == "hbm":
                    extent_space.unpin(blk.lease, self._skey,
                                       blk.gid * self.block_bytes,
                                       self.block_bytes)
                    self._hbm_used -= 1
                elif blk.tier == "ram":
                    self._ram_free.append(blk.slot)
                else:
                    self._ssd_free.append(blk.slot)
                self._lru.pop(blk.gid, None)

    def migrate(self, seq, peer: "KvBlockPool", *,
                release: bool = True) -> int:
        """Move sequence *seq*'s whole chain into *peer*'s pool (the
        cross-host KV migration lane, ISSUE 17: on a multi-host serving
        mesh each host runs its own pool over its own local spill, and a
        hot host sheds chains to a cold peer instead of thrashing its
        own tiers).

        All-or-nothing: blocks are copied out through the read path (so
        spilled blocks page in via the fault ladder) while the SOURCE
        chain stays intact, then appended to the peer in order with the
        sequence's QoS class preserved.  A mid-migration peer failure
        rolls the peer back (``peer.release``) and raises — the source
        is untouched and still SSD-resumable, so a crashed destination
        host loses nothing.  Only after the peer holds every block is
        the source chain released (``release=False`` keeps it, e.g. for
        a read-only replica).  Returns the bytes migrated."""
        if not bool(config.get("kv_migrate")):
            raise StromError(_errno.EOPNOTSUPP,
                             "cross-host KV migration disabled (kv_migrate)")
        if peer is self:
            raise StromError(_errno.EINVAL,
                             "cannot migrate a sequence onto its own pool")
        if self.block_bytes > peer.block_bytes:
            raise StromError(
                _errno.EINVAL,
                f"peer blocks ({peer.block_bytes}B) smaller than "
                f"ours ({self.block_bytes}B)")
        t0 = time.monotonic_ns()
        with self._lock:
            self._check_open()
            if seq not in self._tables:
                raise StromError(_errno.ENOENT, f"no sequence {seq!r}")
            qos = self._classes.get(seq,
                                    str(config.get("qos_default_class")))
            n = len(self._tables[seq])
        if peer.blocks(seq):
            raise StromError(_errno.EEXIST,
                             f"peer already holds sequence {seq!r}")
        # copy-out happens under OUR lock per block; peer.append runs
        # under the PEER's lock only — never both at once, so two pools
        # migrating toward each other cannot deadlock
        try:
            for i in range(n):
                peer.append(seq, self.read(seq, i), qos_class=qos)
        except BaseException:
            stats.add("nr_kv_migrate_fail")
            try:
                peer.release(seq)
            except Exception:  # noqa: BLE001 - rollback is best-effort
                pass
            raise
        if release:
            self.release(seq)
        stats.add("nr_kv_migrate")
        if _trace.active:
            _trace.span("kv_migrate", t0, time.monotonic_ns(),
                        length=n * self.block_bytes,
                        args={"blocks": n, "class": qos,
                              "released": release})
        return n * self.block_bytes

    def shed_to_peer(self, peer: "KvBlockPool", nbytes: int, *,
                     reason: str = "pressure") -> int:
        """Hot-host pressure relief over the fabric: migrate whole
        chains to a cold peer until ~*nbytes* have moved, bulk-class
        sequences first (the :data:`_SHED_ORDER` ladder — latency
        chains keep their local placement longest).  Chains the peer
        cannot take (full tiers, duplicate key) are skipped, never
        raised: like :meth:`shed`, this sheds what it can."""
        with self._lock:
            if self._closed:
                return 0
            seqs = sorted(
                self._tables,
                key=lambda s: _SHED_ORDER.get(
                    self._classes.get(s, "normal"), 1))
        shed = 0
        for seq in seqs:
            if shed >= nbytes:
                break
            try:
                moved = self.migrate(seq, peer)
            except StromError:
                continue
            shed += moved
            if _trace.active:
                _trace.instant("pressure_shed", length=moved,
                               args={"tier": "kv-peer", "reason": reason})
        return shed

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            for seq in list(self._tables):
                self.release(seq)
            self._closed = True
            try:
                self._session.unmap_buffer(self._handle)
            except StromError:
                pass

    # -- internals (pool lock held) ------------------------------------

    def _check_open(self) -> None:
        if self._closed:
            raise StromError(_errno.EBADF, "KV pool closed")

    def _get_block(self, seq, idx: int) -> _Block:
        try:
            return self._tables[seq][idx]
        except (KeyError, IndexError):
            raise StromError(
                _errno.ENOENT, f"no KV block {idx} for sequence {seq!r}"
            ) from None

    def _ram_view(self, slot: int) -> memoryview:
        base = slot * self.block_bytes
        return self._dma.view()[base:base + self.block_bytes]

    def _fill_ram(self, blk: _Block, data) -> None:
        n = len(data)
        if n > self.block_bytes:
            raise StromError(_errno.EINVAL,
                             f"{n}B exceeds the {self.block_bytes}B block")
        view = self._ram_view(blk.slot)
        view[:n] = bytes(data) if not isinstance(data, (bytes, bytearray,
                                                        memoryview)) else data
        if n < self.block_bytes:
            view[n:] = b"\0" * (self.block_bytes - n)
        # the crc covers the whole (zero-padded) block: page-out writes
        # and page-in reads move full blocks
        blk.crc = _integrity.checksum(view)

    def _get_ram_slot(self, avoid_seq=None) -> int:
        """A free RAM slot, paging out the pool-LRU RAM block if none
        is free (HBM blocks are pinned and SSD blocks hold no slot, so
        only ``tier=="ram"`` entries are candidates)."""
        if self._ram_free:
            return self._ram_free.pop()
        for gid, blk in self._lru.items():
            if blk.tier == "ram" and (avoid_seq is None
                                      or blk.seq != avoid_seq):
                self._page_out(blk)
                break
        if not self._ram_free:
            raise StromError(_errno.ENOSPC,
                             "KV RAM tier exhausted and nothing evictable")
        return self._ram_free.pop()

    def _page_out(self, blk: _Block) -> None:
        """RAM→SSD demotion over the session's write ladder (mirrored
        spill sources keep both legs coherent)."""
        if not self._ssd_free:
            raise StromError(_errno.ENOSPC, "KV spill extent full")
        if _integrity.active:
            # page-out is a tier transition: catch RAM rot before it is
            # made durable (counted; the write still proceeds — this is
            # the only copy, and the counter is the operator's signal)
            _integrity.verify(self._ram_view(blk.slot), blk.crc)
        ssd_slot = self._ssd_free.pop()
        ts = time.monotonic_ns()
        res = self._session.memcpy_ram2ssd(
            self._spill, self._handle, [ssd_slot], self.block_bytes,
            src_offset=blk.slot * self.block_bytes)
        self._session.memcpy_wait(res.dma_task_id)
        if self._durable:
            self._spill.sync()
        self._ram_free.append(blk.slot)
        self._lru.pop(blk.gid, None)
        blk.tier, blk.slot = "ssd", ssd_slot
        stats.add("nr_kv_pageout")
        if _trace.active:
            _trace.span("kv_page", ts, time.monotonic_ns(),
                        offset=blk.gid * self.block_bytes,
                        length=self.block_bytes,
                        args={"dir": "out", "block": blk.idx})

    def _page_in(self, blk: _Block) -> None:
        """SSD→RAM page-in; the engine's fault ladder (hedges, mirror
        reads) serves it even with a spill member fail-stopped.  Under
        the integrity domain the landed bytes are verified against the
        page-out crc, and a mismatch is healed from the mirror leg
        (write-back to the corrupt primary) or raises EBADMSG."""
        slot = self._get_ram_slot()
        ts = time.monotonic_ns()
        ssd_slot = blk.slot
        res = self._session.memcpy_ssd2ram(
            self._spill, self._handle, [ssd_slot], self.block_bytes,
            dest_offset=slot * self.block_bytes)
        self._session.memcpy_wait(res.dma_task_id)
        try:
            self._verify_landed(blk, ssd_slot, self._ram_view(slot))
        except StromError:
            self._ram_free.append(slot)  # block stays on SSD, corrupt
            raise
        self._ssd_free.append(ssd_slot)
        self._lru[blk.gid] = blk
        blk.tier, blk.slot = "ram", slot
        stats.add("nr_kv_pagein")
        stats.add("nr_tier_ram_fault")  # SSD→RAM demand fault
        if _trace.active:
            _trace.span("kv_page", ts, time.monotonic_ns(),
                        offset=blk.gid * self.block_bytes,
                        length=self.block_bytes,
                        args={"dir": "in", "block": blk.idx})

    def _promote(self, blk: _Block) -> None:
        """RAM→HBM while the pool's pinned share allows; the extent
        space places and pins the block in one transition (the lease pin
        makes the tier's own LRU skip it)."""
        if not extent_space.tier_active("hbm") \
                or self._hbm_used >= self._hbm_budget:
            return
        base = blk.gid * self.block_bytes
        data = self._ram_view(blk.slot)
        # pin verifies data against the crc (promote is a transition);
        # a rotted RAM block simply stays in RAM, counted
        lease = extent_space.pin(self._skey, base, self.block_bytes,
                                 data, crc=blk.crc)
        if lease is None:
            return
        self._ram_free.append(blk.slot)
        blk.tier, blk.slot, blk.lease = "hbm", -1, lease
        self._hbm_used += 1

    def _demote_hbm(self, blk: _Block) -> None:
        """HBM→RAM: copy the device bytes into a fresh RAM slot, then
        drop the tier entry WITHOUT host-ARC demotion (the pool is the
        bytes' home)."""
        slot = self._get_ram_slot()
        ok = blk.lease.copy_into(self._ram_view(slot))
        if ok and _integrity.active:
            # demote is a tier transition: a rotted device copy is the
            # only copy, so the mismatch is counted, not raised
            _integrity.verify(self._ram_view(slot), blk.crc)
        self._drop_hbm(blk)
        blk.tier, blk.slot = "ram", slot
        if not ok:  # pragma: no cover - invalidated between pin and copy
            raise StromError(_errno.EIO,
                             f"KV block {blk.idx} lost to HBM revocation")

    def _drop_hbm(self, blk: _Block) -> None:
        extent_space.unpin(blk.lease, self._skey,
                           blk.gid * self.block_bytes, self.block_bytes)
        blk.lease = None
        self._hbm_used -= 1

    # -- integrity domain (ISSUE 16) -----------------------------------

    def _verify_landed(self, blk: _Block, ssd_slot: int, view) -> None:
        """Verify a page-in's landed bytes against the page-out crc;
        on mismatch heal from the mirror leg (fixing the corrupt
        primary on disk too) or raise EBADMSG — a spill block has no
        other copy to fail open to."""
        if blk.crc is None or not _integrity.active:
            return
        if _integrity.verify(view, blk.crc):
            return
        t0 = time.monotonic_ns()
        debits = self._heal_spill(blk, ssd_slot, view)
        if debits is None:
            stats.add("nr_scrub_fail")
            raise StromError(
                _errno.EBADMSG,
                f"KV block {blk.idx} corrupt on spill and unhealable")
        stats.add("nr_scrub_repair")
        if _trace.active:
            _trace.span("repair", t0, time.monotonic_ns(),
                        offset=ssd_slot * self.block_bytes,
                        length=self.block_bytes,
                        args={"tier": "ssd", "block": blk.idx})
        for m in debits:
            self._debit(m)

    def _heal_spill(self, blk: _Block, ssd_slot: int, view):
        """Re-assemble the block from each extent's mirror leg into
        *view*, verify, and write the healed bytes back to the corrupt
        primary members.  Returns the list of primary members healed
        over (for health debits), or None when unhealable (no mirror,
        or the mirror leg is corrupt too)."""
        spill = self._spill
        if getattr(spill, "mirror_of", None) is None:
            return None
        base = ssd_slot * self.block_bytes
        try:
            spans = spill.extents(base, self.block_bytes)
        except Exception:
            return None
        for ext in spans:
            mirror = spill.mirror_of(ext.member)
            if mirror is None:
                return None
            off = ext.logical_off - base
            try:
                spill.read_member_buffered(
                    mirror, ext.file_off, view[off:off + ext.length])
            except Exception:
                return None
        if not _integrity.verify(view, blk.crc):
            return None  # both legs rotted: data is gone
        debits = []
        for ext in spans:
            try:
                spill.write_member_buffered(
                    ext.member, ext.file_off,
                    view[ext.logical_off - base:
                         ext.logical_off - base + ext.length])
                debits.append(ext.member)
            except Exception:
                continue  # primary still down: RAM copy is good anyway
        if self._durable:
            try:
                spill.sync()
            except Exception:
                pass
        return debits

    def _debit(self, member: int) -> None:
        """A scrub/page-in failure attributable to a spill member."""
        stats.member_error(member)
        try:
            self._session._member_health.record_failure(member)
        except Exception:  # pragma: no cover - session tearing down
            pass

    def scrub_spill(self, budget: int):
        """Scrubber entry point: verify SSD-resident blocks against
        their page-out crcs, healing mismatches from the mirror leg.
        Returns ``(bytes_scanned, member_debits)``; never raises."""
        with self._lock:
            if self._closed:
                return 0, []
            blocks = [b for t in self._tables.values() for b in t
                      if b.tier == "ssd" and b.crc is not None]
        scanned = 0
        debits: List[int] = []
        buf = memoryview(bytearray(self.block_bytes))
        for blk in blocks:
            if scanned >= budget:
                break
            with self._lock:
                if self._closed:
                    break
                if blk.tier != "ssd":
                    continue
                ssd_slot = blk.slot
                t0 = time.monotonic_ns()
                try:
                    self._spill.read_buffered(
                        ssd_slot * self.block_bytes, buf)
                except Exception:
                    continue
                scanned += self.block_bytes
                stats.add("nr_scrub_extent")
                stats.add("bytes_scrubbed", self.block_bytes)
                ok = _integrity.verify(buf, blk.crc)
                if _trace.active:
                    _trace.span("scrub", t0, time.monotonic_ns(),
                                offset=ssd_slot * self.block_bytes,
                                length=self.block_bytes,
                                args={"tier": "ssd", "ok": ok})
                if ok:
                    continue
                t0 = time.monotonic_ns()
                healed = self._heal_spill(blk, ssd_slot, buf)
                if healed is None:
                    stats.add("nr_scrub_fail")
                    continue
                stats.add("nr_scrub_repair")
                if _trace.active:
                    _trace.span("repair", t0, time.monotonic_ns(),
                                offset=ssd_slot * self.block_bytes,
                                length=self.block_bytes,
                                args={"tier": "ssd", "block": blk.idx})
                debits.extend(healed)
        return scanned, debits

    def shed(self, nbytes: int, *, reason: str = "memlock") -> int:
        """Pressure relief: demote resident (HBM/RAM) blocks to SSD,
        bulk-class sequences first (the PR 12 QoS ordering), never
        raising — a full spill just bounds what can shed."""
        shed = 0
        with self._lock:
            if self._closed:
                return 0
            cands = [b for t in self._tables.values() for b in t
                     if b.tier != "ssd"]
            cands.sort(key=lambda b: _SHED_ORDER.get(
                self._classes.get(b.seq, "normal"), 1))
            for blk in cands:
                if shed >= nbytes:
                    break
                try:
                    if blk.tier == "hbm":
                        self._demote_hbm(blk)
                    self._page_out(blk)
                except StromError:
                    break  # spill full / revoked: shed what we could
                shed += self.block_bytes
                stats.add("nr_pressure_shed")
                if _trace.active:
                    _trace.instant(
                        "pressure_shed", offset=blk.gid * self.block_bytes,
                        length=self.block_bytes,
                        args={"tier": "kv", "reason": reason,
                              "class": self._classes.get(blk.seq,
                                                         "normal")})
        return shed
