"""Device-side (HBM) residency tier — ROADMAP item 2's device leg.

A capacity-bounded extent tier ABOVE the pinned-host-RAM ARC cache
(:mod:`..cache`): extents the host tier observes getting hot (second
touch, the t1→t2 ARC transition) are promoted into device-resident
buffers registered with :mod:`..hbm.registry`, the engine consults this
tier FIRST at plan time (an HBM hit costs one device→dest memcpy and no
host-slab touch at all), and eviction demotes the bytes back into the
host tier so capacity pressure moves data DOWN the hierarchy instead of
dropping it.  This is the LMB capacity-hierarchy story (PAPERS.md,
arXiv:2406.02039) with HBM as the top tier.

The contract deliberately mirrors ``cache.py``:

* **Keying** — identical: ``(source_key, base, length)`` exact-extent.
* **Leases** — :meth:`lookup` returns a refcounted :class:`HbmLease`
  (the unified :class:`..tiering.TierLease` contract); eviction skips
  pinned entries, invalidation marks them stale, stale entries are
  never served and free at the last release.  The KV pool pins its HBM
  working set through ``extent_space.pin``, which hands out exactly
  these leases.
* **Coherency** — the unified extent space fans every
  ``invalidate_extents``/``invalidate_paths`` out over all tiers, so
  every existing write-path/checkpoint invalidation site covers the
  device tier with no new call sites.
* **one-branch-when-off** — ``configure()`` reads ``tier_hbm_bytes``
  once; hot paths check the plain ``active`` attribute.

Eviction is byte-weighted LRU (not ARC): admission is already
frequency-filtered by the host tier's second-touch rule, so a recency
list suffices and keeps eviction O(1) against pinned working sets.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Optional, Sequence, Tuple

import numpy as np

from ..config import config
from ..stats import stats
from ..trace import recorder as _trace
from ..integrity import domain as _integrity
from ..tiering import TierLease, extent_space, source_key as _source_key

__all__ = ["HbmLease", "HbmResidencyTier", "hbm_tier"]


class _Entry:
    __slots__ = ("key", "array", "handle", "length", "refs", "stale",
                 "crc", "source_ref")

    def __init__(self, key, array, handle: int, length: int,
                 crc=None, source_ref=None) -> None:
        self.key = key
        self.array = array          # device-resident uint8 jax.Array
        self.handle = handle        # hbm.registry handle (revocation tie-in)
        self.length = length
        self.refs = 0
        self.stale = False
        # integrity domain (ISSUE 16): the extent's fill-time crc32c and
        # a source weakref so the scrubber can heal a rotted extent
        self.crc = crc
        self.source_ref = source_ref


class HbmLease(TierLease):
    """Refcounted pin on an HBM-resident extent: the unified
    :class:`..tiering.TierLease` holder contract under its
    pre-unification name.  ``device_array()`` hands zero-copy consumers
    — the KV pool's pinned working set — the device-resident bytes
    without ever leaving the device."""

    __slots__ = ()


class HbmResidencyTier:
    """Byte-weighted LRU over device-resident extent buffers."""

    def __init__(self) -> None:
        self.active = False
        self._lock = threading.Lock()
        self._cap = 0
        self._bytes = 0
        self._entries: "OrderedDict[tuple, _Entry]" = OrderedDict()
        self._device = None

    # -- configuration ------------------------------------------------

    def configure(self) -> None:
        """Re-read ``tier_hbm_bytes`` (``hbm_cache_bytes`` aliases it);
        0 disables the tier, frees it, and rewires the extent space's
        inter-tier transitions (the RAM tier's promotion hook)."""
        cap = int(config.get("tier_hbm_bytes"))
        demoted = []
        with self._lock:
            self._cap = cap
            self.active = cap > 0
            if not self.active:
                demoted = self._clear_locked()
            else:
                while self._bytes > cap:
                    d = self._evict_one_locked()
                    if d is None:
                        break
                    demoted.append(d)
        self._demote_to_host(demoted)
        # ONE placement engine: the extent space arms the RAM tier's
        # second-touch promotion hook iff this tier is on — one branch
        # when off
        extent_space.rewire()

    def clear(self) -> None:
        with self._lock:
            self._clear_locked()

    def _clear_locked(self):
        demoted = []
        for e in self._entries.values():
            if e.refs:
                e.stale = True
            else:
                demoted.append((e.key, self._take_bytes(e), e.source_ref))
                self._free_entry(e)
        self._entries.clear()
        self._bytes = 0
        stats.gauge_set("hbm_resident_bytes", 0)
        return demoted

    # -- identity (one identity across the unified space) -------------

    source_key = staticmethod(_source_key)

    # -- read side ----------------------------------------------------

    def lookup(self, skey: tuple, base: int,
               length: int) -> Optional[HbmLease]:
        """Return a pinned lease on the extent, or None.  Bumps LRU
        recency on the hit."""
        if not self.active:
            return None
        key = (skey, base, length)
        with self._lock:
            e = self._entries.get(key)
            if e is None or e.stale:
                return None
            self._entries.move_to_end(key)
            e.refs += 1
            return HbmLease(self, e)

    def _release(self, e: _Entry) -> None:
        drop = False
        with self._lock:
            e.refs -= 1
            if e.refs <= 0 and e.stale:
                drop = True
        if drop:
            self._free_entry(e)

    def _lease_view(self, e: _Entry):
        """TierLease owner hook: the extent's bytes as a host view (one
        D2H copy), or None when the backend revoked the array."""
        try:
            return memoryview(np.asarray(e.array).data)
        except Exception:  # pragma: no cover - revoked backend
            return None

    # -- fill / promotion side -----------------------------------------

    def admit(self, skey: tuple, base: int, length: int, data, *,
              crc=None, source_ref=None) -> bool:
        """Promote healed host bytes into a device-resident buffer.
        Called by the host tier on its second-touch transition (outside
        its lock) and by the KV pool when pinning a block.  Returns
        True when the extent is now HBM-resident; evicted victims are
        demoted into the host tier, never dropped.  ``crc`` is the
        extent's fill-time crc32c when the caller already has one
        (verified here — admit is a tier transition); ``source_ref``
        lets the scrubber heal the extent later."""
        if not self.active or length <= 0:
            return False
        key = (skey, base, length)
        # the device_put happens OUTSIDE the tier lock: it may be slow
        # (real H2D DMA) and needs no tier state
        host = np.frombuffer(bytes(data[:length]), dtype=np.uint8)
        if _integrity.active:
            if crc is None:
                crc = _integrity.checksum(host)
            elif not _integrity.verify(host, crc):
                return False  # corrupt promote: never lands in HBM
        arr, handle = self._place(host)
        if arr is None:
            return False
        demoted = []
        installed = False
        with self._lock:
            cap = self._cap
            if length > cap or key in self._entries:
                pass  # oversized, or a racing admit won
            else:
                ok = True
                while self._bytes + length > cap:
                    d = self._evict_one_locked()
                    if d is None:
                        ok = False  # everything evictable is pinned
                        break
                    demoted.append(d)
                if ok:
                    self._entries[key] = _Entry(key, arr, handle, length,
                                                crc, source_ref)
                    self._bytes += length
                    installed = True
                    stats.add("nr_hbm_promote")
                    stats.gauge_set("hbm_resident_bytes", self._bytes)
        self._demote_to_host(demoted)
        if not installed:
            self._unmap(handle)
        return installed

    def _place(self, host: np.ndarray):
        """host uint8 ndarray → registered device array.  Registration
        through :mod:`..hbm.registry` ties the tier into backend-loss
        revocation (a revoked entry raises on access; drop() heals)."""
        try:
            import jax
            from ..hbm.registry import registry
            dev = self._device or jax.local_devices()[0]
            self._device = dev
            arr = jax.device_put(host, dev)
            arr.block_until_ready()
            return arr, registry.map_device_memory(arr)
        except Exception:  # pragma: no cover - backend loss / no device
            return None, 0

    # -- eviction / demotion -------------------------------------------

    def _evict_one_locked(self):
        """Evict one unpinned LRU entry; returns ``(key, bytes)`` for
        host demotion, or None when everything evictable is pinned."""
        for key, e in self._entries.items():  # LRU first
            if e.refs:
                continue
            del self._entries[key]
            data = self._take_bytes(e)
            if data is not None and _integrity.active and \
                    not _integrity.verify(data, e.crc):
                data = None  # corrupt demote: never poisons the host tier
            self._bytes -= e.length
            self._free_entry(e)
            stats.add("nr_hbm_demote")
            stats.gauge_set("hbm_resident_bytes", self._bytes)
            if _trace.active:
                _trace.instant("cache_evict", offset=key[1],
                               length=e.length, args={"tier": "hbm"})
            return key, data, e.source_ref
        return None

    @staticmethod
    def _take_bytes(e: _Entry) -> Optional[bytes]:
        try:
            return bytes(np.asarray(e.array).data)
        except Exception:  # pragma: no cover - revoked backend
            return None

    def _demote_to_host(self, demoted) -> None:
        """Demoted extents move DOWN through the unified space: capacity
        pressure migrates data into the RAM tier instead of dropping it
        (a failed fill just means a future SSD re-read)."""
        extent_space.demote_from_hbm(demoted)

    def _free_entry(self, e: _Entry) -> None:
        self._unmap(e.handle)
        e.array = None

    @staticmethod
    def _unmap(handle: int) -> None:
        if not handle:
            return
        try:
            from ..hbm.registry import registry
            registry.unmap(handle, timeout=5.0)
        except Exception:  # pragma: no cover - already revoked/unmapped
            pass

    def drop(self, skey: tuple, base: int, length: int) -> bool:
        """Remove one extent WITHOUT demoting it to the host tier (the
        KV pool's explicit HBM→RAM demotion: the pool owns the bytes'
        next home).  Pinned entries go stale and free at last release."""
        if not self.active:
            return False
        key = (skey, base, length)
        with self._lock:
            e = self._entries.pop(key, None)
            if e is None:
                return False
            self._bytes -= e.length
            stats.gauge_set("hbm_resident_bytes", self._bytes)
            if e.refs:
                e.stale = True
                return True
        self._free_entry(e)
        return True

    # -- integrity scrub (ISSUE 16) ------------------------------------

    def scrub_keys(self) -> list:
        """Snapshot of verifiable resident keys.  Pinned entries (the KV
        pool's HBM working set) are skipped: the pool verifies its own
        blocks at its page/promote transitions, and exclusive placement
        means dropping one here would lose the only copy."""
        with self._lock:
            return [k for k, e in self._entries.items()
                    if not e.stale and e.crc is not None and not e.refs]

    def scrub_extent(self, key: tuple):
        """Verify one HBM-resident extent (one D2H copy) against its
        fill-time crc.  Returns ``(ok, length, source_ref)`` or None.
        A mismatch drops the entry WITHOUT host demotion — corrupt bytes
        never move down the hierarchy."""
        with self._lock:
            e = self._entries.get(key)
            if e is None or e.stale or e.crc is None or e.refs:
                return None
            e.refs += 1  # pin while the D2H copy + hash run unlocked
        data = self._take_bytes(e)
        ok = data is not None and _integrity.verify(data, e.crc)
        src = e.source_ref
        drop = None
        with self._lock:
            e.refs -= 1
            if not ok and not e.stale:
                if self._entries.get(key) is e:
                    del self._entries[key]
                    self._bytes -= e.length
                    stats.gauge_set("hbm_resident_bytes", self._bytes)
                    if e.refs:
                        e.stale = True
                    else:
                        drop = e
            elif e.stale and e.refs <= 0:
                drop = e  # invalidated under the scrub pin
        if drop is not None:
            self._free_entry(drop)
        return ok, e.length, src

    def _drop_corrupt(self, e: _Entry) -> None:
        """Integrity mismatch on a leased extent: drop it under its
        lease rules (the caller holds a ref, so it goes stale and frees
        at the last release)."""
        with self._lock:
            if self._entries.get(e.key) is e:
                del self._entries[e.key]
                self._bytes -= e.length
                stats.gauge_set("hbm_resident_bytes", self._bytes)
                e.stale = True

    def _flip_resident_byte(self, skey: tuple, base: int, length: int,
                            pos: int = 0) -> bool:
        """Testing hook (FaultPlan resident-corruption tiers): replace
        the device array with a one-byte-flipped copy, modelling HBM
        bit-rot.  The registry handle keeps mapping the original array —
        acceptable for a test-only flip; it is still unmapped on free."""
        key = (skey, base, length)
        with self._lock:
            e = self._entries.get(key)
            if e is None or e.stale:
                return False
        try:
            import jax
            host = np.array(np.asarray(e.array), dtype=np.uint8, copy=True)
            host[pos % host.size] ^= 0xFF
            flipped = jax.device_put(
                host, self._device or jax.local_devices()[0])
            flipped.block_until_ready()
        except Exception:  # pragma: no cover - backend loss
            return False
        with self._lock:
            if self._entries.get(key) is e and not e.stale:
                e.array = flipped
                return True
        return False

    # -- coherency (forwarded by the host tier) ------------------------

    def invalidate_extents(self, skey: tuple,
                           extents: Sequence[Tuple[int, int]]) -> int:
        """Same matching rule as the host tier: byte overlap under the
        same key, wholesale drop across framings that share a file."""
        if not self.active:
            return 0
        pathset = set(skey)
        victims = []
        with self._lock:
            for key in list(self._entries):
                ks, kb, kl = key
                if ks == skey:
                    if not any(kb < b + l and b < kb + kl
                               for b, l in extents):
                        continue
                elif not (pathset & set(ks)):
                    continue
                victims.append(self._invalidate_locked(key))
        return self._note_invalidated(victims, extents)

    def invalidate_paths(self, paths: Sequence[str]) -> int:
        if not self.active:
            return 0
        import os
        want = {os.path.realpath(p) for p in paths}
        victims = []
        with self._lock:
            for key in list(self._entries):
                if want & set(key[0]):
                    victims.append(self._invalidate_locked(key))
        return self._note_invalidated(victims, [])

    def _invalidate_locked(self, key) -> Optional[_Entry]:
        e = self._entries.pop(key)
        self._bytes -= e.length
        stats.gauge_set("hbm_resident_bytes", self._bytes)
        if e.refs:
            e.stale = True
            return None
        return e

    def _note_invalidated(self, victims, extents) -> int:
        for e in victims:
            if e is not None:
                self._free_entry(e)
        n = len(victims)
        if n:
            stats.add("nr_cache_invalidate", n)
            if _trace.active:
                off = extents[0][0] if extents else -1
                _trace.instant("cache_invalidate", offset=off, length=n,
                               args={"tier": "hbm"})
        return n

    # -- introspection ------------------------------------------------

    def resident_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def resident_fraction(self, paths: Sequence[str],
                          total_bytes: int) -> float:
        """Fraction of a table's bytes HBM-resident — the planner's
        expected device-hit ratio for a scan over *paths*."""
        if not self.active or total_bytes <= 0 or not paths:
            return 0.0
        import os
        want = {os.path.realpath(p) for p in paths if isinstance(p, str)}
        if not want:
            return 0.0
        got = 0
        with self._lock:
            for (ks, _b, ln), e in self._entries.items():
                if not e.stale and (want & set(ks)):
                    got += ln
        return min(1.0, got / float(total_bytes))


#: process-wide device tier; ``configure()`` is called at Session
#: construction (via extent_space.configure()) and by tests after
#: flipping ``hbm_cache_bytes``/``tier_hbm_bytes``
hbm_tier = HbmResidencyTier()

#: the unified extent space owns every transition in and out of this
#: tier (second-touch promotion in, demotion to the RAM tier out,
#: invalidation fan-out, the KV pool's pins)
extent_space.register_tier("hbm", hbm_tier)
