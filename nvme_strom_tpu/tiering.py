"""Unified extent address space (ISSUE 20).

ONE placement/migration engine over the capacity hierarchy
HBM → pinned RAM → SSD.  The per-tier stores stay where they grew up —
:mod:`.cache` is the RAM tier (byte-weighted ARC policy plugin),
:mod:`.serving.hbm_tier` is the HBM tier (byte-weighted LRU) — but every
*transition* between tiers is decided here, in :class:`ExtentSpace`:

* **demand faults** — a miss filled at wait time, after the fault ladder
  (retry/hedge/mirror/checksum) healed the bytes, lands in the RAM tier
  through :meth:`ExtentSpace.fault_fill`;
* **promotion** — the RAM tier's second-touch (ARC t1→t2) transition
  hands the extent UP, and the move is *exclusive*: the RAM copy is
  surrendered (:meth:`yield_up` on the tier) so HBM + RAM behave as one
  capacity pool instead of double-caching the hot set;
* **demotion** — HBM eviction victims move DOWN into the RAM tier; RAM
  eviction victims drop to the SSD-backed tier (the file itself — a
  future read is a demand fault, not data loss);
* **invalidation** — the write ladder's existing invalidation sites call
  ONE contract (:meth:`invalidate_extents` / :meth:`invalidate_paths`)
  that fans out over every registered tier;
* **pins** — the KV pool's block pins ride :meth:`pin`/:meth:`unpin`
  instead of reaching into the HBM tier directly.

Every lease any tier hands out is a :class:`TierLease`: one refcounted
type with one holder contract (``copy_into`` fail-open on stale or
corrupt, ``device_array`` when the bytes live on device, freed at the
last release).  The stromlint rule family ``tiers`` ratchets the rest of
the tree onto this surface: tier internals (``lookup``/``fill``/
``admit``/``drop``/``promote_hook``/``invalidate_*``) outside this
module and the two policy plugins are findings.

The module-global ``extent_space`` follows the one-branch-when-off
contract of the tiers it drives: ``configure()`` re-reads the capacity
Vars once, hot paths check the plain per-tier ``active`` attributes.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence, Tuple

from .stats import stats
from .trace import recorder as _trace
from .integrity import domain as _integrity

__all__ = ["TierLease", "ExtentSpace", "extent_space", "source_key"]


def source_key(source) -> tuple:
    """Stable identity for a source in the unified space: the tuple of
    its members' real paths (works for plain, segmented and striped
    sources, and the loopback fakes, which subclass them)."""
    # representation tags (e.g. a packed .cpk sidecar's
    # "#repr=cpk"/"#gen=..." pair) extend the identity so a re-encoded
    # file can never alias a stale cached extent; tags start with '#'
    # and thus never collide with real paths
    extra = tuple(getattr(source, "cache_key_extra", ()) or ())
    members = getattr(source, "members", None)
    if members:
        try:
            return tuple(os.path.realpath(m.path)
                         for m in members) + extra
        except AttributeError:
            pass
    path = getattr(source, "path", None)
    if isinstance(path, str):
        return (os.path.realpath(path),) + extra
    return ("<anon:%d>" % id(source),) + extra


class TierLease:
    """Refcounted pin on a resident extent, in ANY tier.

    Taken under the owning tier's lock by its ``lookup``; the holder
    copies out with :meth:`copy_into` and must :meth:`release` (eviction
    skips the entry, invalidation only marks it stale while the lease is
    live, stale entries are never served and free at the last release).

    The owning tier supplies three hooks: ``_lease_view(entry)`` — a
    host memoryview of the bytes (None when the backing is gone),
    ``_drop_corrupt(entry)`` — drop a rotted entry under its lease
    rules, and ``_release(entry)`` — refcount bookkeeping.
    """

    __slots__ = ("_owner", "_entry", "_released")

    def __init__(self, owner, entry) -> None:
        self._owner = owner
        self._entry = entry
        self._released = False

    @property
    def length(self) -> int:
        return self._entry.length

    @property
    def stale(self) -> bool:
        return self._entry.stale

    def device_array(self):
        """The extent as its device-resident uint8 array (no copy) when
        the owning tier keeps one, else None; None too when the entry
        was invalidated after the lookup."""
        e = self._entry
        return None if e.stale else getattr(e, "array", None)

    def copy_into(self, dest) -> bool:
        """Copy the extent into *dest* (a writable buffer no longer than
        the extent).  Returns False — and copies nothing — when the
        entry was invalidated after the lookup, or (integrity=always)
        when the resident bytes rotted; the caller re-reads through the
        fault ladder.  Fail-open: never EBADMSG from a cached copy."""
        e = self._entry
        if e.stale:
            return False
        view = self._owner._lease_view(e)
        if view is None:
            return False
        if _integrity.verify_reads and \
                not _integrity.verify(view[:e.length], e.crc):
            self._owner._drop_corrupt(e)
            return False
        n = len(dest)
        dest[:] = view[:n]
        return not e.stale

    def release(self) -> None:
        if not self._released:
            self._released = True
            self._owner._release(self._entry)


class ExtentSpace:
    """The one placement/migration engine over the registered tiers.

    Tiers self-register at import (module bottom of :mod:`.cache` and
    :mod:`.serving.hbm_tier`), keeping this module import-light — it
    never imports a tier at top level, the tiers import it for
    :class:`TierLease`.
    """

    #: lookup order, top of the hierarchy first
    _ORDER = ("hbm", "ram")

    def __init__(self) -> None:
        self._tiers: Dict[str, object] = {}

    # -- registry ------------------------------------------------------

    def register_tier(self, name: str, tier) -> None:
        self._tiers[name] = tier

    def tier(self, name: str):
        return self._tiers.get(name)

    def tier_active(self, name: str) -> bool:
        t = self._tiers.get(name)
        return bool(t is not None and t.active)

    def tier_capacity(self, name: str) -> int:
        t = self._tiers.get(name)
        return int(getattr(t, "_cap", 0)) if t is not None else 0

    # -- configuration -------------------------------------------------

    def configure(self) -> None:
        """(Re)configure every tier from the unified capacity Vars and
        rewire the inter-tier transitions.  The canonical knobs are
        ``tier_ram_bytes`` / ``tier_hbm_bytes`` / ``tier_kv_block_bytes``
        (the pre-unification names alias them, see MIGRATION.md)."""
        # deferred imports: the tier modules import this module for the
        # shared lease type, so the space pulls its plugins in lazily
        from .cache import residency_cache            # registers "ram"
        from .serving.hbm_tier import hbm_tier        # registers "hbm"
        residency_cache.configure()
        hbm_tier.configure()          # calls rewire() itself

    def clear_tiers(self) -> None:
        """Drop every resident extent in every tier (test reset)."""
        for t in self._tiers.values():
            t.clear()

    def rewire(self) -> None:
        """Re-arm the inter-tier transitions after any tier's
        ``configure()``: the RAM tier's second-touch hook points at
        :meth:`_promote_from_ram` only while the HBM tier is on — one
        branch when off."""
        ram = self._tiers.get("ram")
        hbm = self._tiers.get("hbm")
        if ram is None:
            return
        on = hbm is not None and hbm.active
        ram.promote_hook = self._promote_from_ram if on else None

    # -- identity ------------------------------------------------------

    source_key = staticmethod(source_key)

    # -- read side -----------------------------------------------------

    @property
    def lookup_active(self) -> bool:
        """Any tier can serve a hit (the engine's plan-time branch)."""
        return any(t.active for t in self._tiers.values())

    @property
    def fill_active(self) -> bool:
        """The RAM tier accepts demand-fault fills (the engine's
        wait-time branch)."""
        return self.tier_active("ram")

    def lookup(self, skey: tuple, base: int,
               length: int) -> Optional[Tuple[TierLease, str]]:
        """Top-down exact-extent lookup: returns ``(lease, tier_name)``
        from the highest tier holding the extent, or None on a full
        miss.  An HBM hit outranks a RAM hit — it costs one device→dest
        copy and no host-slab touch at all."""
        for name in self._ORDER:
            t = self._tiers.get(name)
            if t is None or not t.active:
                continue
            lease = t.lookup(skey, base, length)
            if lease is not None:
                return lease, name
        return None

    # -- placement / migration -----------------------------------------

    def fault_fill(self, skey: tuple, base: int, length: int, data, *,
                   logical_length: int = 0, source_ref=None,
                   speculative: bool = False) -> bool:
        """Demand-fault fill: healed bytes from the fault ladder enter
        the hierarchy at the RAM tier.  Speculative (readahead) fills
        ride the same path but are provenance-tagged by the tier and
        never counted as faults — and, since a still-speculative extent
        takes the first-touch path on its first demand hit, they can
        never promote either."""
        ram = self._tiers.get("ram")
        if ram is None:
            return False
        ok = ram.fill(skey, base, length, data,
                      logical_length=logical_length, source_ref=source_ref,
                      speculative=speculative)
        if ok and not speculative:
            stats.add("nr_tier_ram_fault")
            if _trace.active:
                _trace.instant("tier_fault", offset=base, length=length,
                               args={"tier": "ram"})
        return ok

    def _promote_from_ram(self, skey: tuple, base: int, length: int,
                          data, *, crc=None, source_ref=None) -> bool:
        """Second-touch promotion (the RAM tier's ARC t1→t2 transition,
        invoked outside its lock): admit the bytes into HBM, then —
        exclusive migration — surrender the RAM copy so the two tiers
        pool capacity instead of double-caching.  The surrendered key is
        ghosted, so a later demotion re-enters RAM as frequency."""
        hbm = self._tiers.get("hbm")
        if hbm is None or not hbm.admit(skey, base, length, data,
                                        crc=crc, source_ref=source_ref):
            return False
        stats.add("nr_tier_hbm_promote")
        if _trace.active:
            _trace.instant("tier_promote", offset=base, length=length,
                           args={"tier": "hbm"})
        ram = self._tiers.get("ram")
        if ram is not None:
            ram.yield_up(skey, base, length)
        return True

    def demote_from_hbm(self, demoted) -> None:
        """HBM eviction victims move DOWN: each ``(key, data,
        source_ref)`` re-enters the RAM tier (a failed fill just means a
        future SSD re-read — the fault ladder is the floor of the
        hierarchy)."""
        ram = self._tiers.get("ram")
        if ram is None:
            return
        for key, data, source_ref in demoted:
            if data is None:
                continue
            skey, base, length = key
            if ram.fill(skey, base, length, data, source_ref=source_ref):
                stats.add("nr_tier_hbm_demote")
                if _trace.active:
                    _trace.instant("tier_demote", offset=base,
                                   length=length, args={"tier": "hbm"})

    # -- pinned placement (the KV pool's block pins) -------------------

    def pin(self, skey: tuple, base: int, length: int, data, *,
            crc=None, source_ref=None) -> Optional[TierLease]:
        """Place an extent in HBM and pin it there: admit + lookup as
        one transition.  Returns the holding lease, or None when the
        tier is off, capacity is pinned solid, or a racing drop won.
        The pin IS a promotion — it counts in the tier scoreboard."""
        hbm = self._tiers.get("hbm")
        if hbm is None or not hbm.active:
            return None
        if not hbm.admit(skey, base, length, data,
                         crc=crc, source_ref=source_ref):
            return None
        lease = hbm.lookup(skey, base, length)
        if lease is None:  # racing invalidation/drop won
            hbm.drop(skey, base, length)
            return None
        stats.add("nr_tier_hbm_promote")
        if _trace.active:
            _trace.instant("tier_promote", offset=base, length=length,
                           args={"tier": "hbm"})
        return lease

    def unpin(self, lease: Optional[TierLease], skey: tuple, base: int,
              length: int) -> None:
        """Release a pin taken with :meth:`pin` and drop the extent
        WITHOUT demotion — the caller owns the bytes' next home (the KV
        pool's explicit HBM→RAM block demotion)."""
        if lease is not None:
            lease.release()
        hbm = self._tiers.get("hbm")
        if hbm is not None:
            hbm.drop(skey, base, length)

    # -- coherency (ONE invalidation contract) -------------------------

    def invalidate_extents(self, skey: tuple,
                           extents: Sequence[Tuple[int, int]]) -> int:
        """The write ladder's invalidation contract: drop every resident
        copy the write touches, in EVERY tier.  Same-key entries match
        by byte overlap; entries under a different key that shares a
        file drop wholesale (offsets do not map across framings).
        Returns the number dropped across the hierarchy."""
        n = 0
        for name in self._ORDER:
            t = self._tiers.get(name)
            if t is not None:
                n += t.invalidate_extents(skey, extents)
        return n

    def invalidate_paths(self, paths: Sequence[str]) -> int:
        """Drop every resident extent over any of *paths*, in every tier
        (the checkpoint savers' contract after an atomic rename)."""
        n = 0
        for name in self._ORDER:
            t = self._tiers.get(name)
            if t is not None:
                n += t.invalidate_paths(paths)
        return n

    # -- integrity scrub -----------------------------------------------

    def scrub_tiers(self):
        """``(name, tier)`` pairs the background scrubber walks, bottom
        up (RAM rot is likelier than HBM rot, so RAM goes first in the
        round-robin)."""
        out = []
        for name in reversed(self._ORDER):
            t = self._tiers.get(name)
            if t is not None and t.active:
                out.append((name, t))
        return out

    # -- the ONE residency surface -------------------------------------

    def residency(self) -> Dict[str, int]:
        """Resident bytes per tier (the scoreboard's gauges)."""
        return {name: t.resident_bytes()
                for name, t in self._tiers.items()}

    def resident_fraction(self, paths: Sequence[str],
                          total_bytes: int) -> Dict[str, float]:
        """Fraction of a table's bytes resident, per tier — the surface
        the planner and EXPLAIN consume (expected hit ratio per tier
        for a scan over *paths*)."""
        return {name: t.resident_fraction(paths, total_bytes)
                for name, t in self._tiers.items()}


#: process-wide space; tiers self-register at import, the engine calls
#: ``configure()`` at Session construction, tests rewire via the tier
#: ``configure()`` methods (each ends in ``extent_space.rewire()``)
extent_space = ExtentSpace()
