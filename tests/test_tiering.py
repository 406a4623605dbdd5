"""Unified extent-space tests (ISSUE 20, `tiering` marker).

One placement/migration engine over HBM → pinned RAM → SSD: second-touch
promotion exclusive-migrates (the RAM copy is yielded up so the tiers
pool capacity), demand faults fill through the fault ladder — including
a quarantined member's mirror twin — demotion preserves the resident
checksum and every lease fails open, the write ladder's invalidation
contract fans out across every tier, and speculative (readahead) fills
can never promote.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from nvme_strom_tpu.cache import residency_cache
from nvme_strom_tpu.config import config
from nvme_strom_tpu.engine import Session, open_source, reorder_chunks
from nvme_strom_tpu.integrity import domain
from nvme_strom_tpu.serving.hbm_tier import hbm_tier
from nvme_strom_tpu.stats import stats
from nvme_strom_tpu.testing import (FakeNvmeSource, FakeStripedNvmeSource,
                                    FaultPlan, make_test_file)
from nvme_strom_tpu.testing.chaos import (expected_mirrored_stream,
                                          make_mirrored_members)
from nvme_strom_tpu.testing.fake import expected_bytes
from nvme_strom_tpu.tiering import extent_space

pytestmark = pytest.mark.tiering

EXT = 64 << 10


def _counters():
    return dict(stats.snapshot(reset_max=False).counters)


def _space_on(ram_exts=4, hbm_exts=4):
    config.set("tier_ram_bytes", ram_exts * EXT)
    config.set("tier_hbm_bytes", hbm_exts * EXT)
    extent_space.configure()


def _read_chunks(sess, src, order, chunk=EXT):
    total = len(order) * chunk
    handle, buf = sess.alloc_dma_buffer(total)
    try:
        res = sess.memcpy_ssd2ram(src, handle, list(order), chunk)
        sess.memcpy_wait(res.dma_task_id, timeout=60.0)
        host = reorder_chunks(np.frombuffer(buf.view()[:total], np.uint8),
                              chunk, res.chunk_ids, sorted(order))
        return bytes(host)
    finally:
        sess.unmap_buffer(handle)


# -- second-touch promotion ---------------------------------------------------

def test_second_touch_promotion_exclusive_migrates():
    _space_on()
    skey, data = ("#tp1",), bytes([7]) * EXT
    before = _counters()
    assert extent_space.fault_fill(skey, 0, EXT, data)
    hit = extent_space.lookup(skey, 0, EXT)        # second touch
    assert hit is not None
    lease, tier = hit
    assert tier == "ram"
    out = bytearray(EXT)
    assert lease.copy_into(out) and bytes(out) == data
    lease.release()
    after = _counters()
    assert after["nr_tier_hbm_promote"] - before["nr_tier_hbm_promote"] == 1
    # exclusive migration: the promoted extent now lives in HBM and the
    # RAM copy was surrendered — the tiers pool capacity, no double-cache
    hit = extent_space.lookup(skey, 0, EXT)
    assert hit is not None
    lease, tier = hit
    assert tier == "hbm"
    assert lease.device_array() is not None
    out = bytearray(EXT)
    assert lease.copy_into(out) and bytes(out) == data
    lease.release()
    assert not residency_cache.peek(skey, 0, EXT)
    assert extent_space.residency()["ram"] == 0
    assert extent_space.residency()["hbm"] == EXT


def test_migration_churn_stays_byte_identical(tmp_path):
    """Both tiers far below the working set: seeded-shuffle passes keep
    promotion, demotion and demand faults all running, and every pass
    reassembles the file's bytes exactly."""
    import random

    nchunks = 13
    path = str(tmp_path / "churn.bin")
    make_test_file(path, nchunks * EXT)
    config.set("cache_arbitration", False)
    config.set("dma_max_size", EXT)        # one tier decision per chunk
    _space_on(ram_exts=4, hbm_exts=4)
    rng = random.Random(23)
    src = FakeNvmeSource(path, force_cached_fraction=0.0)
    before = _counters()
    try:
        with Session() as sess:
            for _ in range(4):
                order = list(range(nchunks))
                rng.shuffle(order)
                assert _read_chunks(sess, src, order) == \
                    expected_bytes(0, nchunks * EXT)
    finally:
        src.close()
        extent_space.clear_tiers()
    after = _counters()

    def moved(k):
        return after.get(k, 0) - before.get(k, 0)

    assert moved("nr_tier_hbm_promote") > 0
    assert moved("nr_tier_hbm_demote") + moved("nr_tier_ram_demote") > 0
    assert moved("nr_tier_ram_fault") > 0


# -- demand faults through the fault ladder -----------------------------------

def test_demand_fault_fills_through_quarantined_members_mirror(tmp_path):
    """Member 0 is dead from the first request: every demand fault on
    its stripes heals through the mirror twin and still fills the RAM
    tier — the second pass is served resident, byte-identical."""
    _space_on(ram_exts=64, hbm_exts=0)
    paths = make_mirrored_members(str(tmp_path), tag="tq")
    src = FakeStripedNvmeSource(
        paths, 64 << 10,
        fault_plan=FaultPlan(failstop_member=0, failstop_after=0),
        force_cached_fraction=0.0, mirror="paired")
    want = expected_mirrored_stream(paths)
    nchunks = src.size // EXT
    before = _counters()
    try:
        with Session() as sess:
            got = _read_chunks(sess, src, range(nchunks))
            assert got == want[:nchunks * EXT]
            mid = _counters()
            faults = mid["nr_tier_ram_fault"] - before["nr_tier_ram_fault"]
            assert faults == nchunks
            got = _read_chunks(sess, src, range(nchunks))
            assert got == want[:nchunks * EXT]
            after = _counters()
            # rescan: all resident, no new faults
            assert after["nr_tier_ram_fault"] == mid["nr_tier_ram_fault"]
            assert after["nr_cache_hit"] - mid["nr_cache_hit"] == nchunks
    finally:
        src.close()


# -- demotion ----------------------------------------------------------------

def test_demotion_preserves_crc_and_lease_fails_open():
    config.set("integrity", "always")
    domain.configure()
    _space_on(ram_exts=8, hbm_exts=2)
    skey = ("#td1",)
    blobs = {i: bytes([i + 1]) * EXT for i in range(3)}
    before = _counters()
    for i in range(3):
        assert extent_space.fault_fill(skey, i * EXT, EXT, blobs[i])
        lease, tier = extent_space.lookup(skey, i * EXT, EXT)  # promote
        lease.release()
    # three promotions through a 2-extent HBM cap: at least one victim
    # was demoted back DOWN into the RAM tier, carrying its checksum
    after = _counters()
    assert after["nr_tier_hbm_promote"] - before["nr_tier_hbm_promote"] == 3
    assert after["nr_tier_hbm_demote"] - before["nr_tier_hbm_demote"] >= 1
    demoted = [i for i in range(3) if residency_cache.peek(skey, i * EXT, EXT)]
    assert demoted, "no HBM victim re-entered the RAM tier"
    for i in demoted:
        lease = residency_cache.lookup(skey, i * EXT, EXT)
        e = lease._entry
        assert e.crc is not None and domain.verify(blobs[i], e.crc), \
            "demotion dropped or corrupted the resident checksum"
        out = bytearray(EXT)
        assert lease.copy_into(out) and bytes(out) == blobs[i]
        lease.release()
    # fail-open: a lease taken before invalidation reads False, never
    # stale bytes and never an exception
    i = demoted[0]
    lease = residency_cache.lookup(skey, i * EXT, EXT)
    assert extent_space.invalidate_extents(skey, [(i * EXT, EXT)]) >= 1
    assert lease.stale
    out = bytearray(EXT)
    assert lease.copy_into(out) is False
    lease.release()


# -- one invalidation contract ------------------------------------------------

def test_write_ladder_invalidates_across_tiers(tmp_path):
    """A memcpy_ram2ssd write drops every overlapping resident extent in
    EVERY tier through the one invalidation contract; the next read
    faults fresh bytes, never a stale copy (RAM or HBM)."""
    _space_on(ram_exts=8, hbm_exts=8)
    config.set("cache_arbitration", False)   # page-cache-warm file
    config.set("dma_max_size", EXT)          # one extent per chunk
    path = str(tmp_path / "wl.bin")
    nchunks = 4
    make_test_file(path, nchunks * EXT)
    new0 = bytes(range(256))[::-1] * (EXT // 256)
    with Session() as sess:
        with open_source(path) as src:
            skey = extent_space.source_key(src)
            got = _read_chunks(sess, src, range(nchunks))   # fill RAM
            assert got == expected_bytes(0, nchunks * EXT)
            lease, _ = extent_space.lookup(skey, 0, EXT)    # promote 0
            lease.release()
        hit = extent_space.lookup(skey, 0, EXT)
        assert hit is not None and hit[1] == "hbm"
        hit[0].release()
        assert residency_cache.peek(skey, EXT, EXT)
        handle, buf = sess.alloc_dma_buffer(2 * EXT)
        try:
            buf.view()[:EXT] = new0
            buf.view()[EXT:2 * EXT] = new0
            with open_source(path, writable=True) as sink:
                res = sess.memcpy_ram2ssd(sink, handle, [0, 1], EXT)
                sess.memcpy_wait(res.dma_task_id)
                sink.sync()
        finally:
            sess.unmap_buffer(handle)
        # chunk 0 (HBM) and chunk 1 (RAM) both dropped by the write
        assert extent_space.lookup(skey, 0, EXT) is None
        assert extent_space.lookup(skey, EXT, EXT) is None
        with open_source(path) as src:
            got = _read_chunks(sess, src, range(nchunks))
        assert got[:2 * EXT] == new0 + new0, \
            "write-invalidated extent served stale"
        assert got[2 * EXT:] == expected_bytes(2 * EXT, 2 * EXT)


# -- speculative fills --------------------------------------------------------

def test_speculative_fills_never_promote_or_count_as_faults():
    _space_on()
    skey, data = ("#ts1",), bytes([5]) * EXT
    before = _counters()
    assert extent_space.fault_fill(skey, 0, EXT, data, speculative=True)
    after = _counters()
    # a prefetch is not a demand fault...
    assert after.get("nr_tier_ram_fault", 0) == \
        before.get("nr_tier_ram_fault", 0)
    # ...and its first demand touch is a FIRST touch (the provenance tag
    # clears, the extent stays in recency): no promotion either
    lease, tier = extent_space.lookup(skey, 0, EXT)
    assert tier == "ram"
    lease.release()
    mid = _counters()
    assert mid.get("nr_tier_hbm_promote", 0) == \
        before.get("nr_tier_hbm_promote", 0)
    assert extent_space.residency()["hbm"] == 0
    # the SECOND demand touch is real frequency: now it promotes
    lease, tier = extent_space.lookup(skey, 0, EXT)
    lease.release()
    end = _counters()
    assert end["nr_tier_hbm_promote"] - mid.get("nr_tier_hbm_promote", 0) == 1


def test_kv_block_bytes_alias_resolves():
    """The pre-unification KV knob aliases the canonical tier Var in
    both directions (MIGRATION.md contract)."""
    config.set("kv_block_bytes", 32 << 10)
    assert config.get("tier_kv_block_bytes") == 32 << 10
    config.set("tier_kv_block_bytes", 128 << 10)
    assert config.get("kv_block_bytes") == 128 << 10
