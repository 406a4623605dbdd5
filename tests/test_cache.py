"""Cross-query residency-cache tests (ISSUE 9, `cache` marker).

Covers the tentpole contracts hardware-free: hit/miss split correctness
through the engine, byte identity under eviction churn, ARC scan
resistance, lease pinning vs concurrent eviction, invalidation on the
write-back path, degraded-mode fills through a quarantined member's
mirror, and the cache-off no-op.
"""

import os

import pytest

from nvme_strom_tpu import Session, config, stats
from nvme_strom_tpu.cache import ResidencyCache, residency_cache
from nvme_strom_tpu.engine import open_source
from nvme_strom_tpu.testing import FakeNvmeSource, FaultPlan, make_test_file
from nvme_strom_tpu.testing.fake import FakeStripedNvmeSource, expected_bytes

pytestmark = pytest.mark.cache

CHUNK = 64 << 10


def _enable(nbytes=16 << 20):
    config.set("cache_bytes", nbytes)
    config.set("cache_arbitration", False)  # measure the direct path
    config.set("dma_max_size", CHUNK)
    residency_cache.configure()


def _delta(before, after, name):
    return after.counters.get(name, 0) - before.counters.get(name, 0)


# ---------------------------------------------------------------------------
# engine-level hit/miss split
# ---------------------------------------------------------------------------

def test_hit_miss_split_and_identity(tmp_data_file):
    """Pass 1 misses and fills; pass 2 is served entirely from slabs:
    zero chunks submitted, byte-identical, counters agree."""
    _enable()
    src = FakeNvmeSource(tmp_data_file, force_cached_fraction=0.0)
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            handle, buf = sess.alloc_dma_buffer(8 * CHUNK)
            res1 = sess.memcpy_ssd2ram(src, handle, list(range(8)), CHUNK)
            sess.memcpy_wait(res1.dma_task_id)
            got1 = bytes(buf.view()[:8 * CHUNK])
            mid = stats.snapshot(reset_max=False)
            res2 = sess.memcpy_ssd2ram(src, handle, list(range(8)), CHUNK)
            sess.memcpy_wait(res2.dma_task_id)
            got2 = bytes(buf.view()[:8 * CHUNK])
    finally:
        src.close()
    after = stats.snapshot(reset_max=False)
    assert got1 == expected_bytes(0, 8 * CHUNK)
    assert got2 == expected_bytes(0, 8 * CHUNK)
    assert res1.nr_ssd2dev == 8 and _delta(before, mid, "nr_cache_miss") == 8
    assert _delta(before, mid, "nr_cache_fill") == 8
    # the hot pass submits nothing: hits are RAM-tier tail slots
    assert res2.nr_ssd2dev == 0 and res2.nr_ram2dev == 8
    assert _delta(mid, after, "nr_cache_hit") == 8
    assert _delta(mid, after, "nr_cache_miss") == 0
    assert _delta(mid, after, "total_dma_length") == 0, \
        "fully-resident task still moved DMA bytes"
    assert _delta(mid, after, "bytes_cache_hit") == 8 * CHUNK


def test_partial_hit_reorder(tmp_data_file):
    """A mixed task tail-packs hits after the submitted chunks and the
    reordered ids reconstruct the stream exactly."""
    import numpy as np

    from nvme_strom_tpu.engine import reorder_chunks
    _enable()
    src = FakeNvmeSource(tmp_data_file, force_cached_fraction=0.0)
    try:
        with Session() as sess:
            handle, buf = sess.alloc_dma_buffer(8 * CHUNK)
            # warm only the even chunks
            res = sess.memcpy_ssd2ram(src, handle, [0, 2, 4, 6], CHUNK)
            sess.memcpy_wait(res.dma_task_id)
            want = list(range(8))
            res = sess.memcpy_ssd2ram(src, handle, want, CHUNK)
            sess.memcpy_wait(res.dma_task_id)
            assert res.nr_ssd2dev == 4  # odd chunks submitted
            assert sorted(res.chunk_ids[res.nr_ssd2dev:]) == [0, 2, 4, 6]
            host = reorder_chunks(
                np.frombuffer(buf.view()[:8 * CHUNK], np.uint8),
                CHUNK, res.chunk_ids, want)
            assert bytes(host) == expected_bytes(0, 8 * CHUNK)
    finally:
        src.close()


def test_eviction_churn_stays_byte_identical(tmp_path):
    """Capacity a quarter of the table: every pass churns the ARC lists,
    the reordered stream (hits are tail-packed) stays identical and
    residency never exceeds capacity."""
    import numpy as np

    from nvme_strom_tpu.engine import reorder_chunks
    nchunks, cap = 16, 4 * CHUNK
    path = str(tmp_path / "evict.bin")
    make_test_file(path, nchunks * CHUNK)
    _enable(cap)
    src = FakeNvmeSource(path, force_cached_fraction=0.0)
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            handle, buf = sess.alloc_dma_buffer(nchunks * CHUNK)
            want = list(range(nchunks))
            for _ in range(3):
                res = sess.memcpy_ssd2ram(src, handle, want, CHUNK)
                sess.memcpy_wait(res.dma_task_id)
                host = reorder_chunks(
                    np.frombuffer(buf.view()[:nchunks * CHUNK], np.uint8),
                    CHUNK, res.chunk_ids, want)
                assert bytes(host) == expected_bytes(0, nchunks * CHUNK)
    finally:
        src.close()
    after = stats.snapshot(reset_max=False)
    assert _delta(before, after, "nr_cache_evict") > 0
    assert residency_cache.resident_bytes() <= cap


# ---------------------------------------------------------------------------
# ARC policy (unit-level)
# ---------------------------------------------------------------------------

def _mk_cache(nbytes):
    config.set("cache_bytes", nbytes)
    c = ResidencyCache()
    c.configure()
    return c


def test_arc_scan_resistance():
    """One streaming pass must not flush the promoted hot set: hot keys
    live in t2 and the adaptive target starts recency-first."""
    L = 4096
    c = _mk_cache(8 * L)
    skey = ("/hot",)
    for i in range(4):
        assert c.fill(skey, i * L, L, bytes([i]) * L)
    for i in range(4):  # second touch promotes to t2
        lease = c.lookup(skey, i * L, L)
        assert lease is not None
        lease.release()
    scan = ("/scan",)
    for i in range(100):  # one-touch stream 50x the capacity
        c.fill(scan, i * L, L, b"s" * L)
    hot = 0
    for i in range(4):
        lease = c.lookup(skey, i * L, L)
        if lease is not None:
            out = bytearray(L)
            assert lease.copy_into(out) and out == bytes([i]) * L
            lease.release()
            hot += 1
    assert hot == 4, f"stream evicted {4 - hot} hot extents"


def test_lease_pins_against_eviction():
    """Pinned slabs are never evicted (fill skips instead), and the
    pinned bytes stay intact; release makes them evictable again."""
    L = 4096
    c = _mk_cache(3 * L)
    skey = ("/pin",)
    for i in range(3):
        assert c.fill(skey, i * L, L, bytes([i]) * L)
    leases = [c.lookup(skey, i * L, L) for i in range(3)]
    assert all(leases)
    # every resident byte is pinned: the fill must be refused, not
    # evict under a reader
    assert not c.fill(skey, 99 * L, L, b"x" * L)
    for i, lease in enumerate(leases):
        out = bytearray(L)
        assert lease.copy_into(out) and out == bytes([i]) * L
        lease.release()
    assert c.fill(skey, 99 * L, L, b"x" * L)  # now evictable


def test_invalidate_marks_pinned_stale():
    """Invalidation during a lease: the lease refuses to serve, the slab
    is freed at release, and the extent re-fills cleanly."""
    L = 4096
    c = _mk_cache(4 * L)
    skey = ("/stale",)
    assert c.fill(skey, 0, L, b"a" * L)
    lease = c.lookup(skey, 0, L)
    assert c.invalidate_extents(skey, [(0, L)]) == 1
    assert not lease.copy_into(bytearray(L)), "stale slab served"
    lease.release()
    assert c.lookup(skey, 0, L) is None
    assert c.fill(skey, 0, L, b"b" * L)
    lease = c.lookup(skey, 0, L)
    out = bytearray(L)
    assert lease.copy_into(out) and out == b"b" * L
    lease.release()


# ---------------------------------------------------------------------------
# write-back coherency through the engine
# ---------------------------------------------------------------------------

def test_invalidation_on_write_back(tmp_data_file):
    """A memcpy_ram2ssd over a cached extent drops it: the next read
    returns the new bytes, never the stale slab."""
    _enable()
    before = stats.snapshot(reset_max=False)
    with Session() as sess:
        handle, buf = sess.alloc_dma_buffer(4 * CHUNK)
        with open_source(tmp_data_file) as src:
            res = sess.memcpy_ssd2ram(src, handle, list(range(4)), CHUNK)
            sess.memcpy_wait(res.dma_task_id)
        new0 = bytes(range(256)) * (CHUNK // 256)
        buf.view()[:CHUNK] = new0
        with open_source(tmp_data_file, writable=True) as sink:
            res = sess.memcpy_ram2ssd(sink, handle, [0], CHUNK)
            sess.memcpy_wait(res.dma_task_id)
            sink.sync()
        with open_source(tmp_data_file) as src:
            res = sess.memcpy_ssd2ram(src, handle, list(range(4)), CHUNK)
            sess.memcpy_wait(res.dma_task_id)
            got = bytes(buf.view()[:4 * CHUNK])
    after = stats.snapshot(reset_max=False)
    assert got[:CHUNK] == new0, "stale cached extent served after write"
    assert got[CHUNK:] == expected_bytes(CHUNK, 3 * CHUNK)
    assert _delta(before, after, "nr_cache_invalidate") > 0


# ---------------------------------------------------------------------------
# degraded-mode fills
# ---------------------------------------------------------------------------

def test_degraded_fill_through_mirror(tmp_path):
    """A fail-stopped member's extents are healed via its mirror — and
    those healed bytes still populate the tier, so the rescan hits."""
    from nvme_strom_tpu.testing.chaos import (expected_mirrored_stream,
                                              make_mirrored_members,
                                              read_all)
    stripe = 64 << 10
    paths = make_mirrored_members(str(tmp_path), n_pairs=2, size=512 << 10,
                                  tag="cm")
    _enable()
    want = expected_mirrored_stream(paths, stripe)

    plan = FaultPlan(failstop_member=0, failstop_after=0)
    src = FakeStripedNvmeSource(paths, stripe_chunk_size=stripe,
                                fault_plan=plan,
                                force_cached_fraction=0.0, mirror="paired")
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            got1, total = read_all(sess, src, chunk=stripe)
            mid = stats.snapshot(reset_max=False)
            got2, _ = read_all(sess, src, chunk=stripe)
    finally:
        src.close()
    after = stats.snapshot(reset_max=False)
    assert got1 == want[:total] and got2 == want[:total]
    assert _delta(before, mid, "nr_cache_fill") > 0, \
        "degraded task populated nothing"
    assert _delta(mid, after, "nr_cache_hit") == total // stripe
    assert _delta(mid, after, "nr_cache_miss") == 0


# ---------------------------------------------------------------------------
# disabled = no-op
# ---------------------------------------------------------------------------

def test_cache_disabled_is_noop(tmp_data_file):
    """cache_bytes=0 (the default): no counters move, nothing resident,
    result geometry is the classic arbitration shape."""
    assert int(config.get("cache_bytes")) == 0
    config.set("cache_arbitration", False)
    src = FakeNvmeSource(tmp_data_file, force_cached_fraction=0.0)
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            handle, buf = sess.alloc_dma_buffer(8 * CHUNK)
            res = sess.memcpy_ssd2ram(src, handle, list(range(8)), CHUNK)
            sess.memcpy_wait(res.dma_task_id)
            got = bytes(buf.view()[:8 * CHUNK])
    finally:
        src.close()
    after = stats.snapshot(reset_max=False)
    assert got == expected_bytes(0, 8 * CHUNK)
    assert res.nr_ssd2dev == 8
    for k in ("nr_cache_hit", "nr_cache_miss", "nr_cache_fill",
              "nr_cache_evict", "nr_cache_invalidate"):
        assert _delta(before, after, k) == 0, k
    assert residency_cache.resident_bytes() == 0
