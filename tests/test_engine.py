"""Engine contract tests: eligibility, sources, planner merging, memcpy
semantics (chunk reordering, conservation invariant), async error retention,
buffer registry.  The reference has none of these (SURVEY.md SS4) — these
encode its runtime oracles as a real test suite."""

import errno
import os
import time

import numpy as np
import pytest

from nvme_strom_tpu import (DmaTaskState, FsKind, Session, StromError,
                            check_file, config, open_source, stats)
from nvme_strom_tpu.engine import (PlainSource, Request, SegmentedSource,
                                   StripedSource, plan_requests)
from nvme_strom_tpu.testing import FakeNvmeSource, FaultPlan, make_test_file
from nvme_strom_tpu.testing.fake import expected_bytes

CHUNK = 64 << 10  # 64KB test chunk


# ---------------------------------------------------------------------------
# check_file
# ---------------------------------------------------------------------------

def test_check_file_supported(tmp_data_file):
    info = check_file(tmp_data_file)
    assert info.supported
    assert info.file_size == 4 << 20
    assert info.fs_kind in (FsKind.EXT4, FsKind.XFS, FsKind.OTHER_DIRECT)
    assert info.dma_max_size >= 4 << 10
    # dma64 is probed from the real device chain now, not hardcoded;
    # on a non-NVMe CI host it is honestly False
    assert isinstance(info.support_dma64, bool)
    assert info.backing_kind  # classifier always renders a verdict


def test_check_file_rejects_tiny_file(tmp_path):
    # files under one page are excluded (inline-data risk,
    # kmod/nvme_strom.c:503-518)
    p = tmp_path / "tiny.bin"
    p.write_bytes(b"x" * 100)
    info = check_file(str(p))
    assert not info.supported


def test_check_file_missing():
    with pytest.raises(FileNotFoundError):
        check_file("/does/not/exist")


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------

def test_plan_merges_contiguous_chunks(tmp_data_file):
    with PlainSource(tmp_data_file) as src:
        # 8 contiguous 64KB chunks -> 2 x 256KB requests at a 256KB cap
        reqs = plan_requests(src, [(i, i) for i in range(8)], CHUNK, 0,
                             dma_max_size=256 << 10)
        assert [r.length for r in reqs] == [256 << 10, 256 << 10]
        assert reqs[0].file_off == 0 and reqs[1].file_off == 256 << 10


def test_plan_respects_dma_max(tmp_data_file):
    with PlainSource(tmp_data_file) as src:
        reqs = plan_requests(src, [(i, i) for i in range(8)], CHUNK, 0,
                             dma_max_size=128 << 10)
        assert all(r.length <= 128 << 10 for r in reqs)
        assert sum(r.length for r in reqs) == 8 * CHUNK


def test_plan_noncontiguous_chunks_not_merged(tmp_data_file):
    with PlainSource(tmp_data_file) as src:
        reqs = plan_requests(src, [(0, 0), (2, 1), (4, 2)], CHUNK, 0)
        assert len(reqs) == 3


def test_plan_dest_discontiguity_blocks_merge(tmp_data_file):
    with PlainSource(tmp_data_file) as src:
        # file-contiguous but dest slots reversed -> no merge
        reqs = plan_requests(src, [(0, 1), (1, 0)], CHUNK, 0)
        assert len(reqs) == 2


def test_plan_dest_segment_boundary_split(tmp_data_file):
    with PlainSource(tmp_data_file) as src:
        # 128KB dest segments: 4 contiguous 64KB chunks must split into 2+2
        reqs = plan_requests(src, [(i, i) for i in range(4)], CHUNK, 0,
                             dest_segment_shift=17)
        assert [r.length for r in reqs] == [128 << 10, 128 << 10]


def test_plan_misaligned_tail_goes_buffered(tmp_path):
    p = str(tmp_path / "odd.bin")
    make_test_file(p, (1 << 20) + 1000)  # non-block tail
    with PlainSource(p) as src:
        n_chunks = ((1 << 20) + 1000 + CHUNK - 1) // CHUNK
        reqs = plan_requests(src, [(i, i) for i in range(n_chunks)], CHUNK, 0)
        assert reqs[-1].buffered
        assert sum(r.length for r in reqs) == (1 << 20) + 1000


def test_plan_rejects_chunk_beyond_eof(tmp_data_file):
    with PlainSource(tmp_data_file) as src:
        with pytest.raises(StromError):
            plan_requests(src, [(10_000, 0)], CHUNK, 0)


# ---------------------------------------------------------------------------
# memcpy_ssd2ram end-to-end
# ---------------------------------------------------------------------------

def _run_copy(source, chunk_ids, chunk_size=CHUNK, **kw):
    with Session() as sess:
        handle, buf = sess.alloc_dma_buffer(len(chunk_ids) * chunk_size)
        res = sess.memcpy_ssd2ram(source, handle, chunk_ids, chunk_size, **kw)
        sess.memcpy_wait(res.dma_task_id)
        data = bytes(buf.view()[:len(chunk_ids) * chunk_size])
        sess.stat_info()  # fold native counters into the global registry
        return res, data


def test_sequential_copy_correct(tmp_data_file):
    with PlainSource(tmp_data_file) as src:
        ids = list(range(8))
        res, data = _run_copy(src, ids)
        assert res.nr_chunks == 8
        assert res.nr_ssd2dev + res.nr_ram2dev == 8
        assert sorted(res.chunk_ids) == ids
        # verify each chunk landed at its reordered slot
        for slot, cid in enumerate(res.chunk_ids):
            want = expected_bytes(cid * CHUNK, CHUNK)
            got = data[slot * CHUNK:(slot + 1) * CHUNK]
            assert got == want, f"chunk {cid} at slot {slot} corrupt"


def test_random_chunk_order(tmp_data_file):
    with PlainSource(tmp_data_file) as src:
        ids = [5, 0, 3, 7, 1]
        res, data = _run_copy(src, ids)
        for slot, cid in enumerate(res.chunk_ids):
            assert data[slot * CHUNK:(slot + 1) * CHUNK] == expected_bytes(cid * CHUNK, CHUNK)


def test_cache_arbitration_writeback(tmp_data_file):
    # force the arbiter to see every chunk as fully cached
    src = FakeNvmeSource(tmp_data_file, force_cached_fraction=1.0)
    try:
        res, data = _run_copy(src, [0, 1, 2, 3])
        assert res.nr_ram2dev == 4 and res.nr_ssd2dev == 0
        for slot, cid in enumerate(res.chunk_ids):
            assert data[slot * CHUNK:(slot + 1) * CHUNK] == expected_bytes(cid * CHUNK, CHUNK)
    finally:
        src.close()


def test_hot_hint_forces_writeback(tmp_data_file):
    """One hot page is decisive (reference scores PageDirty at
    threshold+1, kmod/nvme_strom.c:1643): a chunk overlapping a hot hint
    takes the write-back path even when nothing is page-cached."""
    src = FakeNvmeSource(tmp_data_file, force_cached_fraction=0.0)
    try:
        # hint one page inside chunk 2 only
        src.hint_hot_range(2 * CHUNK + 4096, 4096)
        res, data = _run_copy(src, [0, 1, 2, 3])
        assert res.nr_ram2dev == 1 and res.nr_ssd2dev == 3
        # write-back chunks ride the tail of chunk_ids (reference contract)
        assert res.chunk_ids[-1] == 2
        for slot, cid in enumerate(res.chunk_ids):
            assert data[slot * CHUNK:(slot + 1) * CHUNK] == \
                expected_bytes(cid * CHUNK, CHUNK)
        # clearing the hints restores the direct path
        src.clear_hot_hints()
        res2, _ = _run_copy(src, [0, 1, 2, 3])
        assert res2.nr_ram2dev == 0 and res2.nr_ssd2dev == 4
    finally:
        src.close()


def test_hot_fraction_overlap_math(tmp_data_file):
    # force_cached_fraction pins arbitration to hints-only (no ambient
    # dirtiness of the freshly written test file)
    src = FakeNvmeSource(tmp_data_file, force_cached_fraction=0.0)
    try:
        assert src.hot_fraction(0, CHUNK) == 0.0
        src.hint_hot_range(0, CHUNK // 2)
        assert src.hot_fraction(0, CHUNK) == pytest.approx(0.5)
        assert src.hot_fraction(CHUNK, CHUNK) == 0.0
        src.hint_hot_range(CHUNK // 2, CHUNK // 2)
        assert src.hot_fraction(0, CHUNK) == pytest.approx(1.0)
        src.clear_hot_hints()
        assert src.hot_fraction(0, CHUNK) == 0.0
    finally:
        src.close()


@pytest.mark.skipif(not os.access("/proc/kpageflags", os.R_OK),
                    reason="kpageflags not readable here")
def test_dirty_pages_detected_via_kpageflags(tmp_path):
    """Freshly buffered-written (un-fsynced) pages read back as dirty
    through pagemap->kpageflags, feeding hot_fraction without any hint."""
    from nvme_strom_tpu.engine import PlainSource
    path = str(tmp_path / "d.bin")
    with open(path, "wb") as f:
        f.write(b"\0" * (1 << 20))
        f.flush()
        os.fsync(f.fileno())
    with PlainSource(path) as src:
        clean = src.hot_fraction(0, 1 << 20)
        # dirty the first 64KB with a buffered write, no fsync
        fd = os.open(path, os.O_WRONLY)
        os.pwrite(fd, b"x" * (64 << 10), 0)
        os.close(fd)
        dirty = src.hot_fraction(0, 1 << 20)
    assert dirty > clean, (clean, dirty)
    assert dirty > 0.0


@pytest.mark.parametrize("limit, want_reads", [(8 << 20, 3), (0, 9)])
def test_writeback_reads_coalesce_by_run(tmp_data_file, limit, want_reads):
    """Write-back copies of file-consecutive chunks are one read per run,
    bounded by coalesce_limit: one read per 8 KiB page overran the task
    deadline of a cached 4 GiB sharded load on the chip (PR 21)."""
    config.set("coalesce_limit", limit)
    page = 8 << 10
    ids = [4, 5, 6, 7, 20, 9, 10, 11, 12]
    src = FakeNvmeSource(tmp_data_file, force_cached_fraction=1.0)
    reads = []
    plain = src.read_buffered
    src.read_buffered = lambda off, dest: (reads.append(len(dest)),
                                           plain(off, dest))
    try:
        with Session() as sess:
            handle, buf = sess.alloc_dma_buffer(len(ids) * page)
            res = sess.memcpy_ssd2ram(src, handle, ids, page)
            sess.memcpy_wait(res.dma_task_id)
            data = bytes(buf.view()[:len(ids) * page])
            sess.unmap_buffer(handle)
    finally:
        src.close()
    assert res.nr_ram2dev == len(ids) and res.chunk_ids == ids
    assert len(reads) == want_reads and sum(reads) == len(ids) * page
    for slot, cid in enumerate(ids):
        assert data[slot * page:(slot + 1) * page] == \
            expected_bytes(cid * page, page)


def test_cache_arbitration_off(tmp_data_file):
    config.set("cache_arbitration", False)
    src = FakeNvmeSource(tmp_data_file, force_cached_fraction=1.0)
    try:
        res, _ = _run_copy(src, [0, 1])
        assert res.nr_ssd2dev == 2
    finally:
        src.close()


def test_writeback_to_separate_wb_buffer(tmp_data_file):
    """SSD2GPU contract: wb chunks land in the caller's wb_buffer, tail-packed
    (kmod/nvme_strom.h:99-101)."""
    src = FakeNvmeSource(tmp_data_file, force_cached_fraction=1.0)
    wb = bytearray(4 * CHUNK)
    try:
        with Session() as sess:
            handle, buf = sess.alloc_dma_buffer(4 * CHUNK)
            res = sess.memcpy_ssd2ram(src, handle, [0, 1, 2, 3], CHUNK,
                                      wb_buffer=memoryview(wb))
            sess.memcpy_wait(res.dma_task_id)
            assert res.nr_ram2dev == 4
            for slot, cid in enumerate(res.chunk_ids):
                assert wb[slot * CHUNK:(slot + 1) * CHUNK] == \
                    expected_bytes(cid * CHUNK, CHUNK)
    finally:
        src.close()


def test_striped_source_copy(tmp_path):
    paths = [str(tmp_path / f"m{i}.bin") for i in range(4)]
    stripe_chunk = 64 << 10
    # build members so that striped-logical content is deterministic:
    # write the *logical* stream through the stripe map
    from nvme_strom_tpu.stripe import StripeMap
    sizes = [1 << 20] * 4
    sm = StripeMap(sizes, stripe_chunk)
    logical = bytearray(sm.total_size)
    logical[:] = expected_bytes(0, sm.total_size)
    members = [bytearray(sizes[i]) for i in range(4)]
    for e in sm.map_range(0, sm.total_size):
        members[e.member][e.member_offset:e.member_offset + e.length] = \
            logical[e.logical_offset:e.logical_offset + e.length]
    for p, m in zip(paths, members):
        with open(p, "wb") as f:
            f.write(bytes(m))

    with StripedSource(paths, stripe_chunk) as src:
        ids = [0, 5, 17, 33, 63]
        res, data = _run_copy(src, ids)
        for slot, cid in enumerate(res.chunk_ids):
            assert data[slot * CHUNK:(slot + 1) * CHUNK] == \
                bytes(logical[cid * CHUNK:(cid + 1) * CHUNK]), f"chunk {cid}"


def test_segmented_source_copy(tmp_path):
    seg = 1 << 20
    paths = [str(tmp_path / f"seg{i}.bin") for i in range(3)]
    full = expected_bytes(0, 3 * seg)
    for i, p in enumerate(paths):
        with open(p, "wb") as f:
            f.write(full[i * seg:(i + 1) * seg])
    with SegmentedSource(paths, seg) as src:
        assert src.size == 3 * seg
        ids = [0, 15, 16, 40]  # 16 straddles into segment 2 at 64KB chunks
        res, data = _run_copy(src, ids)
        for slot, cid in enumerate(res.chunk_ids):
            assert data[slot * CHUNK:(slot + 1) * CHUNK] == \
                full[cid * CHUNK:(cid + 1) * CHUNK]


def test_open_source_dispatch(tmp_data_file, tmp_path):
    s = open_source(tmp_data_file)
    assert isinstance(s, PlainSource)
    s.close()
    with pytest.raises(StromError):
        open_source([tmp_data_file, tmp_data_file])  # needs stripe/segment arg


# ---------------------------------------------------------------------------
# async semantics: error latching, retention, wait
# ---------------------------------------------------------------------------

def test_error_latched_and_raised_on_wait(tmp_data_file):
    plan = FaultPlan(fail_offsets={0})  # first request fails
    src = FakeNvmeSource(tmp_data_file, fault_plan=plan, force_cached_fraction=0.0)
    try:
        with Session() as sess:
            handle, buf = sess.alloc_dma_buffer(8 * CHUNK)
            res = sess.memcpy_ssd2ram(src, handle, list(range(8)), CHUNK)
            with pytest.raises(StromError) as ei:
                sess.memcpy_wait(res.dma_task_id)
            assert ei.value.errno == errno.EIO
            # reaped: second wait -> ENOENT
            with pytest.raises(StromError) as ei2:
                sess.memcpy_wait(res.dma_task_id)
            assert ei2.value.errno == errno.ENOENT
    finally:
        src.close()


def test_failed_task_retained_until_session_close(tmp_data_file):
    """Reference design memo kmod/nvme_strom.c:612-626: errors survive until
    a waiter reaps them or the fd closes."""
    plan = FaultPlan(fail_offsets={0})
    src = FakeNvmeSource(tmp_data_file, fault_plan=plan, force_cached_fraction=0.0)
    try:
        sess = Session()
        handle, buf = sess.alloc_dma_buffer(2 * CHUNK)
        res = sess.memcpy_ssd2ram(src, handle, [0, 1], CHUNK)
        # never wait; let the IO fail asynchronously, then confirm the task
        # is *retained* in the table rather than silently dropped
        from nvme_strom_tpu.engine import DmaTaskState
        slot = res.dma_task_id % 512
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            task = sess._slots[slot].get(res.dma_task_id)
            assert task is not None, "failed task dropped before reap"
            if task.state == DmaTaskState.FAILED:
                break
            time.sleep(0.01)
        assert res.dma_task_id in sess.pending_tasks()
        reaped = sess.close()
        assert res.dma_task_id in reaped
    finally:
        src.close()


def test_first_error_wins(tmp_data_file):
    # recovery ladder off: this test pins the raw first-error latch
    # semantics (with retries/fallback on, a periodic plan heals — see
    # test_transient_eio_retries_to_success)
    config.set("io_retries", 0)
    config.set("io_fallback", False)
    plan = FaultPlan(fail_every_nth=1)  # every request fails
    src = FakeNvmeSource(tmp_data_file, fault_plan=plan, force_cached_fraction=0.0)
    try:
        with Session() as sess:
            handle, _ = sess.alloc_dma_buffer(8 * CHUNK)
            res = sess.memcpy_ssd2ram(src, handle, list(range(8)), CHUNK)
            with pytest.raises(StromError) as ei:
                sess.memcpy_wait(res.dma_task_id)
            assert ei.value.errno == errno.EIO
    finally:
        src.close()


def test_wait_timeout(tmp_data_file):
    plan = FaultPlan(latency_s=0.5)
    src = FakeNvmeSource(tmp_data_file, fault_plan=plan, force_cached_fraction=0.0)
    try:
        with Session() as sess:
            handle, _ = sess.alloc_dma_buffer(CHUNK)
            res = sess.memcpy_ssd2ram(src, handle, [0], CHUNK)
            with pytest.raises(StromError) as ei:
                sess.memcpy_wait(res.dma_task_id, timeout=0.01)
            assert ei.value.errno == errno.ETIMEDOUT
            # task still completes and can be reaped
            sess.memcpy_wait(res.dma_task_id, timeout=5.0)
    finally:
        src.close()


def test_wait_unknown_task():
    with Session() as sess:
        with pytest.raises(StromError) as ei:
            sess.memcpy_wait(999999, timeout=0.1)
        assert ei.value.errno == errno.ENOENT


# ---------------------------------------------------------------------------
# buffer registry
# ---------------------------------------------------------------------------

def test_buffer_map_list_info_unmap():
    with Session() as sess:
        h1, _ = sess.alloc_dma_buffer(1 << 20)
        h2, _ = sess.alloc_dma_buffer(2 << 20)
        assert sess.list_buffers() == [h1, h2]
        info = sess.info_buffer(h2)
        assert info.length == 2 << 20
        assert info.kind == "pinned_host"
        assert info.owner_uid == os.getuid()
        sess.unmap_buffer(h1)
        assert sess.list_buffers() == [h2]
        with pytest.raises(StromError):
            sess.info_buffer(h1)


def test_buffer_too_small_rejected(tmp_data_file):
    with PlainSource(tmp_data_file) as src, Session() as sess:
        handle, _ = sess.alloc_dma_buffer(CHUNK)
        with pytest.raises(StromError) as ei:
            sess.memcpy_ssd2ram(src, handle, [0, 1], CHUNK)
        assert ei.value.errno == errno.ERANGE


def test_unmap_waits_for_inflight_dma(tmp_data_file):
    plan = FaultPlan(latency_s=0.2)
    src = FakeNvmeSource(tmp_data_file, fault_plan=plan, force_cached_fraction=0.0)
    try:
        with Session() as sess:
            handle, _ = sess.alloc_dma_buffer(CHUNK)
            res = sess.memcpy_ssd2ram(src, handle, [0], CHUNK)
            with pytest.raises(StromError) as ei:
                sess.unmap_buffer(handle, wait=False)
            assert ei.value.errno == errno.EBUSY
            sess.unmap_buffer(handle, wait=True, timeout=5.0)  # blocks till drain
            sess.memcpy_wait(res.dma_task_id)
    finally:
        src.close()


def test_unmap_drain_wakes_on_release():
    """Drain is condition-variable based (kmod/pmemmap.c:149-208 analog):
    _put_buffer signals the waiter instead of the waiter sleep-polling.
    The mechanism is asserted directly (Condition + notify on last ref)
    rather than via a wall-clock latency threshold, which would be both
    flaky under load and satisfiable by a 1ms poll."""
    import threading
    with Session() as sess:
        handle, _ = sess.alloc_dma_buffer(1 << 16)
        assert isinstance(sess._buf_lock, threading.Condition)
        sess._get_buffer(handle)  # simulate one in-flight DMA ref
        notified = threading.Event()
        orig_notify = sess._buf_lock.notify_all
        sess._buf_lock.notify_all = lambda: (notified.set(), orig_notify())

        def release():
            sess._put_buffer(handle)

        th = threading.Thread(target=release)
        th.start()
        sess.unmap_buffer(handle, wait=True, timeout=5.0)
        th.join()
        assert notified.is_set(), "_put_buffer must signal the drain waiter"


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------

def test_stats_counters_move(tmp_data_file):
    before = stats.snapshot()
    with PlainSource(tmp_data_file) as src:
        _run_copy(src, list(range(8)))
    after = stats.snapshot()
    assert after.counters["nr_ioctl_memcpy_submit"] > before.counters["nr_ioctl_memcpy_submit"]
    assert after.counters["nr_ioctl_memcpy_wait"] > before.counters["nr_ioctl_memcpy_wait"]
    assert after.counters["total_dma_length"] >= before.counters["total_dma_length"]
    assert after.counters["cur_dma_count"] == 0


def test_avg_dma_size_reflects_merging(tmp_data_file):
    """8 contiguous 64KB chunks with a 256KB cap must average 256KB/request."""
    config.set("cache_arbitration", False)
    config.set("dma_max_size", "256k")
    # the coalesce second pass (default 8MB) would merge all 8 chunks
    # into ONE submission; this test pins the classic per-cap merging
    config.set("coalesce_limit", 0)
    before = stats.snapshot()
    with PlainSource(tmp_data_file) as src:
        _run_copy(src, list(range(8)))
    after = stats.snapshot()
    d_subs = after.counters["nr_submit_dma"] - before.counters["nr_submit_dma"]
    d_bytes = after.counters["total_dma_length"] - before.counters["total_dma_length"]
    assert d_subs == 2
    assert d_bytes // d_subs == 256 << 10


def test_plan_splits_oversized_chunk(tmp_data_file):
    """A chunk larger than dma_max_size must split into cap-sized requests
    (the reference never issues a DMA above the 256KB cap)."""
    with PlainSource(tmp_data_file) as src:
        reqs = plan_requests(src, [(0, 0)], 1 << 20, 0,
                             dma_max_size=256 << 10)  # 1MB chunk, 256KB cap
        assert all(r.length <= 256 << 10 for r in reqs)
        assert sum(r.length for r in reqs) == 1 << 20
        # contiguity preserved
        assert [r.file_off for r in reqs] == [i * (256 << 10) for i in range(4)]


def test_any_exception_latches_task(tmp_data_file):
    """A non-OSError failure in the read leg must fail the task, never
    complete it as DONE over an unread buffer."""
    class BoomSource(PlainSource):
        def read_member_direct(self, member, file_off, dest):
            raise ValueError("boom")
        def cached_fraction(self, offset, length):
            return 0.0
        def hot_fraction(self, offset, length):
            # pin to 0 so the freshly written (still dirty) test file
            # cannot route the chunk write-back around the direct leg
            return 0.0
    with BoomSource(tmp_data_file) as src, Session() as sess:
        handle, _ = sess.alloc_dma_buffer(CHUNK)
        res = sess.memcpy_ssd2ram(src, handle, [0], CHUNK)
        with pytest.raises(StromError) as ei:
            sess.memcpy_wait(res.dma_task_id)
        assert "boom" in str(ei.value)


def test_plan_segment_split_of_single_piece(tmp_data_file):
    """A single chunk larger than the dest segment must split at segment
    boundaries, not only at merge time."""
    with PlainSource(tmp_data_file) as src:
        reqs = plan_requests(src, [(0, 0)], 256 << 10, 0, dest_segment_shift=17)
        assert [r.length for r in reqs] == [128 << 10, 128 << 10]
        for r in reqs:
            assert (r.dest_off >> 17) == ((r.dest_off + r.length - 1) >> 17)


def test_config_cross_validation_on_either_side():
    config.set("chunk_size", "1m")
    config.set("buffer_size", "3m")
    import pytest as _pytest
    from nvme_strom_tpu.config import ConfigError
    with _pytest.raises(ConfigError):
        config.set("chunk_size", "2m")  # would break buffer multiple invariant
    assert config.get("chunk_size") == 1 << 20  # rolled back


# -- write path (RAM->SSD; exceeds the read-only reference) ------------------

def test_ram2ssd_roundtrip_plain(tmp_path):
    from nvme_strom_tpu.engine import Session, open_source

    path = str(tmp_path / "w.bin")
    with open(path, "wb") as f:
        f.write(b"\0" * (8 << 20))
    rng = np.random.default_rng(91)
    payload = rng.integers(0, 255, 8 << 20, dtype=np.uint8)

    with open_source(path, writable=True) as sink, Session() as sess:
        handle, buf = sess.alloc_dma_buffer(8 << 20)
        buf.view()[:] = payload.tobytes()
        # scatter: write chunks in a shuffled order
        ids = list(rng.permutation(8))
        res = sess.memcpy_ram2ssd(sink, handle, ids, 1 << 20)
        sess.memcpy_wait(res.dma_task_id)
        sink.sync()
        assert res.nr_ssd2dev == 8 and res.chunk_ids == ids

    with open(path, "rb") as f:
        got = np.frombuffer(f.read(), np.uint8)
    for slot, cid in enumerate(ids):
        np.testing.assert_array_equal(
            got[cid << 20:(cid + 1) << 20],
            payload[slot << 20:(slot + 1) << 20])


def test_ram2ssd_striped_and_readback(tmp_path):
    """Write through the stripe map, read back through the direct path."""
    from nvme_strom_tpu.engine import Session, open_source

    paths = []
    for i in range(3):
        p = str(tmp_path / f"m{i}.bin")
        with open(p, "wb") as f:
            f.write(b"\0" * (1 << 20))
        paths.append(p)
    rng = np.random.default_rng(92)
    payload = rng.integers(0, 255, 3 << 20, dtype=np.uint8)

    with open_source(paths, stripe_chunk_size=256 << 10,
                     writable=True) as sink, Session() as sess:
        handle, buf = sess.alloc_dma_buffer(3 << 20)
        buf.view()[:] = payload.tobytes()
        res = sess.memcpy_ram2ssd(sink, handle, list(range(12)), 256 << 10)
        sess.memcpy_wait(res.dma_task_id)
        sink.sync()

    with open_source(paths, stripe_chunk_size=256 << 10) as src, \
            Session() as sess:
        handle, buf = sess.alloc_dma_buffer(3 << 20)
        res = sess.memcpy_ssd2ram(src, handle, list(range(12)), 256 << 10)
        sess.memcpy_wait(res.dma_task_id)
        got = np.frombuffer(buf.view(), np.uint8).reshape(12, 256 << 10)
        order = np.argsort(res.chunk_ids)
        np.testing.assert_array_equal(
            np.ascontiguousarray(got[order]).ravel(), payload)


def test_ram2ssd_requires_writable(tmp_path):
    from nvme_strom_tpu.engine import Session, open_source

    path = str(tmp_path / "ro.bin")
    with open(path, "wb") as f:
        f.write(b"\0" * 8192)
    with open_source(path) as sink, Session() as sess:
        handle, buf = sess.alloc_dma_buffer(8192)
        with pytest.raises(StromError):
            sess.memcpy_ram2ssd(sink, handle, [0], 8192)


def test_ram2ssd_misaligned_src_offset_uses_buffered_leg(tmp_path):
    from nvme_strom_tpu.engine import Session, open_source

    path = str(tmp_path / "mis.bin")
    with open(path, "wb") as f:
        f.write(b"\0" * 8192)
    data = bytes(range(256)) * 32  # 8192 bytes
    with open_source(path, writable=True) as sink, Session() as sess:
        handle, buf = sess.alloc_dma_buffer(8192 + 256)
        buf.view()[256:256 + 8192] = data
        res = sess.memcpy_ram2ssd(sink, handle, [0], 8192, src_offset=256)
        sess.memcpy_wait(res.dma_task_id)
        sink.sync()
    assert open(path, "rb").read() == data
