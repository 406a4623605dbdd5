"""Write-path fault-tolerance tests (ISSUE 11): the RAM→SSD ladder gets
the read path's whole survivability story — transient retry, PERSISTENT
first-error latch, mirror fan-out with degraded-mode journaling, rejoin
resync replay, write_verify read-back, latency-driven suspicion from
write-only traffic, deadline watchdog, adaptive-sizer feedback and the
buffered misaligned tail riding the same policed ladder.  All
hardware-free via :class:`~nvme_strom_tpu.testing.fake.FaultPlan` write
tiers; the SIGKILL-mid-save checkpoint crash harness lives in
``testing/chaos.py`` (``make chaos-write``), the crc round trip rides
here."""

import errno
import os
import time

import numpy as np
import pytest

from nvme_strom_tpu import Session, StromError, config, stats
from nvme_strom_tpu.api import ErrorClass
from nvme_strom_tpu.fault import HealthState
from nvme_strom_tpu.testing import FakeNvmeSource, FaultPlan, make_test_file
from nvme_strom_tpu.testing.fake import FakeStripedNvmeSource
from nvme_strom_tpu.testing.chaos import (STRIPE, assert_pairs_identical,
                                          make_mirrored_members, read_all)

pytestmark = pytest.mark.faults

CHUNK = 64 << 10


def _counter_delta(before, after, name):
    return after.counters.get(name, 0) - before.counters.get(name, 0)


def _writable_fake(path, plan=None, size=8 * CHUNK):
    make_test_file(path, size)
    return FakeNvmeSource(path, fault_plan=plan or FaultPlan(),
                          force_cached_fraction=0.0, writable=True)


def _write_chunks(sess, sink, payload, chunk=CHUNK, timeout=60.0):
    """Write *payload* chunk-strided from slot 0 and wait it out."""
    handle, buf = sess.alloc_dma_buffer(len(payload))
    try:
        buf.view()[:len(payload)] = payload
        res = sess.memcpy_ram2ssd(sink, handle,
                                  list(range(len(payload) // chunk)), chunk)
        sess.memcpy_wait(res.dma_task_id, timeout=timeout)
        sink.sync()
    finally:
        sess.unmap_buffer(handle)


def _mirrored_writable(tmp_path, plan):
    paths = make_mirrored_members(str(tmp_path))
    return paths, FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                        fault_plan=plan,
                                        force_cached_fraction=0.0,
                                        mirror="paired", writable=True)


# ---------------------------------------------------------------------------
# transient retry / persistent latch
# ---------------------------------------------------------------------------

def test_transient_write_eio_retries_heal(tmp_path):
    """A periodic transient EIO on the write path heals inside the retry
    ladder: the file holds exactly the payload and both the shared and
    the write-specific retry counters moved."""
    config.set("dma_max_size", CHUNK)
    path = str(tmp_path / "w.bin")
    sink = _writable_fake(path, FaultPlan(write_fail_every_nth=3))
    payload = os.urandom(8 * CHUNK)
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            _write_chunks(sess, sink, payload)
    finally:
        sink.close()
    with open(path, "rb") as f:
        assert f.read(len(payload)) == payload
    after = stats.snapshot(reset_max=False)
    assert _counter_delta(before, after, "nr_write_retry") > 0
    assert _counter_delta(before, after, "nr_io_retry") > 0


def test_enospc_latches_first_error_no_retry(tmp_path):
    """ENOSPC carries PERSISTENT taxonomy: the FIRST write error latches
    the task — retrying against a full disk is pointless, so the
    write-retry counter must not move even with retries budgeted."""
    config.set("io_retries", 3)
    config.set("dma_max_size", CHUNK)
    path = str(tmp_path / "full.bin")
    sink = _writable_fake(path, FaultPlan(write_fail_every_nth=1,
                                          write_errno=errno.ENOSPC))
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            with pytest.raises(StromError) as ei:
                _write_chunks(sess, sink, os.urandom(4 * CHUNK), timeout=30.0)
            assert ei.value.errno == errno.ENOSPC
            assert ei.value.error_class is ErrorClass.PERSISTENT
    finally:
        sink.close()
    after = stats.snapshot(reset_max=False)
    assert _counter_delta(before, after, "nr_write_retry") == 0


# ---------------------------------------------------------------------------
# mirror fan-out / degraded journal / rejoin resync
# ---------------------------------------------------------------------------

def test_mirror_fanout_byte_identity(tmp_path):
    """Every aligned write leg lands on primary AND pair partner: after a
    clean whole-stream write both files of each pair are byte-identical,
    the mirror-write counter covers every leg, and a logical read-back
    returns exactly the payload."""
    config.set("dma_max_size", STRIPE)
    plan = FaultPlan()
    paths, sink = _mirrored_writable(tmp_path, plan)
    payload = os.urandom(2 * (1 << 20))
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            _write_chunks(sess, sink, payload, chunk=STRIPE)
            got, total = read_all(sess, sink, chunk=STRIPE)
            assert got == payload[:total]
    finally:
        sink.close()
    after = stats.snapshot(reset_max=False)
    assert _counter_delta(before, after, "nr_mirror_write") == \
        len(payload) // STRIPE
    assert_pairs_identical(paths, "mirror_fanout")


def test_degraded_write_journals_skipped_extents(tmp_path):
    """A primary whose writes fail persistently (no rejoin in sight)
    degrades the stream to mirror-only: the task still retires, every
    extent the victim missed sits in its dirty-extent journal, the
    member routes away, and the mirror serves the payload — stale bytes
    are never reachable."""
    config.set("io_retries", 1)
    config.set("dma_max_size", STRIPE)
    config.set("quarantine_s", 60.0)       # no rejoin during the test
    config.set("canary_interval_s", 60.0)  # no canary churn either
    victim = 0
    plan = FaultPlan(write_failstop_member=victim, write_failstop_after=0)
    paths, sink = _mirrored_writable(tmp_path, plan)
    payload = os.urandom(2 * (1 << 20))
    try:
        with Session() as sess:
            _write_chunks(sess, sink, payload, chunk=STRIPE)
            health = sess._member_health
            assert health.state(victim) is not HealthState.HEALTHY
            assert health.routes_away(victim)
            # the journal owns exactly the victim's share of the stream
            want = [(x.file_off, x.file_off + x.length)
                    for x in sink.extents(0, len(payload))
                    if x.member == victim]
            lo, hi = min(s for s, _ in want), max(e for _, e in want)
            got = sess._resync.pending_extents(victim)
            assert sess._resync.pending_bytes(victim) == \
                sum(e - s for s, e in want)
            assert (min(s for s, _ in got), max(e for _, e in got)) == (lo, hi)
            # reads route to the mirror: the payload is fully served
            got_bytes, total = read_all(sess, sink, chunk=STRIPE)
            assert got_bytes == payload[:total]
    finally:
        sink.close()


def test_rejoin_replay_drains_journal_before_healthy(tmp_path):
    """A write-side fail-stop that later heals: the rejoin path must
    replay the dirty-extent journal (mirror → rejoiner) to empty before
    the member reaches HEALTHY, after which the pair files are
    byte-identical — a rejoined disk never serves stale bytes."""
    config.set("io_retries", 1)
    config.set("task_deadline_s", 30.0)
    config.set("canary_interval_s", 0.05)
    config.set("quarantine_s", 0.1)
    config.set("rejoin_successes", 2)
    config.set("rejoin_tokens_s", 1000.0)
    config.set("dma_max_size", STRIPE)
    config.set("member_queue_depth", 1)
    victim = 2
    plan = FaultPlan(write_failstop_member=victim, write_failstop_after=3,
                     write_rejoin_after=9)
    paths, sink = _mirrored_writable(tmp_path, plan)
    payload = os.urandom(2 * (1 << 20))
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            _write_chunks(sess, sink, payload, chunk=STRIPE)
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                if sess._member_health.state(victim) is HealthState.HEALTHY:
                    break
                time.sleep(0.05)
            assert sess._member_health.state(victim) is HealthState.HEALTHY, \
                (f"victim stuck in {sess._member_health.state(victim)} with "
                 f"{sess._resync.pending_bytes(victim)} bytes pending")
            # HEALTHY implies the journal drained first, never after
            assert sess._resync.pending_bytes(victim) == 0
            got, total = read_all(sess, sink, chunk=STRIPE)
            assert got == payload[:total]
    finally:
        sink.close()
    after = stats.snapshot(reset_max=False)
    assert _counter_delta(before, after, "nr_resync_extent") > 0
    assert_pairs_identical(paths, "rejoin_replay")


# ---------------------------------------------------------------------------
# write_verify read-back
# ---------------------------------------------------------------------------

def test_write_verify_detects_torn_write(tmp_path):
    """A byte torn AFTER the write lands (media lied) is invisible to the
    errno ladder; the wait-time crc32c read-back is the oracle that
    latches it as EBADMSG."""
    config.set("write_verify", True)
    path = str(tmp_path / "torn.bin")
    sink = _writable_fake(path, FaultPlan(torn_write_offsets={100}),
                          size=2 * CHUNK)
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            with pytest.raises(StromError) as ei:
                _write_chunks(sess, sink, os.urandom(2 * CHUNK), timeout=30.0)
            assert ei.value.errno == errno.EBADMSG
    finally:
        sink.close()
    after = stats.snapshot(reset_max=False)
    assert _counter_delta(before, after, "nr_write_verify_fail") > 0


def test_write_verify_clean_pass_counts_reread(tmp_path):
    """Control: with no fault injected the verify pass re-reads every
    written byte and flags nothing."""
    config.set("write_verify", True)
    path = str(tmp_path / "clean.bin")
    sink = _writable_fake(path, size=4 * CHUNK)
    payload = os.urandom(4 * CHUNK)
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            _write_chunks(sess, sink, payload)
    finally:
        sink.close()
    with open(path, "rb") as f:
        assert f.read(len(payload)) == payload
    after = stats.snapshot(reset_max=False)
    assert _counter_delta(before, after, "nr_write_verify_fail") == 0
    assert _counter_delta(before, after, "bytes_verify_reread") >= len(payload)


# ---------------------------------------------------------------------------
# ladder parity: suspicion, watchdog and sizer feedback from writes alone
# ---------------------------------------------------------------------------

def test_write_only_traffic_drives_suspect(tmp_path):
    """ISSUE 11 acceptance: a member that is only ever WRITTEN — never
    read — still trips the latency SUSPECT machinery, because write
    service times feed the same per-member histograms."""
    # the histogram is log2-ns bucketed, so pick a stall far enough out
    # that quantized p99s can't tie the ratio boundary — and far above
    # the fast member's own tail when other test workers load the host's
    # CPUs (an 8 ms stall lost to that tail under -n 6)
    config.set("suspect_ratio", 3.0)
    config.set("dma_max_size", STRIPE)
    size = 512 << 10
    paths = [str(tmp_path / f"s{i}.bin") for i in range(2)]
    for p in paths:
        make_test_file(p, size)
    plan = FaultPlan(slow_write_member=1, slow_write_s=0.064)
    sink = FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                 fault_plan=plan,
                                 force_cached_fraction=0.0, writable=True)
    payload = os.urandom(2 * size)
    try:
        with Session() as sess:
            # suspect evaluation fires on 32-sample boundaries and needs
            # both members warm; keep streaming until it trips, within
            # this test's own time limit
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                _write_chunks(sess, sink, payload, chunk=STRIPE)
                if sess._member_health.state(1) is HealthState.SUSPECT:
                    break
            assert sess._member_health.state(1) is HealthState.SUSPECT
            assert sess._member_health.state(0) is HealthState.HEALTHY
    finally:
        sink.close()


def test_write_deadline_rides_watchdog(tmp_path):
    """An overdue write task is latched ETIMEDOUT by the same watchdog
    that polices reads — memcpy_wait returns long before the injected
    write stalls would have finished."""
    config.set("task_deadline_s", 0.25)
    config.set("dma_max_size", CHUNK)
    path = str(tmp_path / "slow.bin")
    sink = _writable_fake(path, FaultPlan(slow_write_member=0,
                                          slow_write_s=0.8),
                          size=4 * CHUNK)
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            t0 = time.monotonic()
            with pytest.raises(StromError) as ei:
                _write_chunks(sess, sink, os.urandom(4 * CHUNK), timeout=30.0)
            assert time.monotonic() - t0 < 20.0
            assert ei.value.errno == errno.ETIMEDOUT
    finally:
        sink.close()
    after = stats.snapshot(reset_max=False)
    assert _counter_delta(before, after, "nr_task_timeout") > 0


def test_write_latency_shrinks_adaptive_sizer(tmp_path):
    """Write service times feed the per-member AdaptiveChunkSizer just
    like reads: a member slow at the current size must shrink its
    effective coalesce cap from write-only traffic."""
    config.set("chunk_adaptive", True)
    config.set("dma_max_size", CHUNK)
    config.set("coalesce_limit", 4 * CHUNK)
    path = str(tmp_path / "adapt.bin")
    sink = _writable_fake(path, FaultPlan(slow_write_member=0,
                                          slow_write_s=0.12),
                          size=2 * CHUNK)
    try:
        with Session() as sess:
            _write_chunks(sess, sink, os.urandom(2 * CHUNK), timeout=30.0)
            szr = sess._chunk_sizers.get(0)
            assert szr is not None, \
                "write-only traffic never created a sizer"
            assert szr.effective < 4 * CHUNK
    finally:
        sink.close()


# ---------------------------------------------------------------------------
# buffered misaligned tail rides the pool ladder (satellite f)
# ---------------------------------------------------------------------------

def test_buffered_tail_rides_pool_ladder(tmp_path):
    """A non-block-multiple file tail plans as a buffered write leg that
    must ride the SAME policed ladder as aligned legs: byte-exact
    landing and a traced extent span carrying the buffered attribution
    (not the old unpoliced synchronous write)."""
    from nvme_strom_tpu.trace import recorder, _ARGS, _NAME
    config.set("trace_policy", "all")
    recorder.configure()
    recorder.clear()
    tail = 1000
    path = str(tmp_path / "tail.bin")
    sink = _writable_fake(path, size=CHUNK + tail)
    payload = os.urandom(CHUNK + tail)
    try:
        with Session() as sess:
            handle, buf = sess.alloc_dma_buffer(2 * CHUNK)
            try:
                buf.view()[:len(payload)] = payload
                res = sess.memcpy_ram2ssd(sink, handle, [0, 1], CHUNK)
                sess.memcpy_wait(res.dma_task_id)
                sink.sync()
            finally:
                sess.unmap_buffer(handle)
    finally:
        sink.close()
    with open(path, "rb") as f:
        assert f.read() == payload
    spans = [e for e in recorder.snapshot_events()
             if e[_NAME] == "extent" and (e[_ARGS] or {}).get("write")]
    assert any((e[_ARGS] or {}).get("buffered") for e in spans), \
        "no buffered write extent span — tail bypassed the pool ladder"


# ---------------------------------------------------------------------------
# crash-consistent checkpoints: per-leaf crc32c (the SIGKILL harness is
# testing/chaos.py scenario_ckpt_crash; the crc oracle round-trips here)
# ---------------------------------------------------------------------------

def test_checkpoint_crc_roundtrip_detects_corruption(tmp_path):
    from nvme_strom_tpu.data.checkpoint import (checkpoint_info,
                                                restore_checkpoint,
                                                save_checkpoint)
    from nvme_strom_tpu.tools.strom_ckpt import main as ckpt_main
    tree = {"w": np.arange(4096, dtype=np.float32),
            "b": np.full(257, 3, dtype=np.int32)}
    path = str(tmp_path / "model.ckpt")
    save_checkpoint(path, tree)
    meta = checkpoint_info(path)
    assert all("crc32c" in e for e in meta["leaves"])
    out = restore_checkpoint(path, verify=True)
    for k, v in tree.items():
        assert np.array_equal(np.asarray(out[f"['{k}']"]).ravel(), v)
    assert ckpt_main(["verify", path]) == 0
    # flip one payload byte: verify latches EBADMSG, the CLI counts it
    e = meta["leaves"][0]
    spot = meta["data_offset"] + e["offset"] + 5
    with open(path, "r+b") as f:
        f.seek(spot)
        orig = f.read(1)
        f.seek(spot)
        f.write(bytes([orig[0] ^ 0xFF]))
    with pytest.raises(StromError) as ei:
        restore_checkpoint(path, verify=True)
    assert ei.value.errno == errno.EBADMSG
    assert ckpt_main(["verify", path]) == 1
    # un-verified restore still loads (operator's escape hatch) ...
    restore_checkpoint(path)
    # ... and healing the byte restores a clean verify
    with open(path, "r+b") as f:
        f.seek(spot)
        f.write(orig)
    assert ckpt_main(["verify", path]) == 0


def test_crc32c_incremental_matches_oneshot():
    """The streamed restore verifies with crc32c_update over spans; it
    must agree with the one-shot digest for any chunking (and with the
    published crc32c test vector)."""
    from nvme_strom_tpu.scan.heap import crc32c, crc32c_update
    assert crc32c(b"hello world") == 0xC99465AA
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, size=100_000, dtype=np.uint8).tobytes()
    for step in (1, 7, 4096, 65536, len(data)):
        crc = 0
        for i in range(0, len(data), step):
            crc = crc32c_update(crc, data[i:i + step])
        assert crc == crc32c(data), f"chunking {step} diverged"
