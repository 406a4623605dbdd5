"""stromd shared-serving-daemon tests (ISSUE 12, `daemon` marker).

Covers the tentpole's contracts end-to-end against a real daemon on a
real Unix socket: session lifecycle with byte identity through the
shared memfd buffer, protocol-version fail-closed, admission rejection
under quota, orphan reaping (abrupt disconnect AND a SIGKILLed
subprocess client), max-session admission, token-bucket shaping, the
QoS scheduler's class/weight policy at the unit level and end to end
(bytes delivered under DRR weights), and the daemon's
stats/trace/prometheus surface.
"""

from __future__ import annotations

import errno
import os
import signal
import socket as socket_mod
import subprocess
import sys
import time

import pytest

from nvme_strom_tpu.api import StromError
from nvme_strom_tpu.config import config
from nvme_strom_tpu.daemon import (DaemonBuffer, DaemonSession,
                                   PROTOCOL_VERSION)
from nvme_strom_tpu.daemon.protocol import Framer, send_msg
from nvme_strom_tpu.daemon.qos import QosScheduler, TokenBucket, WorkItem
from nvme_strom_tpu.daemon.server import StromDaemon
from nvme_strom_tpu.stats import stats
from nvme_strom_tpu.testing.fake import expected_bytes, make_test_file

pytestmark = pytest.mark.daemon

CHUNK = 64 << 10


@pytest.fixture
def daemon(tmp_path):
    d = StromDaemon(str(tmp_path / "stromd.sock"), allow_fake=True).start()
    yield d
    d.close()


@pytest.fixture
def data_file(tmp_path):
    path = str(tmp_path / "data.bin")
    make_test_file(path, 32 * CHUNK)
    return path


def _item(tenant: str, sid: int = 1, task: int = 1, nchunks: int = 4):
    return WorkItem(session_id=sid, tenant=tenant, task_id=task,
                    source_handle=0, buf_handle=0,
                    chunk_ids=list(range(nchunks)), chunk_size=CHUNK)


# -- lifecycle ---------------------------------------------------------------

def test_lifecycle_byte_identity(daemon, data_file):
    """attach -> map -> open -> submit -> wait -> detach, with the DMA
    landing in the client's own memfd pages byte-identically."""
    with DaemonSession(daemon.socket_path, tenant="t-life") as sess:
        assert sess.ping()
        src = sess.open_source(data_file)
        assert src.size == 32 * CHUNK
        handle, buf = sess.alloc_dma_buffer(16 * CHUNK)
        res = sess.memcpy_ssd2ram(src, handle, list(range(16)), CHUNK)
        assert res.nr_chunks == 16          # preliminary, conservation holds
        out = sess.memcpy_wait(res.dma_task_id, timeout=60)
        assert out.nr_chunks == 16
        assert sorted(out.chunk_ids) == list(range(16))
        assert bytes(buf.view()[:16 * CHUNK]) == expected_bytes(0, 16 * CHUNK)
        sess.unmap_buffer(handle)
        src.close()
    time.sleep(0.1)
    assert daemon.session_count() == 0


def test_wait_unknown_task_and_source(daemon, data_file):
    with DaemonSession(daemon.socket_path) as sess:
        with pytest.raises(StromError) as e:
            sess.memcpy_wait(9999, timeout=1)
        assert e.value.errno == errno.ENOENT
        handle, _buf = sess.alloc_dma_buffer(CHUNK)
        with pytest.raises(StromError) as e:
            sess._rpc({"op": "submit", "source": 77, "buffer": handle,
                       "chunk_ids": [0], "chunk_size": CHUNK})
        assert e.value.errno == errno.ENOENT


def test_protocol_version_mismatch_fails_closed(daemon):
    """A wrong-version attach gets EPROTO and the connection drops before
    any resource is allocated."""
    sock = socket_mod.socket(socket_mod.AF_UNIX, socket_mod.SOCK_STREAM)
    sock.settimeout(10)
    sock.connect(daemon.socket_path)
    try:
        send_msg(sock, {"op": "attach", "version": PROTOCOL_VERSION + 1,
                        "tenant": "t-old"})
        framer = Framer(sock)
        reply, _fds = framer.recv()
        assert reply["ok"] is False
        assert reply["errno"] == errno.EPROTO
        assert framer.recv() is None        # daemon hung up
    finally:
        sock.close()
    assert daemon.session_count() == 0


def test_first_message_must_be_attach(daemon):
    sock = socket_mod.socket(socket_mod.AF_UNIX, socket_mod.SOCK_STREAM)
    sock.settimeout(10)
    sock.connect(daemon.socket_path)
    try:
        send_msg(sock, {"op": "ping"})
        reply, _ = Framer(sock).recv()
        assert reply["ok"] is False and reply["errno"] == errno.EPROTO
    finally:
        sock.close()


# -- admission control -------------------------------------------------------

def test_admission_quota_rejects_with_eagain(tmp_path, data_file):
    config.set("daemon_quota_tasks", 2)
    try:
        d = StromDaemon(str(tmp_path / "q.sock"), allow_fake=True,
                        dispatchers=0).start()
    finally:
        config.set("daemon_quota_tasks", 0)
    try:
        before = stats.snapshot(reset_max=False).counters
        with DaemonSession(d.socket_path, tenant="t-quota") as sess:
            src = sess.open_source(data_file)
            handle, _buf = sess.alloc_dma_buffer(4 * CHUNK)
            sess.memcpy_ssd2ram(src, handle, [0], CHUNK)
            sess.memcpy_ssd2ram(src, handle, [1], CHUNK)
            with pytest.raises(StromError) as e:   # third in-flight: bounced
                sess.memcpy_ssd2ram(src, handle, [2], CHUNK)
            assert e.value.errno == errno.EAGAIN
        after = stats.snapshot(reset_max=False).counters
        assert after["nr_admission_reject"] - \
            before.get("nr_admission_reject", 0) == 1
        t = stats.tenant_snapshot()["t-quota"]
        assert t["rejects"] >= 1
    finally:
        d.close()


def test_max_sessions(tmp_path):
    d = StromDaemon(str(tmp_path / "m.sock"), max_sessions=1,
                    allow_fake=True).start()
    try:
        with DaemonSession(d.socket_path):
            with pytest.raises(StromError) as e:
                DaemonSession(d.socket_path)
            assert e.value.errno == errno.EAGAIN
    finally:
        d.close()


# -- orphan reaping ----------------------------------------------------------

def test_abrupt_disconnect_reaps_everything(daemon, data_file):
    """Dropping the socket without detach must release the session's
    engine buffer registrations and sources — no leaked leases."""
    engine = daemon._engine
    before = stats.snapshot(reset_max=False).counters
    sess = DaemonSession(daemon.socket_path, tenant="t-crash")
    src = sess.open_source(data_file)
    handle, _buf = sess.alloc_dma_buffer(4 * CHUNK)
    res = sess.memcpy_ssd2ram(src, handle, list(range(4)), CHUNK)
    sess.memcpy_wait(res.dma_task_id, timeout=60)
    n_before = len(engine.list_buffers())
    sess._sock.close()                       # crash, not close(): no detach
    deadline = time.monotonic() + 30
    while daemon.session_count() > 0:
        assert time.monotonic() < deadline, "orphan never reaped"
        time.sleep(0.01)
    deadline = time.monotonic() + 30
    while len(engine.list_buffers()) >= n_before:
        assert time.monotonic() < deadline, "buffer lease leaked after reap"
        time.sleep(0.01)
    after = stats.snapshot(reset_max=False).counters
    assert after["nr_session_reap"] - before.get("nr_session_reap", 0) == 1
    assert after["daemon_sessions"] == 0
    t = stats.tenant_snapshot()["t-crash"]
    assert t["inflight_tasks"] == 0 and t["inflight_bytes"] == 0


def test_reap_cancels_queued_work(tmp_path, data_file):
    """Queued-but-undispatched items of a dead session are cancelled and
    their quota released — a crashed client cannot wedge the lane."""
    d = StromDaemon(str(tmp_path / "r.sock"), allow_fake=True,
                    dispatchers=0).start()
    try:
        sess = DaemonSession(d.socket_path, tenant="t-wedge")
        src = sess.open_source(data_file)
        handle, _buf = sess.alloc_dma_buffer(4 * CHUNK)
        for i in range(8):
            sess.memcpy_ssd2ram(src, handle, [i], CHUNK)
        assert d.queue_depth() == 8
        sess._sock.close()
        deadline = time.monotonic() + 30
        while d.session_count() > 0 or d.queue_depth() > 0:
            assert time.monotonic() < deadline, "queued orphan work stuck"
            time.sleep(0.01)
        t = stats.tenant_snapshot()["t-wedge"]
        assert t["inflight_tasks"] == 0 and t["inflight_bytes"] == 0
    finally:
        d.close()


def test_sigkilled_client_is_reaped(daemon, data_file):
    """A client process SIGKILLed mid-session (the acceptance-criteria
    crash) is fully reaped: session gone, no leaked engine buffers."""
    engine = daemon._engine
    n_before = len(engine.list_buffers())
    child = subprocess.Popen(
        [sys.executable, "-c", f"""
import sys, time
from nvme_strom_tpu.daemon import DaemonSession
sess = DaemonSession({daemon.socket_path!r}, tenant="t-kill9")
src = sess.open_source({data_file!r})
h, buf = sess.alloc_dma_buffer({4 * CHUNK})
r = sess.memcpy_ssd2ram(src, h, [0, 1, 2, 3], {CHUNK})
sess.memcpy_wait(r.dma_task_id, timeout=60)
print("READY", flush=True)
time.sleep(120)
"""],
        stdout=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        line = child.stdout.readline()
        assert "READY" in line, f"client never came up: {line!r}"
        assert daemon.session_count() == 1
        assert len(engine.list_buffers()) == n_before + 1
        os.kill(child.pid, signal.SIGKILL)
        child.wait(timeout=30)
        deadline = time.monotonic() + 30
        while daemon.session_count() > 0 \
                or len(engine.list_buffers()) > n_before:
            assert time.monotonic() < deadline, \
                "SIGKILLed client left leases behind"
            time.sleep(0.02)
        t = stats.tenant_snapshot()["t-kill9"]
        assert t["inflight_tasks"] == 0 and t["inflight_bytes"] == 0
    finally:
        if child.poll() is None:
            child.kill()
        child.stdout.close()


# -- QoS: shaping + scheduler policy ----------------------------------------

def test_token_bucket():
    bkt = TokenBucket(rate=1 << 20, burst=1 << 20)   # 1MB/s, 1MB burst
    now = bkt._t_last                                 # deterministic clock
    assert bkt.ready_in(1 << 20, now=now) == 0.0
    bkt.consume(1 << 20, now=now)
    wait = bkt.ready_in(1 << 20, now=now)
    assert 0.9 < wait <= 1.0                          # full refill ~1s out
    assert bkt.ready_in(1 << 20, now=now + 2.0) == 0.0   # refilled
    unshaped = TokenBucket(rate=0, burst=1)
    assert unshaped.ready_in(1 << 30, now=now) == 0.0


def test_scheduler_weighted_fairness_unit():
    """40 equal-size dispatches across 3:1-weighted tenants land within
    one quantum of 3:1 — deterministic, no I/O, no sleeping."""
    sched = QosScheduler(quantum=256 << 10)
    sched.register_tenant("a", weight=3.0)
    sched.register_tenant("b", weight=1.0)
    for i in range(40):
        sched.enqueue(_item("a", sid=1, task=i))
        sched.enqueue(_item("b", sid=2, task=100 + i))
    got = {"a": 0, "b": 0}
    for _ in range(40):
        item = sched.next_item(timeout=1)
        got[item.tenant] += 1
    assert 28 <= got["a"] <= 32, got                  # 3:1 of 40 = 30/10
    sched.close()


def test_scheduler_strict_class_priority():
    sched = QosScheduler()
    sched.register_tenant("bulk", qos_class="bulk")
    sched.register_tenant("lat", qos_class="latency")
    for i in range(4):
        sched.enqueue(_item("bulk", sid=1, task=i))
    sched.enqueue(_item("lat", sid=2, task=99))
    first = sched.next_item(timeout=1)
    assert first.tenant == "lat"                      # latency preempts bulk
    assert sched.next_item(timeout=1).tenant == "bulk"
    sched.close()


def test_scheduler_drop_session():
    sched = QosScheduler()
    sched.register_tenant("a")
    sched.register_tenant("b")
    for i in range(3):
        sched.enqueue(_item("a", sid=1, task=i))
    sched.enqueue(_item("b", sid=2, task=9))
    dropped = sched.drop_session(1)
    assert len(dropped) == 3 and all(w.cancelled for w in dropped)
    assert sched.depth() == 1
    assert sched.next_item(timeout=1).tenant == "b"
    sched.close()


def test_weighted_fairness_end_to_end(tmp_path):
    """Real daemon, one dispatcher, 3:1-weighted tenants with their whole
    backlog queued before the first dispatch: while both are still
    backlogged the bytes delivered split within 25% of 3:1."""
    n_tasks, per_task = 128, 4
    path = str(tmp_path / "fair.bin")
    make_test_file(path, n_tasks * per_task * CHUNK)
    # the per-request latency makes the lane the bottleneck, so the
    # split is set by the scheduler while both queues are non-empty
    spec = {"kind": "fake", "path": path, "latency_s": 0.004,
            "force_cached_fraction": 0.0}
    d = StromDaemon(str(tmp_path / "f.sock"), allow_fake=True,
                    dispatchers=0).start()
    try:
        heavy = DaemonSession(d.socket_path, tenant="heavy", weight=3.0)
        light = DaemonSession(d.socket_path, tenant="light", weight=1.0)
        mon = DaemonSession(d.socket_path, tenant="_monitor")
        try:
            for sess in (heavy, light):
                src = sess.open_source(spec)
                h, _buf = sess.alloc_dma_buffer(per_task * CHUNK)
                for t in range(n_tasks):
                    sess.memcpy_ssd2ram(
                        src, h, list(range(t * per_task, (t + 1) * per_task)),
                        CHUNK)
            d.start_dispatchers(1)
            # heavy drains near 4/3 * n_tasks done; sample well before
            deadline = time.monotonic() + 120.0
            while True:
                st = mon.daemon_stat()["tenants"]
                done = sum(st[t]["tasks"] for t in ("heavy", "light"))
                if done >= n_tasks:
                    break
                assert time.monotonic() < deadline, f"stalled at {done}"
                time.sleep(0.002)
            hb, lb = st["heavy"]["bytes"], st["light"]["bytes"]
            assert lb > 0, "light tenant starved outright"
            assert 3.0 * 0.75 <= hb / lb <= 3.0 * 1.25, (hb, lb)
        finally:
            for sess in (heavy, light, mon):
                sess.close()
    finally:
        d.close()


def test_token_bucket_shaping_throttles_end_to_end(tmp_path, data_file):
    """A shaped tenant takes at least the shaped time and trips the
    throttle accounting; an unshaped run of the same bytes is fast."""
    d = StromDaemon(str(tmp_path / "s.sock"), allow_fake=True,
                    dispatchers=1).start()
    try:
        before = stats.snapshot(reset_max=False).counters
        # 512KB at 1MB/s with a 256KB burst => >= ~0.25s shaped
        with DaemonSession(d.socket_path, tenant="t-shaped",
                           rate=float(1 << 20)) as sess:
            sess.configure(rate=float(1 << 20))
            d._sched.register_tenant("t-shaped", rate=float(1 << 20),
                                     burst=float(256 << 10))
            src = sess.open_source(data_file)
            handle, _buf = sess.alloc_dma_buffer(4 * CHUNK)
            t0 = time.monotonic()
            tids = [sess.memcpy_ssd2ram(src, handle, [i * 4 + j for j in
                                                      range(4)],
                                        CHUNK).dma_task_id
                    for i in range(2)]
            for tid in tids:
                sess.memcpy_wait(tid, timeout=60)
            elapsed = time.monotonic() - t0
        after = stats.snapshot(reset_max=False).counters
        assert elapsed >= 0.2, \
            f"shaped 512KB at 1MB/s finished in {elapsed:.3f}s"
        assert after["nr_qos_throttle"] > before.get("nr_qos_throttle", 0)
        assert stats.tenant_snapshot()["t-shaped"]["throttles"] >= 1
    finally:
        d.close()


# -- observability surface ---------------------------------------------------

def test_trace_events_within_schema(tmp_path, data_file):
    from nvme_strom_tpu.trace import EVENT_SCHEMA, recorder
    config.set("trace_policy", "all")
    recorder.configure()
    try:
        d = StromDaemon(str(tmp_path / "t.sock"), allow_fake=True).start()
        try:
            with DaemonSession(d.socket_path, tenant="t-trace") as sess:
                src = sess.open_source(data_file)
                handle, _buf = sess.alloc_dma_buffer(4 * CHUNK)
                r = sess.memcpy_ssd2ram(src, handle, [0, 1, 2, 3], CHUNK)
                sess.memcpy_wait(r.dma_task_id, timeout=60)
            time.sleep(0.1)
        finally:
            d.close()
        names = {ev[2] for ev in recorder.snapshot_events()}
        assert names <= set(EVENT_SCHEMA), names - set(EVENT_SCHEMA)
        for want in ("session_attach", "qos_enqueue", "qos_wait",
                     "session_detach"):
            assert want in names, f"{want} never emitted"
    finally:
        config.set("trace_policy", "off")
        recorder.configure()
        recorder.clear()


def test_prometheus_tenant_series(daemon, data_file):
    from nvme_strom_tpu.trace import render_prometheus
    with DaemonSession(daemon.socket_path, tenant="t-prom") as sess:
        src = sess.open_source(data_file)
        handle, _buf = sess.alloc_dma_buffer(4 * CHUNK)
        r = sess.memcpy_ssd2ram(src, handle, [0, 1, 2, 3], CHUNK)
        sess.memcpy_wait(r.dma_task_id, timeout=60)
    snap = stats.snapshot(reset_max=False, debug=True)
    text = render_prometheus({"counters": snap.counters, "pid": os.getpid(),
                              "timestamp_ns": snap.timestamp_ns,
                              "tenants": stats.tenant_snapshot(),
                              "lat_hist": stats.lat_hist_snapshot()})
    assert 'strom_tpu_tenant_bytes_total{tenant="t-prom"}' in text
    assert 'strom_tpu_tenant_wait_seconds_bucket{tenant="t-prom"' in text
    assert "strom_tpu_daemon_sessions" in text
    assert "strom_tpu_nr_session_attach_total" in text


def test_tpu_stat_daemon_scoreboard(daemon, data_file, capsys):
    from nvme_strom_tpu.tools.tpu_stat import main as tpu_stat_main
    with DaemonSession(daemon.socket_path, tenant="t-board") as sess:
        src = sess.open_source(data_file)
        handle, _buf = sess.alloc_dma_buffer(4 * CHUNK)
        r = sess.memcpy_ssd2ram(src, handle, [0, 1, 2, 3], CHUNK)
        sess.memcpy_wait(r.dma_task_id, timeout=60)
        rc = tpu_stat_main(["--daemon", daemon.socket_path])
    out = capsys.readouterr().out
    assert rc == 0
    assert "t-board" in out and "stromd @" in out


def test_daemon_buffer_roundtrip():
    buf = DaemonBuffer(1 << 16)
    view = buf.view()
    view[:4] = b"abcd"
    assert bytes(buf.view()[:4]) == b"abcd"
    buf.close()
    buf.close()                                       # idempotent


# -- serving leases / restart survival (ISSUE 15) ----------------------------

def test_attach_mints_lease_and_submit_id_dedups(daemon, data_file):
    """Every attach carries a lease token; resubmitting the same
    submit_id returns the SAME task instead of re-enqueuing (idempotent
    retry after a dropped reply)."""
    with DaemonSession(daemon.socket_path, tenant="t-lease") as sess:
        assert sess.lease
        src = sess.open_source(data_file)
        handle, buf = sess.alloc_dma_buffer(4 * CHUNK)
        r1 = sess.memcpy_ssd2ram(src, handle, [0, 1, 2, 3], CHUNK,
                                 submit_id="job-a")
        r2 = sess.memcpy_ssd2ram(src, handle, [0, 1, 2, 3], CHUNK,
                                 submit_id="job-a")
        assert r2.dma_task_id == r1.dma_task_id
        sess.memcpy_wait(r1.dma_task_id, timeout=60)
        assert bytes(buf.view()[:4 * CHUNK]) == expected_bytes(0, 4 * CHUNK)
        # wait acked the submit: the SAME id now names a fresh task
        r3 = sess.memcpy_ssd2ram(src, handle, [0, 1, 2, 3], CHUNK,
                                 submit_id="job-a")
        assert r3.dma_task_id != r1.dma_task_id
        sess.memcpy_wait(r3.dma_task_id, timeout=60)


def test_lease_reattach_same_daemon(daemon, data_file):
    """A dropped connection re-attaches under its lease token: the
    daemon recognizes it (reattach=True) and handles keep working."""
    with DaemonSession(daemon.socket_path, tenant="t-re") as sess:
        src = sess.open_source(data_file)
        handle, buf = sess.alloc_dma_buffer(4 * CHUNK)
        token = sess.lease
        # simulate a dropped TCP-level connection without detach
        sess._sock.close()
        assert sess.reattach() is True
        assert sess.lease == token
        r = sess.memcpy_ssd2ram(src, handle, [0, 1, 2, 3], CHUNK)
        sess.memcpy_wait(r.dma_task_id, timeout=60)
        assert bytes(buf.view()[:4 * CHUNK]) == expected_bytes(0, 4 * CHUNK)


def test_daemon_restart_reattach_and_idempotent_replay(tmp_path, data_file):
    """The daemon dies and is restarted on the same socket.  reattach()
    returns False (lease adopted fresh), remapped buffers keep their
    caller handles, and replaying the unacked submit_id re-runs it
    byte-identically."""
    sock = str(tmp_path / "stromd.sock")
    d1 = StromDaemon(sock, allow_fake=True).start()
    sess = DaemonSession(sock, tenant="t-restart")
    try:
        src = sess.open_source(data_file)
        handle, buf = sess.alloc_dma_buffer(4 * CHUNK)
        r = sess.memcpy_ssd2ram(src, handle, [0, 1, 2, 3], CHUNK,
                                submit_id="job-1")
        sess.memcpy_wait(r.dma_task_id, timeout=60)
        buf.view()[:4 * CHUNK] = b"\0" * (4 * CHUNK)   # scrub the landing
        d1.close()
        d2 = StromDaemon(sock, allow_fake=True).start()
        try:
            assert sess.reattach() is False    # fresh daemon adopted it
            # unacked-from-the-caller's-view work replays idempotently
            r2 = sess.memcpy_ssd2ram(src, handle, [0, 1, 2, 3], CHUNK,
                                     submit_id="job-1")
            sess.memcpy_wait(r2.dma_task_id, timeout=60)
            assert bytes(buf.view()[:4 * CHUNK]) == \
                expected_bytes(0, 4 * CHUNK)
        finally:
            sess.close()
            d2.close()
    finally:
        d1.close()


def test_kv_pool_over_daemon_qos(daemon, tmp_path):
    """The shared KV pool speaks the same admission/QoS path as DMA:
    append/read/write/resume/release round-trip byte-identically through
    stromd with a paired-mirror fake spill."""
    bb = 16 << 10
    paths = []
    for i in range(4):
        p = str(tmp_path / f"spill{i}.bin")
        with open(p, "wb") as f:
            f.truncate(16 * bb)
        paths.append(p)
    with DaemonSession(daemon.socket_path, tenant="t-kv",
                       qos_class="latency") as sess:
        geo = sess.kv_open({"paths": paths, "stripe_chunk_size": bb,
                            "mirror": "paired"}, block_bytes=bb,
                           ram_blocks=4)
        assert geo["block_bytes"] == bb
        blobs = [bytes([i + 1]) * bb for i in range(8)]
        for i, b in enumerate(blobs):
            assert sess.kv_append("s0", b) == i
        res = sess.kv_residency()
        assert sum(res.values()) == 8 and res["ssd"] > 0
        for i, b in enumerate(blobs):
            assert sess.kv_read("s0", i) == b
        sess.kv_write("s0", 3, b"\xAB" * bb)
        assert sess.kv_read("s0", 3) == b"\xAB" * bb
        assert sess.kv_resume("s0") >= 0
        sess.kv_release("s0")
        assert sum(sess.kv_residency().values()) == 0
