"""Native engine (csrc/strom_engine.cc) tests: backend selection, direct ABI
use, error latching/retention, differential correctness vs the Python
backend, and concurrency stress."""

import ctypes
import errno
import mmap
import os
import random
import threading

import pytest

from nvme_strom_tpu import Session, StromError, config
from nvme_strom_tpu._native import NativeEngine, native_available
from nvme_strom_tpu.engine import PlainSource
from nvme_strom_tpu.testing import make_test_file
from nvme_strom_tpu.testing.fake import expected_bytes

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native engine not built")

CHUNK = 64 << 10


def _drop_cache(path):
    fd = os.open(path, os.O_RDWR)
    os.fsync(fd)
    os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    os.close(fd)


@pytest.mark.parametrize("change,stale", [
    (None, False), ("strom_tpu.h", True), ("strom_engine.cc", True),
    ("Makefile", True), ("no .so", True)])
def test_native_build_staleness(tmp_path, monkeypatch, change, stale):
    """The loader rebuilds a .so that is missing or older than any of
    the csrc/ files it is built from — a copied-along build is not
    trusted just because it exists."""
    from nvme_strom_tpu import _native
    csrc = tmp_path / "csrc"
    csrc.mkdir()
    so = tmp_path / "libstrom_tpu.so"
    so.write_text("")
    for f in _native._SOURCES:
        (csrc / f).write_text("")
        os.utime(csrc / f, (1000, 1000))
    os.utime(so, (2000, 2000))
    if change == "no .so":
        so.unlink()
    elif change:
        os.utime(csrc / change, (3000, 3000))
    monkeypatch.setattr(_native, "_SO", str(so))
    monkeypatch.setattr(_native, "_CSRC", str(csrc))
    assert _native._stale() is stale


# ---------------------------------------------------------------------------
# direct ABI
# ---------------------------------------------------------------------------

def test_backend_selection():
    eng = NativeEngine("auto", 32)
    assert eng.backend_name in ("io_uring", "threadpool")
    eng.close()
    eng = NativeEngine("threadpool", 8)
    assert eng.backend_name == "threadpool"
    eng.close()


@pytest.mark.parametrize("backend", ["io_uring", "threadpool"])
def test_native_read_correct(tmp_data_file, backend):
    try:
        eng = NativeEngine(backend, 16)
    except StromError:
        pytest.skip(f"{backend} unavailable")
    fd = os.open(tmp_data_file, os.O_RDONLY | os.O_DIRECT)
    buf = mmap.mmap(-1, 1 << 20)
    try:
        addr = ctypes.addressof(ctypes.c_char.from_buffer(buf))
        # 4 requests of 256KB, shuffled dest slots
        reqs = [(fd, i * (256 << 10), 256 << 10, ((i + 2) % 4) * (256 << 10))
                for i in range(4)]
        tid = eng.submit(addr, reqs)
        eng.wait(tid, 10000)
        for i in range(4):
            got = buf[((i + 2) % 4) * (256 << 10):((i + 2) % 4 + 1) * (256 << 10)]
            assert got == expected_bytes(i * (256 << 10), 256 << 10), f"req {i}"
    finally:
        os.close(fd)
        eng.close()
        buf.close()


def test_native_error_latched_and_retained():
    eng = NativeEngine("auto", 8)
    buf = mmap.mmap(-1, 1 << 20)
    try:
        addr = ctypes.addressof(ctypes.c_char.from_buffer(buf))
        bad_fd = os.open("/dev/null", os.O_RDONLY)
        os.close(bad_fd)  # guaranteed-invalid fd
        tid = eng.submit(addr, [(bad_fd, 0, 4096, 0)])
        with pytest.raises(StromError) as ei:
            eng.wait(tid, 10000)
        assert ei.value.errno == errno.EBADF
        # reaped by the failed wait
        with pytest.raises(StromError) as ei2:
            eng.wait(tid, 1000)
        assert ei2.value.errno == errno.ENOENT
    finally:
        eng.close()
        buf.close()


def test_native_failed_task_survives_until_reap():
    eng = NativeEngine("auto", 8)
    buf = mmap.mmap(-1, 1 << 20)
    try:
        addr = ctypes.addressof(ctypes.c_char.from_buffer(buf))
        bad_fd = 999999
        tid = eng.submit(addr, [(bad_fd, 0, 4096, 0)])
        # never wait; the failure must be retained in the table
        import time
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and tid not in eng.pending():
            time.sleep(0.01)
        assert tid in eng.pending()
        failed = eng.reap(timeout_ms=10000)
        assert tid in failed
        assert eng.pending() == []
    finally:
        eng.close()
        buf.close()


def test_native_wait_timeout_unknown():
    eng = NativeEngine("auto", 8)
    try:
        with pytest.raises(StromError) as ei:
            eng.wait(123456, 50)
        assert ei.value.errno == errno.ENOENT
    finally:
        eng.close()


def test_native_stats_counters(tmp_data_file):
    eng = NativeEngine("auto", 16)
    fd = os.open(tmp_data_file, os.O_RDONLY | os.O_DIRECT)
    buf = mmap.mmap(-1, 1 << 20)
    try:
        addr = ctypes.addressof(ctypes.c_char.from_buffer(buf))
        tid = eng.submit(addr, [(fd, 0, 256 << 10, 0), (fd, 256 << 10, 256 << 10, 256 << 10)])
        eng.wait(tid, 10000)
        s = eng.stats()
        assert s["nr_submit_dma"] == 2
        assert s["total_dma_length"] == 512 << 10
        assert s["nr_ssd2dev"] == 1          # one task completed
        assert s["nr_wait_dtask"] == 1
        assert s["cur_dma_count"] == 0
    finally:
        os.close(fd)
        eng.close()
        buf.close()


# ---------------------------------------------------------------------------
# write direction (IORING_OP_WRITE / pwrite; beyond the read-only reference)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["io_uring", "threadpool"])
def test_native_write_correct(tmp_path, backend):
    try:
        eng = NativeEngine(backend, 16)
    except StromError:
        pytest.skip(f"{backend} unavailable")
    path = str(tmp_path / "w.bin")
    with open(path, "wb") as f:
        f.write(b"\0" * (1 << 20))
    fd = os.open(path, os.O_RDWR | os.O_DIRECT)
    buf = mmap.mmap(-1, 1 << 20)
    try:
        pattern = bytes(random.Random(7).randbytes(1 << 20))
        buf[:] = pattern
        addr = ctypes.addressof(ctypes.c_char.from_buffer(buf))
        # 4 writes, shuffled: file block i comes from buffer slot (i+1)%4
        reqs = [(fd, i * (256 << 10), 256 << 10, ((i + 1) % 4) * (256 << 10))
                for i in range(4)]
        tid = eng.submit(addr, reqs, write=True)
        eng.wait(tid, 10000)
        s = eng.stats()
        assert s["nr_write_dma"] == 4
        assert s["total_write_length"] == 1 << 20
        with open(path, "rb") as f:
            got = f.read()
        for i in range(4):
            src = ((i + 1) % 4) * (256 << 10)
            assert got[i * (256 << 10):(i + 1) * (256 << 10)] == \
                pattern[src:src + (256 << 10)], f"block {i}"
    finally:
        os.close(fd)
        eng.close()
        buf.close()


def test_native_write_error_latched(tmp_path):
    eng = NativeEngine("auto", 8)
    path = str(tmp_path / "ro.bin")
    with open(path, "wb") as f:
        f.write(b"\0" * 8192)
    fd = os.open(path, os.O_RDONLY)  # write on a read-only fd must fail
    buf = mmap.mmap(-1, 8192)
    try:
        addr = ctypes.addressof(ctypes.c_char.from_buffer(buf))
        tid = eng.submit(addr, [(fd, 0, 8192, 0)], write=True)
        with pytest.raises(StromError) as ei:
            eng.wait(tid, 10000)
        assert ei.value.errno in (errno.EBADF, errno.EINVAL, errno.EPERM)
    finally:
        os.close(fd)
        eng.close()
        buf.close()


def test_session_ram2ssd_uses_native_write_queue(tmp_path):
    """The write leg must ride the native engine (GIL-free), not the
    Python thread pool: native write counters move after memcpy_ram2ssd."""
    from nvme_strom_tpu.engine import open_source

    path = str(tmp_path / "w.bin")
    with open(path, "wb") as f:
        f.write(b"\0" * (4 << 20))
    with open_source(path, writable=True) as sink, Session() as sess:
        if sess._native is None:
            pytest.skip("native engine not active in session")
        before = sess._native.stats()
        handle, buf = sess.alloc_dma_buffer(4 << 20)
        buf.view()[:] = bytes(random.Random(11).randbytes(4 << 20))
        res = sess.memcpy_ram2ssd(sink, handle, [2, 0, 3, 1], 1 << 20)
        sess.memcpy_wait(res.dma_task_id)
        after = sess._native.stats()
        assert after["nr_write_dma"] > before["nr_write_dma"]
        assert after["total_write_length"] - before["total_write_length"] \
            == 4 << 20


# ---------------------------------------------------------------------------
# differential: native session vs python session
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["io_uring", "threadpool", "python"])
def test_differential_backends(tmp_path, backend):
    path = str(tmp_path / "d.bin")
    make_test_file(path, 2 << 20)
    _drop_cache(path)
    ids = list(range((2 << 20) // CHUNK))
    random.Random(3).shuffle(ids)
    try:
        sess = Session(io_backend=backend)
    except StromError:
        pytest.skip(f"{backend} unavailable")
    with PlainSource(path) as src, sess:
        if backend != "python":
            assert sess.backend_name == backend
        handle, buf = sess.alloc_dma_buffer(len(ids) * CHUNK)
        res = sess.memcpy_ssd2ram(src, handle, ids, CHUNK)
        sess.memcpy_wait(res.dma_task_id)
        for slot, cid in enumerate(res.chunk_ids):
            assert bytes(buf.view()[slot * CHUNK:(slot + 1) * CHUNK]) == \
                expected_bytes(cid * CHUNK, CHUNK), f"{backend} chunk {cid}"


def test_native_session_misaligned_tail(tmp_path):
    """Native path + buffered tail fallback must compose."""
    path = str(tmp_path / "odd.bin")
    make_test_file(path, (1 << 20) + 777)
    _drop_cache(path)
    n = ((1 << 20) + 777 + CHUNK - 1) // CHUNK
    with PlainSource(path) as src, Session(io_backend="auto") as sess:
        handle, buf = sess.alloc_dma_buffer(n * CHUNK)
        res = sess.memcpy_ssd2ram(src, handle, list(range(n)), CHUNK)
        sess.memcpy_wait(res.dma_task_id)
        flat = bytes(buf.view())
        for slot, cid in enumerate(res.chunk_ids):
            size = min(CHUNK, (1 << 20) + 777 - cid * CHUNK)
            assert flat[slot * CHUNK:slot * CHUNK + size] == \
                expected_bytes(cid * CHUNK, size)


# ---------------------------------------------------------------------------
# stress
# ---------------------------------------------------------------------------

def test_native_concurrent_sessions_stress(tmp_path):
    """Many threads, many tasks, shared engine registry — races here crashed
    the reference's equivalent (its per-slot spinlock + RCU discipline,
    SURVEY.md SS5.2)."""
    path = str(tmp_path / "s.bin")
    make_test_file(path, 4 << 20)
    _drop_cache(path)
    errors = []

    def worker(seed):
        try:
            rng = random.Random(seed)
            with PlainSource(path) as src, Session() as sess:
                handle, buf = sess.alloc_dma_buffer(8 * CHUNK)
                for _ in range(5):
                    ids = rng.sample(range((4 << 20) // CHUNK), 8)
                    res = sess.memcpy_ssd2ram(src, handle, ids, CHUNK)
                    sess.memcpy_wait(res.dma_task_id, timeout=30)
                    for slot, cid in enumerate(res.chunk_ids):
                        if bytes(buf.view()[slot * CHUNK:(slot + 1) * CHUNK]) != \
                                expected_bytes(cid * CHUNK, CHUNK):
                            errors.append(f"seed {seed} chunk {cid} corrupt")
        except Exception as e:  # pragma: no cover
            errors.append(f"seed {seed}: {e!r}")

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:3]


# ---------------------------------------------------------------------------
# registered (fixed) buffers — the PRP-list-pool analog
# ---------------------------------------------------------------------------

def test_fixed_buffer_register_read_unregister(tmp_data_file):
    """Requests into a registered region ride READ_FIXED (counter moves),
    bytes still correct; slots recycle after unregister."""
    try:
        eng = NativeEngine("io_uring", 16)
    except StromError:
        pytest.skip("io_uring unavailable")
    fd = os.open(tmp_data_file, os.O_RDONLY | os.O_DIRECT)
    buf = mmap.mmap(-1, 1 << 20)
    try:
        addr = ctypes.addressof(ctypes.c_char.from_buffer(buf))
        slot = eng.buf_register(addr, 1 << 20)
        if slot is None:
            pytest.skip("fixed buffers unsupported on this kernel")
        reqs = [(fd, i * (256 << 10), 256 << 10, i * (256 << 10))
                for i in range(4)]
        tid = eng.submit(addr, reqs)
        eng.wait(tid, 10000)
        assert bytes(buf[:1 << 20]) == expected_bytes(0, 1 << 20)
        assert eng.stats()["nr_fixed_dma"] == 4
        eng.buf_unregister(slot)
        # slot is reusable and non-registered reads still work
        assert eng.buf_register(addr, 1 << 20) == slot
        tid = eng.submit(addr, [(fd, 0, 64 << 10, 0)])
        eng.wait(tid, 10000)
    finally:
        os.close(fd)
        eng.close()
        buf.close()


def test_fixed_buffer_outside_region_falls_back(tmp_data_file):
    """A destination not inside any registered region uses the plain
    opcode — same bytes, counter unmoved."""
    try:
        eng = NativeEngine("io_uring", 16)
    except StromError:
        pytest.skip("io_uring unavailable")
    fd = os.open(tmp_data_file, os.O_RDONLY | os.O_DIRECT)
    reg = mmap.mmap(-1, 64 << 10)
    other = mmap.mmap(-1, 256 << 10)
    try:
        reg_addr = ctypes.addressof(ctypes.c_char.from_buffer(reg))
        if eng.buf_register(reg_addr, 64 << 10) is None:
            pytest.skip("fixed buffers unsupported on this kernel")
        addr = ctypes.addressof(ctypes.c_char.from_buffer(other))
        tid = eng.submit(addr, [(fd, 0, 256 << 10, 0)])
        eng.wait(tid, 10000)
        assert bytes(other[:256 << 10]) == \
            expected_bytes(0, 256 << 10)
        assert eng.stats()["nr_fixed_dma"] == 0
    finally:
        os.close(fd)
        eng.close()
        reg.close()
        other.close()


def test_session_ssd2ram_rides_fixed_path(tmp_path):
    """Session.alloc_dma_buffer registers the buffer; a ssd2ram memcpy on
    the io_uring backend reports fixed-path requests in the stats debug
    counter, and unregistration follows the buffer's close."""
    path = str(tmp_path / "fixed_sess.bin")
    make_test_file(path, 1 << 20)
    _drop_cache(path)
    config.set("io_backend", "io_uring")
    try:
        with Session() as s:
            if s.backend_name != "io_uring":
                pytest.skip("io_uring unavailable")
            h, buf = s.alloc_dma_buffer(1 << 20)
            with PlainSource(path) as src:
                res = s.memcpy_ssd2ram(src, h, list(range(16)), CHUNK)
                s.memcpy_wait(res.dma_task_id)
            assert bytes(buf.view()[:1 << 20]) == \
                expected_bytes(0, 1 << 20)
            d = s._native.stats()
            if d.get("nr_fixed_dma", 0) == 0:
                pytest.skip("fixed buffers unsupported on this kernel")
            key = id(buf)
            slot = s._fixed_regs[key][0]
            assert slot >= 0
            buf.close()   # close callback releases the registration
            assert key not in s._fixed_regs
            # the slot is free again: a new buffer can take it
            h2, buf2 = s.alloc_dma_buffer(1 << 20)
            assert s._fixed_regs[id(buf2)][0] == slot
            buf2.close()
    finally:
        config.set("io_backend", "auto")


def test_session_close_detaches_pool_buffer_callbacks(tmp_path):
    """Closed sessions must not accumulate in a long-lived pool buffer's
    close-callback list (review finding)."""
    from nvme_strom_tpu.engine import DmaBuffer
    buf = DmaBuffer(1 << 20)
    try:
        for _ in range(3):
            with Session(io_backend="auto") as s:
                s.map_buffer(buf.view(), kind="pinned_host", backing=buf)
        assert len(buf._close_cbs) == 0
    finally:
        buf.close()
