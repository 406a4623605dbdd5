"""HBM bridge tests: device memory registry lifecycle (map/info/list/unmap,
revocation, ownership), staging pipeline correctness + overlap, and the
one-call loader.  Runs on the virtual CPU device mesh (conftest)."""

import errno

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nvme_strom_tpu import Session, StromError
from nvme_strom_tpu.engine import PlainSource
from nvme_strom_tpu.hbm import HbmRegistry, StagingPipeline, load_file_to_device
from nvme_strom_tpu.testing import make_test_file
from nvme_strom_tpu.testing.fake import expected_bytes

CHUNK = 64 << 10


@pytest.fixture()
def reg():
    return HbmRegistry()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_map_info_list_unmap(reg):
    h = reg.map_device_memory(1 << 20)
    info = reg.info(h)
    assert info.length == 1 << 20
    assert info.kind == "hbm"
    assert info.refcount == 0
    assert reg.list() == [h]
    reg.unmap(h)
    assert reg.list() == []
    with pytest.raises(StromError) as ei:
        reg.info(h)
    assert ei.value.errno == errno.ENOENT


def test_adopt_existing_array(reg):
    arr = jnp.arange(128, dtype=jnp.int32)
    h = reg.map_device_memory(arr)
    assert reg.info(h).length == 128 * 4
    reg.unmap(h)


def test_unmap_blocks_on_refcount(reg):
    h = reg.map_device_memory(4096)
    buf = reg.acquire(h)
    with pytest.raises(StromError) as ei:
        reg.unmap(h, timeout=0.05)
    assert ei.value.errno == errno.ETIMEDOUT
    reg.release(buf)
    reg.unmap(h)


def test_revoked_buffer_rejects_use(reg):
    h = reg.map_device_memory(4096)
    buf = reg.get(h)
    reg.unmap(h)
    with pytest.raises(StromError) as ei:
        _ = buf.array
    assert ei.value.errno == errno.ENODEV
    with pytest.raises(StromError):
        reg.acquire(h)


# ---------------------------------------------------------------------------
# staging pipeline
# ---------------------------------------------------------------------------

def test_pipeline_end_to_end(tmp_path, reg):
    path = str(tmp_path / "p.bin")
    make_test_file(path, 4 << 20)
    with PlainSource(path) as src, Session() as sess:
        h = reg.map_device_memory(4 << 20)
        with StagingPipeline(sess, staging_bytes=512 << 10, hbm_registry=reg) as pipe:
            res = pipe.memcpy_ssd2dev(src, h, list(range(64)), CHUNK)
        assert res.nr_chunks == 64
        arr = np.asarray(reg.get(h).array)
        for slot, cid in enumerate(res.chunk_ids):
            got = arr[slot * CHUNK:(slot + 1) * CHUNK].tobytes()
            assert got == expected_bytes(cid * CHUNK, CHUNK), f"chunk {cid}"
        reg.unmap(h)


def test_pipeline_out_of_order_and_offset(tmp_path, reg):
    path = str(tmp_path / "p2.bin")
    make_test_file(path, 1 << 20)
    ids = [7, 1, 12, 3]
    with PlainSource(path) as src, Session() as sess:
        h = reg.map_device_memory((len(ids) + 2) * CHUNK)
        with StagingPipeline(sess, staging_bytes=2 * CHUNK, hbm_registry=reg) as pipe:
            res = pipe.memcpy_ssd2dev(src, h, ids, CHUNK, dest_offset=2 * CHUNK)
        arr = np.asarray(reg.get(h).array)
        assert not arr[:2 * CHUNK].any()  # untouched region stays zero
        for slot, cid in enumerate(res.chunk_ids):
            start = 2 * CHUNK + slot * CHUNK
            assert arr[start:start + CHUNK].tobytes() == \
                expected_bytes(cid * CHUNK, CHUNK)
        reg.unmap(h)


def test_pipeline_partial_chunk_only_last(tmp_path, reg):
    """ISSUE 8 relaxed the full-chunk constraint: a partial chunk is
    legal ONLY in the final slot (it stages/lands a partial slot); a
    partial chunk anywhere else would hole the device layout and still
    raises EINVAL, as does a chunk entirely beyond EOF."""
    path = str(tmp_path / "p3.bin")
    size = CHUNK + 512
    make_test_file(path, size)
    with PlainSource(path) as src, Session() as sess:
        h = reg.map_device_memory(4 * CHUNK)
        with StagingPipeline(sess, staging_bytes=2 * CHUNK, hbm_registry=reg) as pipe:
            # partial chunk 1 NOT in the final slot: rejected
            with pytest.raises(StromError) as ei:
                pipe.memcpy_ssd2dev(src, h, [1, 0], CHUNK)
            assert ei.value.errno == errno.EINVAL
            # chunk beyond EOF: rejected
            with pytest.raises(StromError) as ei:
                pipe.memcpy_ssd2dev(src, h, [0, 2], CHUNK)
            assert ei.value.errno == errno.EINVAL
            # partial chunk in the final slot: stages a partial slot
            res = pipe.memcpy_ssd2dev(src, h, [0, 1], CHUNK)
        assert res.nr_chunks == 2
        arr = np.asarray(reg.get(h).array)
        assert arr[:CHUNK].tobytes() == expected_bytes(0, CHUNK)
        assert arr[CHUNK:size].tobytes() == expected_bytes(CHUNK, 512)
        assert not arr[size:].any()   # beyond the tail stays zero
        reg.unmap(h)


def test_pipeline_device_buffer_too_small(tmp_path, reg):
    path = str(tmp_path / "p4.bin")
    make_test_file(path, 1 << 20)
    with PlainSource(path) as src, Session() as sess:
        h = reg.map_device_memory(CHUNK)
        with StagingPipeline(sess, staging_bytes=2 * CHUNK, hbm_registry=reg) as pipe:
            with pytest.raises(StromError) as ei:
                pipe.memcpy_ssd2dev(src, h, [0, 1, 2], CHUNK)
            assert ei.value.errno == errno.ERANGE
        reg.unmap(h)


def test_pipeline_refcount_during_copy(tmp_path, reg):
    path = str(tmp_path / "p5.bin")
    make_test_file(path, 1 << 20)
    with PlainSource(path) as src, Session() as sess:
        h = reg.map_device_memory(1 << 20)
        with StagingPipeline(sess, staging_bytes=512 << 10, hbm_registry=reg) as pipe:
            pipe.memcpy_ssd2dev(src, h, list(range(16)), CHUNK)
        assert reg.info(h).refcount == 0  # released after the command
        reg.unmap(h)


# ---------------------------------------------------------------------------
# loader
# ---------------------------------------------------------------------------

def test_load_file_to_device(tmp_path, reg):
    path = str(tmp_path / "f.bin")
    make_test_file(path, 2 << 20)
    with PlainSource(path) as src:
        arr = load_file_to_device(src, chunk_size=256 << 10,
                                  staging_bytes=512 << 10, hbm_registry=reg)
    assert arr.shape == (2 << 20,)
    assert bytes(np.asarray(arr).tobytes()) == expected_bytes(0, 2 << 20)


def test_load_file_with_tail(tmp_path, reg):
    size = (1 << 20) + 24 * 1024  # tail of 24KB beyond the chunk grid
    path = str(tmp_path / "t.bin")
    make_test_file(path, size)
    with PlainSource(path) as src:
        arr = load_file_to_device(src, chunk_size=256 << 10,
                                  staging_bytes=512 << 10, hbm_registry=reg)
    assert arr.shape == (size,)
    assert np.asarray(arr).tobytes() == expected_bytes(0, size)


def test_load_as_int32(tmp_path, reg):
    path = str(tmp_path / "i.bin")
    make_test_file(path, 1 << 20)
    with PlainSource(path) as src:
        arr = load_file_to_device(src, chunk_size=256 << 10, dtype=jnp.int32,
                                  staging_bytes=512 << 10, hbm_registry=reg)
    assert arr.dtype == jnp.int32
    assert arr.shape == ((1 << 20) // 4,)
    want = np.frombuffer(expected_bytes(0, 1 << 20), dtype=np.int32)
    np.testing.assert_array_equal(np.asarray(arr), want)


def test_device_choice_is_never_silently_device_0():
    """An out-of-range device index raises instead of landing on device
    0, and a registered destination is allocated on the device asked
    for (conftest gives 8 virtual CPU devices)."""
    import errno

    import jax

    from nvme_strom_tpu.hbm import HbmRegistry
    from nvme_strom_tpu.hbm.staging import default_device

    n = len(jax.local_devices())
    assert default_device(n - 1) == jax.local_devices()[n - 1]
    with pytest.raises(StromError) as ei:
        default_device(n)
    assert ei.value.errno == errno.ENODEV
    reg = HbmRegistry()
    dev = jax.devices()[3]
    h = reg.map_device_memory(1024, device=dev)
    assert reg.get(h).array.devices() == {dev}
    reg.unmap(h)


@pytest.mark.parametrize("path", ["plain", "auto"])
def test_h2d_transfer_paths_agree(path):
    """h2d_path plain and auto move identical bytes."""
    import jax

    from nvme_strom_tpu import config
    from nvme_strom_tpu.hbm.staging import h2d_transfer

    dev = jax.devices()[0]
    a = np.arange(1 << 14, dtype=np.uint8)
    config.set("h2d_path", path)
    d, fence = h2d_transfer(a, dev)
    np.testing.assert_array_equal(np.asarray(d), a)
    jax.block_until_ready(fence)


def test_pinned_host_path_refuses_where_unsupported(tmp_path):
    """h2d_path=pinned_host on a runtime that cannot lower the
    pinned_host->device copy (the CPU backend) fails with ENOTSUP, both
    per transfer and through the whole staging pipeline — it never
    quietly takes the plain path."""
    import errno

    import jax

    from nvme_strom_tpu import Session, StromError, config, open_source
    from nvme_strom_tpu.hbm.staging import h2d_transfer, load_file_to_device
    from nvme_strom_tpu.testing.fake import make_test_file

    p = str(tmp_path / "pin.bin")
    make_test_file(p, 2 << 20)
    config.set("h2d_path", "pinned_host")
    config.set("landing", "staged")   # CPU would otherwise land zero-copy
    with pytest.raises(StromError) as ei:
        h2d_transfer(np.zeros(16, np.uint8), jax.devices()[0])
    assert ei.value.errno == errno.ENOTSUP
    with open_source(p) as src, Session() as s:
        with pytest.raises(StromError) as ei:
            load_file_to_device(src, chunk_size=256 << 10, session=s)
    assert ei.value.errno == errno.ENOTSUP


def test_adaptive_h2d_depth_grows_and_decays():
    """The shared depth policy (VERDICT r3 #6): blocking fences deepen
    the pipeline, a streak of fence-free retirements DECAYS it back, so
    a closed burst window releases its pinned chunks; floor and cap are
    both honored."""
    from nvme_strom_tpu.hbm.staging import AdaptiveH2DDepth

    ad = AdaptiveH2DDepth(6)
    assert ad.depth == 2
    blocked = ad.BLOCK_NS + 1
    for want in (3, 4, 5, 6):
        ad.observe(blocked)
        assert ad.depth == want
    ad.observe(blocked)
    assert ad.depth == 6            # capped
    # decay: decay_after consecutive non-blocking fences shrink by one
    for _ in range(ad.decay_after - 1):
        ad.observe(0)
    assert ad.depth == 6            # streak not complete yet
    ad.observe(0)
    assert ad.depth == 5
    # one blocking fence resets the streak and regrows
    ad.observe(0)
    ad.observe(blocked)
    assert ad.depth == 6
    # sustained regime: decays all the way to the floor, never below
    for _ in range(100):
        ad.observe(0)
    assert ad.depth == 2
    # degenerate cap: pinned to 1, grow and decay are both no-ops
    ad1 = AdaptiveH2DDepth(1)
    assert ad1.depth == 1
    ad1.observe(blocked)
    assert ad1.depth == 1
    for _ in range(10):
        ad1.observe(0)
    assert ad1.depth == 1


def test_pinned_ring_window_adapts(tmp_path):
    """The checkpoint restore ring rotates through an adaptive window:
    it starts at 2 (not the full h2d_depth_max allocation) and its
    policy is the shared AdaptiveH2DDepth instance."""
    from nvme_strom_tpu.data.checkpoint import _PinnedRing

    with Session() as s:
        ring = _PinnedRing(s, 1 << 16)
        try:
            assert ring.bufs == []          # nothing pinned until used
            assert ring.adaptive.depth == 2
            seen = set()
            for _ in range(6):   # CPU fences never block -> window stays 2
                ring.next_buf()
                seen.add(ring.cur)
            assert seen == {0, 1}
            # pinned memory tracks the window high-water, not
            # h2d_depth_max (lazy allocation)
            assert len(ring.bufs) == 2
        finally:
            ring.close()


def test_backend_loss_fails_staging_and_revokes(tmp_path):
    """VERDICT r3 #5: a dead/wedged device backend (injected at the H2D
    fence) makes in-flight staging FAIL with ENODEV — promptly, via the
    bounded fence — instead of hanging; registered HBM buffers revoke
    with ENODEV; the session survives for CPU-side work; strom_check
    reports the latched state."""
    import time as _time

    from nvme_strom_tpu import config, open_source
    from nvme_strom_tpu.hbm.backend import monitor
    from nvme_strom_tpu.hbm.registry import registry
    from nvme_strom_tpu.testing import backend_fault
    from nvme_strom_tpu.tools.strom_check import check_backend_latch

    path = str(tmp_path / "loss.bin")
    make_test_file(path, 1 << 20)
    old_t = config.get("backend_fence_timeout")
    config.set("backend_fence_timeout", 0.2)
    try:
        with open_source(path) as src, Session() as s:
            handle = registry.map_device_memory(1 << 20)
            pipe = StagingPipeline(s, n_buffers=2,
                                   staging_bytes=256 << 10)
            try:
                with backend_fault(mode="hang", hang_s=5.0):
                    t0 = _time.monotonic()
                    with pytest.raises(StromError) as ei:
                        pipe.memcpy_ssd2dev(src, handle,
                                            list(range(4)), 256 << 10)
                    assert ei.value.errno == errno.ENODEV
                    # bounded: seconds, not the injected 5s hang per fence
                    assert _time.monotonic() - t0 < 3.0
                    assert monitor.lost() is not None
                    # the registered buffer is revoked with ENODEV
                    buf = registry.get(handle)
                    with pytest.raises(StromError) as e2:
                        buf.array
                    assert e2.value.errno == errno.ENODEV
                    with pytest.raises(StromError) as e3:
                        registry.acquire(handle)
                    assert e3.value.errno == errno.ENODEV
                    # the doctor reports the latched state
                    assert check_backend_latch() is False
                    # no orphaned engine tasks: everything was reaped
                    assert s.pending_tasks() == []
                    # the engine itself survives for CPU-side work
                    h2, b2 = s.alloc_dma_buffer(256 << 10)
                    res = s.memcpy_ssd2ram(src, h2, [0], 256 << 10)
                    s.memcpy_wait(res.dma_task_id)
                    s.unmap_buffer(h2)
                    b2.close()
                    # revoked handles unmap immediately (nothing to drain)
                    registry.unmap(handle)
                    assert handle not in registry.list()
            finally:
                pipe.close()
        # context exit resets the latch; the doctor is green again
        assert monitor.lost() is None
        assert check_backend_latch() is True
    finally:
        config.set("backend_fence_timeout", old_t)


def test_backend_error_mode_latches_loss(tmp_path):
    """A PJRT-style runtime ERROR from the fence (not a hang) latches
    the same loss path."""
    from nvme_strom_tpu import config, open_source
    from nvme_strom_tpu.hbm.backend import monitor
    from nvme_strom_tpu.hbm.registry import registry
    from nvme_strom_tpu.testing import backend_fault

    path = str(tmp_path / "losserr.bin")
    make_test_file(path, 1 << 20)
    with open_source(path) as src, Session() as s:
        handle = registry.map_device_memory(1 << 20)
        pipe = StagingPipeline(s, n_buffers=2, staging_bytes=256 << 10)
        try:
            with backend_fault(mode="error"):
                with pytest.raises(StromError) as ei:
                    pipe.memcpy_ssd2dev(src, handle, list(range(4)),
                                        256 << 10)
                assert ei.value.errno == errno.ENODEV
                assert "injected PJRT failure" in monitor.lost()
            registry.unmap(handle)
        finally:
            pipe.close()


def test_backend_loss_fails_scan_not_hangs(tmp_path):
    """The scan executor's deferred fences ride the same bounded path:
    an injected wedge fails scan_filter with ENODEV (no hang), and the
    scanner tears down cleanly."""
    import numpy as np

    from nvme_strom_tpu import config
    from nvme_strom_tpu.scan.executor import TableScanner
    from nvme_strom_tpu.scan.heap import HeapSchema, build_heap_file
    from nvme_strom_tpu.testing import backend_fault

    schema = HeapSchema(n_cols=2, visibility=True)
    rng = np.random.default_rng(5)
    n = schema.tuples_per_page * 64
    path = str(tmp_path / "scanloss.heap")
    build_heap_file(path, [rng.integers(-100, 100, n).astype(np.int32),
                           rng.integers(0, 50, n).astype(np.int32)],
                    schema)
    old_t = config.get("backend_fence_timeout")
    old_c = config.get("chunk_size")
    config.set("backend_fence_timeout", 0.2)
    config.set("chunk_size", 64 << 10)
    try:
        with backend_fault(mode="hang", hang_s=5.0):
            with TableScanner(path, schema, numa_bind=False) as sc:
                with pytest.raises(StromError) as ei:
                    sc.scan_filter(lambda pages: {"n": pages.shape[0]})
                assert ei.value.errno == errno.ENODEV
    finally:
        config.set("backend_fence_timeout", old_t)
        config.set("chunk_size", old_c)


def test_backend_loss_fails_mesh_stream_and_restore(tmp_path):
    """The remaining fence sites ride the bounded path too: an injected
    wedge fails the sharded mesh stream and a checkpoint restore with
    StromError (no hang), and both tear down cleanly."""
    import jax
    import numpy as np

    from nvme_strom_tpu import config, open_source
    from nvme_strom_tpu.data import restore_checkpoint, save_checkpoint
    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    from nvme_strom_tpu.parallel.stream import ShardedBatchStream
    from nvme_strom_tpu.scan.heap import PAGE_SIZE
    from nvme_strom_tpu.testing import backend_fault, make_test_file

    old_t = config.get("backend_fence_timeout")
    config.set("backend_fence_timeout", 0.2)
    try:
        # mesh stream: the double-buffer rotation fences from batch 2 on
        mesh = make_scan_mesh(jax.devices())
        dp = mesh.shape["dp"]
        path = str(tmp_path / "stream.bin")
        make_test_file(path, 8 * dp * 4 * PAGE_SIZE)
        with backend_fault(mode="hang", hang_s=5.0):
            with open_source(path) as src:
                with ShardedBatchStream(src, mesh,
                                        batch_pages=dp) as stream:
                    with pytest.raises(StromError) as ei:
                        for _first, _arr in stream:
                            pass
                    assert ei.value.errno == errno.ENODEV

        # checkpoint restore: the pinned ring fences once a buffer is
        # revisited (leaves larger than the window force rotation)
        ck = str(tmp_path / "loss.strom")
        save_checkpoint(ck, {"w": np.arange(1 << 16, dtype=np.float32)})
        with backend_fault(mode="hang", hang_s=5.0):
            with pytest.raises(StromError) as e2:
                restore_checkpoint(ck, staging_bytes=4096)
            assert e2.value.errno == errno.ENODEV
    finally:
        config.set("backend_fence_timeout", old_t)


def test_h2d_plain_path_single_host_copy():
    """Zero-extra-copy claim, host layer (VERDICT r3 #7 fallback): the
    plain h2d path performs exactly ONE host-side allocation of the
    transfer size — the CPU backend's deliberate owned copy
    (safe_device_put; an accelerator PJRT consumes the pinned pages
    directly via BufferFromHostBuffer, making even that one copy the DMA
    itself).  A second host-side staging copy in OUR layer would show as
    2x here; the device-side cost of the hop needs a chip run."""
    import tracemalloc

    from nvme_strom_tpu import config
    from nvme_strom_tpu.hbm.staging import h2d_transfer

    dev = jax.devices()[0]
    size = 8 << 20
    with Session() as s:
        h, buf = s.alloc_dma_buffer(size)
        host = np.frombuffer(buf.view(), np.uint8)
        host[:] = 7
        warm, _ = h2d_transfer(host[: 1 << 20], dev)   # compile/init
        jax.block_until_ready(warm)
        old = config.get("h2d_path")
        try:
            config.set("h2d_path", "plain")
            tracemalloc.start()
            d, fence = h2d_transfer(host, dev)
            jax.block_until_ready(fence)
            _cur, peak = tracemalloc.get_traced_memory()
            # lower bound keeps the measurement honest: if the owned
            # copy ever moves to an untraced allocator, this must FAIL
            # (a dead instrument reading 0 is not a zero-copy proof)
            assert size <= peak < size * 1.5, f"host copies: peak {peak}"
            np.testing.assert_array_equal(np.asarray(d)[:16], host[:16])
        finally:
            tracemalloc.stop()
            config.set("h2d_path", old)
        s.unmap_buffer(h)
        buf.close()
