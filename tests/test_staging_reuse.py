"""The staging ring's reuse order (``StagingPipeline.memcpy_ssd2dev``).

A batch is retired once ``n_buffers - 1`` reads are in flight, so at
every submit two buffers are free: the one whose host->device transfer
was just issued, and an older one.  The submit must refill the free
buffer whose transfer was issued earliest, so its reuse fence finds the
transfer done.  The moved bytes must not depend on the order: partial
tail slot, first-completed retire and positional ``chunk_ids`` hold at
every depth."""

import errno

import numpy as np
import pytest

from nvme_strom_tpu import Session, StromError, config
from nvme_strom_tpu.engine import PlainSource
from nvme_strom_tpu.hbm import HbmRegistry, StagingPipeline
from nvme_strom_tpu.hbm import staging
from nvme_strom_tpu.testing import make_test_file
from nvme_strom_tpu.testing.fake import expected_bytes

CHUNK = 64 << 10
TAIL = 512
#: 13 full chunks in batches of two, then the partial tail: 8 batches
SIZE = 13 * CHUNK + TAIL
DEPTHS = [2, 3, 4, 5]


class _Transfers:
    """Stands in for ``h2d_transfer``: lands the batch for real, but
    hands back a reuse fence that records which buffer it guards and
    reports not-ready until *lag* later transfers have been issued (never,
    before its fence, with ``lag=None``)."""

    def __init__(self, pipe, lag):
        self.addr = {np.frombuffer(buf.view(), np.uint8).ctypes.data: i
                     for i, (_, buf) in enumerate(pipe._bufs)}
        self.lag = lag
        self.issued = []          # fences in issue order
        self.fenced = []          # (fence, seqs of the unfenced ones)

    def __call__(self, host, dev):
        fence = _Fence(self, self.addr[host.ctypes.data], len(self.issued))
        self.issued.append(fence)
        return staging.safe_device_put(host, dev), fence


class _Fence:
    def __init__(self, log, buf, seq):
        self.log, self.buf, self.seq = log, buf, seq
        self.done = False

    def is_ready(self):
        lag = self.log.lag
        return self.done or (lag is not None
                             and len(self.log.issued) > self.seq + lag)

    def block_until_ready(self):
        self.done = True
        return self


def _counters(sess):
    return dict(sess.stat_info().counters)


@pytest.fixture()
def data_file(tmp_path):
    path = str(tmp_path / "ring.bin")
    make_test_file(path, SIZE)
    return path


def _check_bytes(arr, chunk_ids):
    """Entry i of *chunk_ids* is the chunk at device slot i."""
    for slot, cid in enumerate(chunk_ids):
        n = min(CHUNK, SIZE - cid * CHUNK)
        got = arr[slot * CHUNK:slot * CHUNK + n].tobytes()
        assert got == expected_bytes(cid * CHUNK, n), f"slot {slot}"


@pytest.mark.parametrize("lag", [1, None], ids=["done-next-cycle", "slow"])
@pytest.mark.parametrize("n_buffers", DEPTHS)
def test_reuse_fences_the_earliest_retired_buffer(data_file, monkeypatch,
                                                  n_buffers, lag):
    config.set("landing", "staged")
    reg = HbmRegistry()
    real_fence = staging.bounded_fence
    with PlainSource(data_file) as src, Session() as sess:
        handle = reg.map_device_memory(SIZE)
        pipe = StagingPipeline(sess, n_buffers=n_buffers,
                               staging_bytes=2 * CHUNK, hbm_registry=reg)
        log = _Transfers(pipe, lag)

        def fence(arr, what="h2d"):
            if what == "staging-reuse":
                log.fenced.append((arr, {f.seq for f in log.issued}
                                   - {f.seq for f, _ in log.fenced}))
            return real_fence(arr, what)

        monkeypatch.setattr(staging, "h2d_transfer", log)
        monkeypatch.setattr(staging, "bounded_fence", fence)
        try:
            before = _counters(sess)
            res = pipe.memcpy_ssd2dev(src, handle, list(range(14)), CHUNK)
            after = _counters(sess)
        finally:
            pipe.close()
        _check_bytes(np.asarray(reg.get(handle).array), res.chunk_ids)
        reg.unmap(handle)
    assert len(log.issued) == 8 and log.fenced
    for f, unfenced in log.fenced:
        # the free buffer whose transfer was issued earliest ...
        assert f.seq == min(unfenced)
        # ... which is never the one retired in this iteration (the
        # newest transfer, unfenced until a later iteration)
        assert f.seq < max(unfenced)
    fences = after["nr_staging_fence"] - before["nr_staging_fence"]
    blocked = (after["nr_staging_fence_blocked"]
               - before["nr_staging_fence_blocked"])
    assert fences == len(log.fenced)
    assert blocked <= fences
    # a transfer done within a cycle never blocks the refill; one that
    # is still running blocks every fence that reaches it
    assert blocked == (0 if lag == 1 else fences)


@pytest.mark.parametrize("n_buffers", DEPTHS)
def test_ring_bytes_identical_at_every_depth(data_file, monkeypatch,
                                             n_buffers):
    """Partial tail, first-completed retire and checksum verify, at
    each depth: the device holds the file, slot for slot."""
    config.set("landing", "staged")
    config.set("checksum_verify", True)
    reg = HbmRegistry()
    with PlainSource(data_file) as src, Session() as sess:
        submitted, retired, held = [], [], set()
        real_submit, real_wait = sess.memcpy_ssd2ram, sess.memcpy_wait

        def submit(*a, **kw):
            res = real_submit(*a, **kw)
            submitted.append(res.dma_task_id)
            if len(submitted) % 2:        # odd batches answer late once
                held.add(res.dma_task_id)
            return res

        def wait(task_id, timeout=None):
            if timeout == 0.0:
                if task_id in held:
                    held.discard(task_id)
                    raise StromError(errno.ETIMEDOUT, "held")
                timeout = None            # any other batch has completed
            res = real_wait(task_id, timeout=timeout)
            retired.append(task_id)
            return res

        monkeypatch.setattr(sess, "memcpy_ssd2ram", submit)
        monkeypatch.setattr(sess, "memcpy_wait", wait)
        handle = reg.map_device_memory(SIZE)
        with StagingPipeline(sess, n_buffers=n_buffers,
                             staging_bytes=2 * CHUNK,
                             hbm_registry=reg) as pipe:
            res = pipe.memcpy_ssd2dev(src, handle, list(range(14)), CHUNK)
        arr = np.asarray(reg.get(handle).array)
        reg.unmap(handle)
    assert res.nr_chunks == 14 and sorted(res.chunk_ids) == list(range(14))
    assert res.chunk_ids[-1] == 13             # the tail keeps its slot
    _check_bytes(arr, res.chunk_ids)
    assert sorted(retired) == sorted(submitted) and len(submitted) == 8
    # with two or more reads in flight a younger batch retires first
    assert (retired != submitted) == (n_buffers >= 3)
