"""Zero-copy landing tests (ISSUE 8, `landing` marker).

The tentpole contract: on an eligible command the engine's reads land
directly in an owned :class:`LandingBuffer` the device array aliases —
no staging hop — with per-command fallback to the staged ring recorded
by reason.  Covers plan-time eligibility routing, the partial-tail slot
riding both paths, fixed-buffer re-registration across a mid-task lane
scale-out, `_old_engines` drain at close, bytes touched per byte
delivered on the direct path, and direct-vs-staged byte identity down
the fault ladder (transient EIO, a torn read, hedged legs).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nvme_strom_tpu import Session, StromError, config, stats
from nvme_strom_tpu.engine import PlainSource, StripedSource
from nvme_strom_tpu.hbm import (HbmRegistry, StagingPipeline,
                                load_file_to_device, plan_landing)
from nvme_strom_tpu.stats import bytes_touched_ratio
from nvme_strom_tpu.testing import (FakeNvmeSource, FakeStripedNvmeSource,
                                    FaultPlan, make_test_file)

pytestmark = pytest.mark.landing

CHUNK = 256 << 10


def _counters():
    return dict(stats.snapshot(reset_max=False).counters)


def _delta(before):
    after = _counters()
    return {k: after.get(k, 0) - before.get(k, 0) for k in after}


def _pipeline_load(sess, reg, source, nbytes, chunk, *, dtype=jnp.uint8):
    """One pipeline command covering the destination exactly; returns
    (result, device bytes)."""
    n_elems = nbytes // np.dtype(dtype).itemsize
    handle = reg.map_device_memory(n_elems, dtype=dtype)
    try:
        with StagingPipeline(sess, hbm_registry=reg) as pipe:
            res = pipe.memcpy_ssd2dev(
                source, handle, list(range((nbytes + chunk - 1) // chunk)),
                chunk, device_dtype=dtype)
        got = np.asarray(reg.get(handle).array).tobytes()
    finally:
        reg.unmap(handle)
    return res, got


# ---------------------------------------------------------------------------
# eligibility routing + counters
# ---------------------------------------------------------------------------

def test_eligible_command_lands_direct(tmp_path):
    """landing=auto on the CPU backend, exact-cover command: the direct
    path is taken, counted, and delivers the file's bytes."""
    size = 4 * CHUNK
    path = str(tmp_path / "d.bin")
    make_test_file(path, size)
    before = _counters()
    reg = HbmRegistry()
    with Session() as sess, PlainSource(path) as src:
        res, got = _pipeline_load(sess, reg, src, size, CHUNK)
    assert res.landing == "direct"
    with open(path, "rb") as f:
        assert got == f.read()
    d = _delta(before)
    assert d.get("nr_landing_direct", 0) == 1
    assert d.get("nr_landing_staged", 0) == 0
    assert d.get("nr_landing_fallback", 0) == 0


def test_partial_tail_rides_the_direct_path(tmp_path):
    """A non-multiple source tail lands as a partial final slot on the
    direct path too (its own single-chunk engine command)."""
    size = 2 * CHUNK + 4096
    path = str(tmp_path / "t.bin")
    make_test_file(path, size)
    reg = HbmRegistry()
    with Session() as sess, PlainSource(path) as src:
        res, got = _pipeline_load(sess, reg, src, size, CHUNK)
    assert res.landing == "direct"
    assert res.nr_chunks == 3
    with open(path, "rb") as f:
        assert got == f.read()


def test_landing_config_staged_pins_the_ring(tmp_path):
    """landing=staged is an operator override, not a fallback: the ring
    is used and no fallback counter fires."""
    size = 2 * CHUNK
    path = str(tmp_path / "s.bin")
    make_test_file(path, size)
    config.set("landing", "staged")
    before = _counters()
    reg = HbmRegistry()
    with Session() as sess, PlainSource(path) as src:
        res, got = _pipeline_load(sess, reg, src, size, CHUNK)
    assert res.landing == "staged"
    with open(path, "rb") as f:
        assert got == f.read()
    d = _delta(before)
    assert d.get("nr_landing_staged", 0) >= 1
    assert d.get("nr_landing_fallback", 0) == 0


def test_fallback_reasons_are_attributed(tmp_path):
    """Ineligible commands fall back to the ring with the cause counted:
    a destination the command does not cover exactly is 'alignment', a
    dtype the geometry cannot express is 'dtype'."""
    size = 2 * CHUNK
    path = str(tmp_path / "f.bin")
    make_test_file(path, size)
    reg = HbmRegistry()
    with Session() as sess, PlainSource(path) as src:
        # oversized destination: command covers a prefix only
        before = _counters()
        handle = reg.map_device_memory(size + CHUNK)
        try:
            with StagingPipeline(sess, hbm_registry=reg) as pipe:
                res = pipe.memcpy_ssd2dev(src, handle, [0, 1], CHUNK)
        finally:
            reg.unmap(handle)
        assert res.landing == "staged"
        d = _delta(before)
        assert d.get("nr_landing_fallback", 0) == 1
        assert d.get("nr_landing_fallback_alignment", 0) == 1

        # 2D destination: geometry the alias cannot express (the ring
        # lands it row-addressed)
        before = _counters()
        arr2d = jax.device_put(jnp.zeros((2, CHUNK), dtype=jnp.uint8))
        handle = reg.map_device_memory(arr2d)
        try:
            with StagingPipeline(sess, staging_bytes=CHUNK,
                                 hbm_registry=reg) as pipe:
                res = pipe.memcpy_ssd2dev(src, handle, [0, 1], CHUNK)
            got = np.asarray(reg.get(handle).array).tobytes()
        finally:
            reg.unmap(handle)
        assert res.landing == "staged"
        with open(path, "rb") as f:
            assert got == f.read()
        d = _delta(before)
        assert d.get("nr_landing_fallback", 0) == 1
        assert d.get("nr_landing_fallback_dtype", 0) == 1


def test_plan_landing_backend_reason():
    """A non-CPU destination platform routes staged with reason
    'backend' — accelerators pay a host→HBM copy either way and the ring
    overlaps it with in-flight DMA."""
    class _Dev:
        platform = "tpu"

    class _Arr:
        ndim, dtype, nbytes = 1, np.dtype(np.uint8), CHUNK

        def devices(self):
            return [_Dev()]

    class _Hbm:
        array = _Arr()

    mode, why = plan_landing(_Hbm(), [0], CHUNK, 0, jnp.uint8, CHUNK)
    assert (mode, why) == ("staged", "backend")


# ---------------------------------------------------------------------------
# fixed-buffer lifetime across an engine rebuild
# ---------------------------------------------------------------------------

class _DirectStripe(StripedSource):
    """Freshly-written members are fully page-cached; forcing the
    verdict keeps every chunk on the direct/native path."""

    def cached_fraction(self, offset, length):
        return 0.0


def _expected_stream(paths, stripe_chunk):
    parts = [open(p, "rb").read() for p in paths]
    nm = len(parts)
    total = sum(len(p) for p in parts)
    out = bytearray(total)
    for i in range(total // stripe_chunk):
        m, row = i % nm, i // nm
        out[i * stripe_chunk:(i + 1) * stripe_chunk] = \
            parts[m][row * stripe_chunk:(row + 1) * stripe_chunk]
    return bytes(out)


def test_fixed_registration_survives_lane_scale_out(tmp_path):
    """The first striped submit of a direct-landing command swaps the
    native engine mid-task (one lane → one per member).  The landing
    buffer's fixed registration must carry to the new engine, the bytes
    must stay identical, and close() must drain the retired engine."""
    nmem, msize, stripe = 4, 512 << 10, 128 << 10
    paths = []
    for m in range(nmem):
        p = str(tmp_path / f"lm{m}.bin")
        make_test_file(p, msize, seed=m)
        paths.append(p)
    total = nmem * msize
    src = _DirectStripe(paths, stripe_chunk_size=stripe)
    reg = HbmRegistry()
    sess = Session()
    try:
        if sess._native is None:
            pytest.skip("native engine not active")
        assert sess._native.nlanes() == 1
        handle = reg.map_device_memory(total)
        try:
            with StagingPipeline(sess, hbm_registry=reg) as pipe:
                res = pipe.memcpy_ssd2dev(
                    src, handle, list(range(total // stripe)), stripe)
            assert res.landing == "direct"
            # the submit scaled the engine out mid-command...
            assert sess._native.nlanes() == nmem
            assert len(sess._old_engines) >= 1
            # ...and the landing buffer's fixed slot carried to the new
            # engine (the buffer is alive: the device array aliases it,
            # so unmap has not yet dropped the registration)
            if sess.backend_name == "io_uring":
                assert any(slot >= 0 for slot, _b, _cb in
                           sess._fixed_regs.values()), \
                    "no fixed registration survived the engine swap"
            got = np.asarray(reg.get(handle).array).tobytes()
        finally:
            reg.unmap(handle)
    finally:
        src.close()
        sess.close()
    assert got == _expected_stream(paths, stripe)
    assert sess._old_engines == [], "retired engines not drained at close"


# ---------------------------------------------------------------------------
# bytes touched per byte delivered
# ---------------------------------------------------------------------------

def test_direct_landing_touches_each_byte_once(tmp_path):
    """On the direct path the engine's reads land in the buffer the
    device array aliases: the command touches at most 1.05 bytes per
    byte delivered (no staging copy) and matches the staged ring."""
    size, chunk = 4 << 20, 1 << 20
    path = str(tmp_path / "ratio.bin")
    make_test_file(path, size)
    # a freshly written file is page-cached: arbitration would route
    # every chunk write-back and move no DMA
    config.set("cache_arbitration", False)
    reg = HbmRegistry()
    config.set("landing", "staged")
    with Session() as sess, PlainSource(path) as src:
        _res, staged = _pipeline_load(sess, reg, src, size, chunk)
    config.set("landing", "direct")
    before = _counters()
    with Session() as sess, PlainSource(path) as src:
        res, direct = _pipeline_load(sess, reg, src, size, chunk)
    d = _delta(before)
    assert res.landing == "direct"
    assert direct == staged
    assert d.get("nr_landing_direct", 0) >= 1
    assert d.get("nr_landing_fallback", 0) == 0
    ratio = bytes_touched_ratio(d)
    assert ratio is not None and ratio <= 1.05, d


# ---------------------------------------------------------------------------
# fault-ladder identity
# ---------------------------------------------------------------------------

def test_direct_vs_staged_identity_under_transient_faults(tmp_path):
    """Transient EIO every 3rd read: the retry tier heals both landing
    paths to the same bytes."""
    size = 1 << 20
    path = str(tmp_path / "fault.bin")
    make_test_file(path, size)

    def load(mode):
        config.set("landing", mode)
        src = FakeNvmeSource(path, fault_plan=FaultPlan(fail_every_nth=3),
                             force_cached_fraction=0.0)
        reg = HbmRegistry()
        try:
            with Session() as sess:
                res, got = _pipeline_load(sess, reg, src, size, CHUNK)
            assert res.landing == mode
        finally:
            src.close()
        return got

    staged, direct = load("staged"), load("direct")
    assert direct == staged
    with open(path, "rb") as f:
        assert direct == f.read()


def _striped_failstop(tmp_path):
    """Two striped members, every 3rd read EIOs once (transient)."""
    paths = []
    for m in range(2):
        p = str(tmp_path / f"tm{m}.bin")
        make_test_file(p, 1 << 20, seed=m)
        paths.append(p)
    return (lambda: FakeStripedNvmeSource(
        paths, stripe_chunk_size=CHUNK,
        fault_plan=FaultPlan(fail_every_nth=3),
        force_cached_fraction=0.0)), CHUNK, _expected_stream(paths, CHUNK)


def _corrupt_once(tmp_path):
    """A torn read that flips once and heals on re-read: the checksum
    re-read tier repairs it."""
    from nvme_strom_tpu.scan.heap import PAGE_SIZE, HeapSchema, build_heap_file

    config.set("checksum_verify", True)
    schema = HeapSchema(n_cols=2, visibility=False)
    n = schema.tuples_per_page * 8
    path = str(tmp_path / "co.heap")
    build_heap_file(path, [np.arange(n, dtype=np.int32),
                           (n - np.arange(n)).astype(np.int32)], schema)
    with open(path, "rb") as f:
        want = f.read()
    return (lambda: FakeNvmeSource(
        path,
        fault_plan=FaultPlan(corrupt_once_offsets={2 * PAGE_SIZE + 99}),
        force_cached_fraction=0.0)), PAGE_SIZE, want


def _hedged(tmp_path):
    """Hedged legs racing a slow member on a mirrored stripe."""
    from nvme_strom_tpu.testing.chaos import (expected_mirrored_stream,
                                              make_mirrored_members)

    chunk = 128 << 10
    paths = make_mirrored_members(str(tmp_path), n_pairs=1, size=1 << 20,
                                  tag="hm")
    config.set("hedge_policy", "fixed")
    config.set("hedge_ms", 2.0)
    return (lambda: FakeStripedNvmeSource(
        paths, stripe_chunk_size=chunk,
        fault_plan=FaultPlan(slow_member=1, slow_s=0.02),
        force_cached_fraction=0.0, mirror="paired")), chunk, \
        expected_mirrored_stream(paths, chunk)


@pytest.mark.parametrize("ladder", [_striped_failstop, _corrupt_once,
                                    _hedged],
                         ids=["striped_failstop", "corrupt_once", "hedged"])
def test_direct_vs_staged_identity_down_the_fault_ladder(tmp_path, ladder):
    """Each rung of the fault ladder heals both landing paths to the
    same bytes as the file's logical stream."""
    make_src, chunk, want = ladder(tmp_path)

    def load(mode):
        config.set("landing", mode)
        src = make_src()
        reg = HbmRegistry()
        try:
            with Session() as sess:
                res, got = _pipeline_load(sess, reg, src, src.size, chunk)
            assert res.landing == mode
        finally:
            src.close()
        return got

    staged, direct = load("staged"), load("direct")
    assert staged == want[:len(staged)]
    assert direct == staged
