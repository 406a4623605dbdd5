"""Raw NVMe passthrough tests (ISSUE 19, `passthru` marker).

Covers the raw-command data path hardware-free: the blockmap's
FIEMAP/synthetic extent resolution against the emulator's SLBA/NLB
oracle, per-extent eligibility splits for every refusing FIEMAP flag,
LBA alignment shaving, generation caching + write-ladder invalidation,
capability-probe refusal reasons down the failover ladder, the fault
ladder (hedge wins, member fail-stop/health) riding over passthrough
lanes, byte identity across a mixed passthrough + O_DIRECT split,
autotuner epochs on a passthrough workload, the zero-counters-when-pinned
guarantee, SLBA drift detection, and the emulator's command validation.
"""

import os

import pytest

from nvme_strom_tpu import Session, blockmap, config, open_source
from nvme_strom_tpu.api import StromError
from nvme_strom_tpu.stats import stats
from nvme_strom_tpu.testing import (FakeNvmeSource, FakeStripedNvmeSource,
                                    FaultPlan, make_test_file)
from nvme_strom_tpu.testing.fake import expected_bytes
from nvme_strom_tpu.testing.passthru_emu import (NVME_CMD_READ,
                                                 PassthruEmulator,
                                                 pack_uring_cmd)

pytestmark = pytest.mark.passthru

CHUNK = 64 << 10
LBA = 512


def _counter_delta(before, after, key):
    return after.counters.get(key, 0) - before.counters.get(key, 0)


def _base_config():
    config.set("cache_bytes", 0)
    config.set("cache_arbitration", False)
    config.set("dma_max_size", CHUNK)
    config.set("hedge_policy", "off")
    config.set("autotune", False)


def _read_pass(sess, src, nchunks, chunk=CHUNK):
    handle, buf = sess.alloc_dma_buffer(nchunks * chunk)
    try:
        res = sess.memcpy_ssd2ram(src, handle, list(range(nchunks)), chunk)
        sess.memcpy_wait(res.dma_task_id, timeout=60.0)
        return bytes(buf.view()[:nchunks * chunk])
    finally:
        sess.unmap_buffer(handle)


# ---------------------------------------------------------------------------
# blockmap resolution vs the emulator's SLBA/NLB oracle
# ---------------------------------------------------------------------------

def test_resolve_split_matches_emulator_oracle(tmp_path):
    """Every device run resolve_split emits must round-trip through the
    emulator's wire format to exactly the file bytes it claims — the
    LBA-math oracle the native submit path relies on."""
    size = 4 * CHUNK
    path = str(tmp_path / "oracle.bin")
    make_test_file(path, size)
    emu = PassthruEmulator(str(tmp_path / "oracle.img"))
    try:
        emu.provision(path, frag=4)
        runs = blockmap.resolve_split(path, 0, size, emu.lba_size)
        assert sum(ln for _fo, ln, _d in runs) == size
        assert all(dev is not None for _fo, _ln, dev in runs), \
            "fully-eligible provisioned file still produced refused runs"
        for fo, ln, dev in runs:
            buf = bytearray(ln)
            cmd = pack_uring_cmd(nsid=emu.nsid, slba=dev >> emu.lba_shift,
                                 nlb0=(ln >> emu.lba_shift) - 1, data_len=ln)
            got_path, got_off = emu.execute(cmd, memoryview(buf))
            assert (got_path, got_off) == (path, fo)
            assert bytes(buf) == expected_bytes(fo, ln)
    finally:
        emu.close()


@pytest.mark.parametrize("flag", sorted(
    {0x2: "unknown", 0x4: "delalloc", 0x8: "encoded", 0x80: "encrypted",
     0x100: "not_aligned", 0x200: "inline", 0x400: "tail",
     0x800: "unwritten"}))
def test_resolve_split_refuses_each_ineligible_flag(flag):
    """Each FIEMAP flag in the refusal mask forces its extent — and only
    its extent — off the passthrough lane."""
    path = "/synthetic/flags.bin"
    blockmap.register_synthetic(path, [
        blockmap.Extent(0, 1 << 20, CHUNK, 0),
        blockmap.Extent(CHUNK, (1 << 20) + CHUNK, CHUNK, flag),
        blockmap.Extent(2 * CHUNK, (1 << 20) + 2 * CHUNK, CHUNK, 0),
    ])
    try:
        runs = blockmap.resolve_split(path, 0, 3 * CHUNK, LBA)
        assert [(fo, ln, dev is not None) for fo, ln, dev in runs] == [
            (0, CHUNK, True), (CHUNK, CHUNK, False), (2 * CHUNK, CHUNK, True)]
        # whole-or-nothing resolve refuses any span touching the extent
        assert blockmap.resolve(path, 0, 3 * CHUNK, LBA) is None
        assert blockmap.resolve(path, 0, CHUNK, LBA) is not None
    finally:
        blockmap.unregister_synthetic(path)


def test_resolve_split_alignment_shaving():
    """Unaligned head/tail of an eligible extent are shaved onto the
    O_DIRECT lane at LBA boundaries in FILE space, so the refused
    neighbours stay alignment-legal."""
    path = "/synthetic/align.bin"
    blockmap.register_synthetic(path, [
        blockmap.Extent(0, 4096, 8192, 0)])
    try:
        runs = blockmap.resolve_split(path, 100, 2000, LBA)
        assert runs == [(100, 412, None), (512, 1536, 4096 + 512),
                        (2048, 52, None)]
        # a device-misaligned extent is refused whole
        blockmap.register_synthetic(path, [
            blockmap.Extent(0, 4096 + 7, 8192, 0)])
        assert blockmap.resolve_split(path, 0, 8192, LBA) == [
            (0, 8192, None)]
    finally:
        blockmap.unregister_synthetic(path)


def test_resolve_split_holes_ride_odirect():
    """A hole between extents (and past EOF) becomes a refused run; the
    whole-span resolve() refuses outright."""
    path = "/synthetic/hole.bin"
    blockmap.register_synthetic(path, [
        blockmap.Extent(0, 1 << 16, 4096, 0),
        blockmap.Extent(8192, (1 << 16) + 8192, 4096, 0)])
    try:
        runs = blockmap.resolve_split(path, 0, 16384, LBA)
        assert runs == [(0, 4096, 1 << 16), (4096, 4096, None),
                        (8192, 4096, (1 << 16) + 8192), (12288, 4096, None)]
        assert blockmap.resolve(path, 0, 16384, LBA) is None
    finally:
        blockmap.unregister_synthetic(path)


# ---------------------------------------------------------------------------
# generation cache + write-ladder invalidation
# ---------------------------------------------------------------------------

def test_generation_cache_and_out_of_band_writer(tmp_path):
    """A second map_file is served from the generation cache (no new
    walk); an out-of-band rewrite changes the generation key and forces
    a re-walk; invalidate() drops the entry and counts."""
    path = str(tmp_path / "gen.bin")
    make_test_file(path, CHUNK)
    if not blockmap.fiemap_supported(path):
        pytest.skip("filesystem without FIEMAP")
    blockmap.invalidate(path)
    before = stats.snapshot(reset_max=False)
    assert blockmap.map_file(path) is not None   # cold: walks
    assert blockmap.map_file(path) is not None   # cached: no walk
    mid = stats.snapshot(reset_max=False)
    assert _counter_delta(before, mid, "nr_blockmap_resolve") == 1
    os.truncate(path, CHUNK // 2)                # out-of-band writer
    assert blockmap.map_file(path) is not None   # generation changed: walks
    after = stats.snapshot(reset_max=False)
    assert _counter_delta(mid, after, "nr_blockmap_resolve") == 1
    blockmap.invalidate(path)
    end = stats.snapshot(reset_max=False)
    assert _counter_delta(after, end, "nr_blockmap_invalidate") == 1
    blockmap.invalidate(path)                    # already gone: no count
    assert _counter_delta(end, stats.snapshot(reset_max=False),
                          "nr_blockmap_invalidate") == 0


def test_writeback_invalidates_blockmap(tmp_path):
    """memcpy_ram2ssd rides the write-ladder contract: the sink's cached
    extent maps are dropped at the same site as the resident cache."""
    _base_config()
    path = str(tmp_path / "wb.bin")
    make_test_file(path, 2 * CHUNK)
    if not blockmap.fiemap_supported(path):
        pytest.skip("filesystem without FIEMAP")
    assert blockmap.map_file(path) is not None   # populate the cache
    before = stats.snapshot(reset_max=False)
    with Session() as sess:
        handle, buf = sess.alloc_dma_buffer(CHUNK)
        try:
            buf.view()[:CHUNK] = b"\xa5" * CHUNK
            with open_source(path, writable=True) as sink:
                res = sess.memcpy_ram2ssd(sink, handle, [0], CHUNK)
                sess.memcpy_wait(res.dma_task_id)
        finally:
            sess.unmap_buffer(handle)
    after = stats.snapshot(reset_max=False)
    assert _counter_delta(before, after, "nr_blockmap_invalidate") >= 1


# ---------------------------------------------------------------------------
# capability probe + failover ladder refusal reasons
# ---------------------------------------------------------------------------

def _native():
    from nvme_strom_tpu import _native as nat
    if not nat.native_available():
        pytest.skip("native engine unavailable")
    if nat.native_api_version() is not None \
            and nat.native_api_version() < 4:
        pytest.skip("native .so predates API v4")
    return nat


def test_probe_refusal_reasons(monkeypatch):
    nat = _native()
    monkeypatch.delenv("NSTPU_DISABLE_PASSTHRU", raising=False)
    assert nat.passthru_probe("/nonexistent/ng0n1") == -2      # nodev
    assert nat.passthru_probe(None) == -2
    monkeypatch.setenv("NSTPU_DISABLE_PASSTHRU", "1")
    assert nat.passthru_probe("/nonexistent/ng0n1") == -1      # disabled
    assert nat.PASSTHRU_REASONS[-1] == "disabled"
    assert nat.PASSTHRU_REASONS[-2] == "nodev"


def test_session_counts_ladder_refusal(monkeypatch):
    """A ladder that INCLUDED the passthru rung counts exactly why it
    fell on a host without the char device; the session still opens on
    a lower rung."""
    import glob
    nat = _native()
    monkeypatch.delenv("NSTPU_PASSTHRU_DEV", raising=False)
    monkeypatch.delenv("NSTPU_DISABLE_PASSTHRU", raising=False)
    if glob.glob(str(config.get("passthru_dev_glob"))):
        pytest.skip("host actually has an NVMe char device")
    _base_config()
    config.set("engine_backend", "auto")
    before = stats.snapshot(reset_max=False)
    with Session() as sess:
        assert sess.backend_name != "nvme_passthru"
    after = stats.snapshot(reset_max=False)
    assert _counter_delta(before, after, "nr_passthru_refusal_nodev") >= 1
    # demanding the rung falls back down the ladder, fallback counted
    config.set("engine_backend", "passthru")
    before = after
    with Session() as sess:
        assert sess.backend_name != "nvme_passthru"
    after = stats.snapshot(reset_max=False)
    assert (_counter_delta(before, after, "nr_passthru_fallback")
            + _counter_delta(before, after, "nr_passthru_refusal_nodev")
            + _counter_delta(before, after, "nr_passthru_refusal_disabled")
            ) >= 1


def test_disable_env_counts_disabled_reason(monkeypatch):
    nat = _native()
    monkeypatch.setenv("NSTPU_DISABLE_PASSTHRU", "1")
    _base_config()
    config.set("engine_backend", "auto")
    before = stats.snapshot(reset_max=False)
    with Session():
        pass
    after = stats.snapshot(reset_max=False)
    assert _counter_delta(before, after,
                          "nr_passthru_refusal_disabled") >= 1


# ---------------------------------------------------------------------------
# fault ladder over passthrough lanes
# ---------------------------------------------------------------------------

def _mirrored_emulated(tmp_path, plan):
    import shutil
    paths = []
    for k in range(2):
        p = str(tmp_path / f"m{2 * k}.bin")
        make_test_file(p, 4 * CHUNK, seed=50 + k)
        q = str(tmp_path / f"m{2 * k + 1}.bin")
        shutil.copyfile(p, q)
        paths += [p, q]
    emu = PassthruEmulator(str(tmp_path / "mirror.img"))
    for p in paths:
        emu.provision(p, frag=2)
    src = FakeStripedNvmeSource(paths, CHUNK, fault_plan=plan,
                                force_cached_fraction=0.0, mirror="paired")
    emu.attach(src)
    return paths, emu, src


def _mirrored_expected(paths):
    parts = [open(p, "rb").read() for p in paths[::2]]
    nm, total = len(parts), sum(len(p) for p in parts)
    out = bytearray(total)
    for i in range(total // CHUNK):
        m, row = i % nm, i // nm
        out[i * CHUNK:(i + 1) * CHUNK] = \
            parts[m][row * CHUNK:(row + 1) * CHUNK]
    return bytes(out)


def test_hedge_win_over_passthru_counts_lane_exit(tmp_path):
    """A hedged chunk whose slow primary rode the passthrough lane exits
    it when the hedge leg wins — counted, bytes identical."""
    _base_config()
    config.set("io_retries", 0)
    config.set("hedge_policy", "fixed")
    config.set("hedge_ms", 2.0)
    from nvme_strom_tpu.testing.chaos import read_all
    plan = FaultPlan(slow_member=0, slow_s=0.1)
    paths, emu, src = _mirrored_emulated(tmp_path, plan)
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            got, total = read_all(sess, src, chunk=CHUNK)
    finally:
        src.close()
        emu.close()
    after = stats.snapshot(reset_max=False)
    assert got == _mirrored_expected(paths)[:total]
    assert _counter_delta(before, after, "nr_hedge_won") >= 1
    assert _counter_delta(before, after, "nr_passthru_dma") >= 1
    assert _counter_delta(before, after, "nr_passthru_fallback") >= 1


def test_failstop_member_health_under_passthru(tmp_path):
    """A fail-stopped member's passthrough reads fall to the mirror rung
    and debit the health machine — passthrough never hides failures."""
    from nvme_strom_tpu.fault import HealthState
    _base_config()
    config.set("io_retries", 0)
    config.set("quarantine_after", 1)
    config.set("quarantine_s", 60.0)
    from nvme_strom_tpu.testing.chaos import read_all
    plan = FaultPlan(failstop_member=0, failstop_after=0)
    paths, emu, src = _mirrored_emulated(tmp_path, plan)
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            got, total = read_all(sess, src, chunk=CHUNK)
            assert sess._member_health.state(0) is not HealthState.HEALTHY
    finally:
        src.close()
        emu.close()
    after = stats.snapshot(reset_max=False)
    assert got == _mirrored_expected(paths)[:total]
    assert _counter_delta(before, after, "nr_passthru_fallback") >= 1
    assert _counter_delta(before, after, "nr_mirror_read") >= 1


def test_autotuner_epochs_on_passthru_lane(tmp_path):
    """The controller tunes a passthrough workload like any other: epochs
    observe traffic (no idle freeze), knobs move, bytes stay identical."""
    _base_config()
    config.set("autotune", True)
    config.set("submit_window", 2)
    size = 8 * CHUNK
    path = str(tmp_path / "tune.bin")
    make_test_file(path, size)
    emu = PassthruEmulator(str(tmp_path / "tune.img"))
    emu.provision(path, frag=2)
    src = FakeNvmeSource(path, fault_plan=FaultPlan(latency_s=0.002),
                         force_cached_fraction=0.0)
    emu.attach(src)
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            sess._tuner.stop()          # drive epochs synchronously
            for _ in range(6):
                got = _read_pass(sess, src, 8)
                assert got == expected_bytes(0, size)
                sess._tuner.step_epoch()
            hist = sess._tuner._climber.history
    finally:
        src.close()
        emu.close()
    after = stats.snapshot(reset_max=False)
    assert _counter_delta(before, after, "nr_passthru_dma") > 0
    assert any(ev for ep in hist for ev in ep), \
        "controller saw a passthrough workload but never acted"


def test_mixed_split_reads_byte_identical(tmp_path):
    """A fragmented file with an UNWRITTEN hole and an INLINE tail reads
    through the mixed passthrough + O_DIRECT plan: both lanes carry
    bytes and the result matches the O_DIRECT-only read and the
    generator oracle."""
    _base_config()
    nchunks = 8
    size = nchunks * CHUNK
    path = str(tmp_path / "split.bin")
    make_test_file(path, size)
    emu = PassthruEmulator(str(tmp_path / "split.img"))
    # FIEMAP flags 0x800 = UNWRITTEN, 0x200 = DATA_INLINE
    emu.provision(path, frag=4,
                  ineligible=((CHUNK, 4096, 0x800),
                              (5 * CHUNK + 512, 8192, 0x200)))
    try:
        with FakeNvmeSource(path, force_cached_fraction=0.0) as ref_src, \
                Session() as sess:
            odirect = _read_pass(sess, ref_src, nchunks)
        before = stats.snapshot(reset_max=False)
        src = FakeNvmeSource(path, force_cached_fraction=0.0)
        emu.attach(src)
        try:
            with Session() as sess:
                passthru = _read_pass(sess, src, nchunks)
        finally:
            src.close()
    finally:
        emu.close()
    after = stats.snapshot(reset_max=False)
    assert odirect == expected_bytes(0, size)
    assert passthru == odirect
    assert _counter_delta(before, after, "nr_passthru_dma") > 0
    assert _counter_delta(before, after, "nr_passthru_refused_extent") > 0
    assert 0 < _counter_delta(before, after, "bytes_passthru") < size


# ---------------------------------------------------------------------------
# zero-counters guarantee + drift + command validation
# ---------------------------------------------------------------------------

def _pinned_ladder_moves_zero_passthru_counters(tmp_path, backend):
    """engine_backend pinned below the passthrough rung, emulator
    attached: the same bytes move and no passthrough counter does."""
    _base_config()
    config.set("engine_backend", backend)
    size = 2 * CHUNK
    path = str(tmp_path / "pin.bin")
    make_test_file(path, size)
    emu = PassthruEmulator(str(tmp_path / "pin.img"))
    emu.provision(path, frag=2)
    src = FakeNvmeSource(path, force_cached_fraction=0.0)
    emu.attach(src)
    before = stats.snapshot(reset_max=False)
    try:
        with Session() as sess:
            got = _read_pass(sess, src, 2)
    finally:
        src.close()
        emu.close()
    after = stats.snapshot(reset_max=False)
    assert got == expected_bytes(0, size)
    dirty = {k: _counter_delta(before, after, k) for k in after.counters
             if (k.startswith("nr_passthru") or k == "bytes_passthru")
             and _counter_delta(before, after, k)}
    assert not dirty
    assert emu.commands_served == 0


def test_pinned_ladder_moves_zero_passthru_counters(tmp_path):
    _pinned_ladder_moves_zero_passthru_counters(tmp_path, "threadpool")


def test_pinned_uring_moves_zero_passthru_counters(tmp_path):
    _pinned_ladder_moves_zero_passthru_counters(tmp_path, "uring")


def test_slba_drift_is_a_hard_error(tmp_path):
    """A device offset that reverse-maps to the wrong file offset is an
    error, never a wrong-bytes read."""
    path = str(tmp_path / "drift.bin")
    make_test_file(path, CHUNK)
    emu = PassthruEmulator(str(tmp_path / "drift.img"))
    try:
        exts = emu.provision(path, frag=1)
        src = FakeNvmeSource(path, force_cached_fraction=0.0)
        chan = emu.attach(src)
        buf = bytearray(LBA)
        # off-by-one-LBA: planner said file_off 0, command lands at +512
        with pytest.raises(StromError, match="drift"):
            chan.read(0, 0, exts[0].physical + LBA, memoryview(buf))
        src.close()
    finally:
        emu.close()


def test_emulator_validates_commands(tmp_path):
    path = str(tmp_path / "val.bin")
    make_test_file(path, CHUNK)
    emu = PassthruEmulator(str(tmp_path / "val.img"))
    try:
        exts = emu.provision(path, frag=1)
        slba = exts[0].physical >> emu.lba_shift
        buf = memoryview(bytearray(LBA))
        with pytest.raises(StromError, match="size"):
            emu.execute(b"\x00" * 16, buf)
        bad_op = pack_uring_cmd(nsid=1, slba=slba, nlb0=0, data_len=LBA,
                                opcode=0x01)
        with pytest.raises(StromError, match="opcode"):
            emu.execute(bad_op, buf)
        bad_ns = pack_uring_cmd(nsid=7, slba=slba, nlb0=0, data_len=LBA)
        with pytest.raises(StromError, match="NSID"):
            emu.execute(bad_ns, buf)
        bad_len = pack_uring_cmd(nsid=1, slba=slba, nlb0=0, data_len=4096)
        with pytest.raises(StromError, match="data_len"):
            emu.execute(bad_len, buf)
        # LBA 0 is left unprovisioned on purpose: commands there are wild
        wild = pack_uring_cmd(nsid=1, slba=0, nlb0=0, data_len=LBA)
        with pytest.raises(StromError, match="provisioned"):
            emu.execute(wild, buf)
        ok = pack_uring_cmd(nsid=1, slba=slba, nlb0=0, data_len=LBA)
        assert emu.execute(ok, buf) == (path, 0)
        assert bytes(buf) == expected_bytes(0, LBA)
    finally:
        emu.close()
