"""Self-driving data-path tests (ISSUE 18, `autotune` marker).

Covers the controller contracts hardware-free: monotone hill-climb to
the knob bound, p99-regression step-back, hysteresis (a settled
trajectory never oscillates), health-machine freeze (unit-level and on
a session whose mirrored member fail-stops), stride and successor
prediction, strided-scan readahead hits, the token-bucket prefetch
budget, ARC ghost-list isolation of speculative fills, declared knob
bounds, and the everything-off inertness contract (one predicted
branch, no counters).
"""

import os

import pytest

from nvme_strom_tpu import Session, config, stats
from nvme_strom_tpu.autotune import (AutoTuner, HillClimber, KnobFamily,
                                     Reading, ReadaheadPredictor)
from nvme_strom_tpu.cache import ResidencyCache, residency_cache
from nvme_strom_tpu.testing import FakeNvmeSource, make_test_file

pytestmark = pytest.mark.autotune

CHUNK = 64 << 10


def _fam(lo=1, hi=256, v0=2, name="window"):
    f = KnobFamily(name, lo, hi)
    f.ensure(0, v0)
    return f


def _climber(*fams, **kw):
    return HillClimber(list(fams) or [_fam()], **kw)


def _drive(c, respond, epochs=40):
    """Run *epochs* synthetic epochs; ``respond(values)`` maps the
    current knob state to a Reading (the fake device)."""
    for _ in range(epochs):
        c.step(respond())


# ---------------------------------------------------------------------------
# hill-climb policy (pure unit)
# ---------------------------------------------------------------------------

def test_monotone_climb_reaches_bound():
    """On a device where throughput is proportional to the knob, the
    climber doubles all the way to the declared maxval and stops."""
    fam = _fam(lo=1, hi=16, v0=2)
    c = _climber(fam)
    _drive(c, lambda: Reading(fam.values[0], 1000, 10), epochs=20)
    assert fam.values[0] == 16.0
    kinds = [k for ep in c.history for (k, *_r) in ep]
    assert "step" in kinds
    # pinned at the bound: the up direction has nothing left to apply
    assert not fam.stepped("up")


def test_p99_regression_steps_back():
    """A probe that raises throughput but blows p99 past p99_tol x
    baseline is reverted, and that (family, direction) stays rejected."""
    fam = _fam(lo=1, hi=64, v0=4)
    c = _climber(fam, p99_tol=1.5)

    def respond():
        v = fam.values[0]
        # bigger knob moves more bytes but tail latency explodes
        return Reading(v, int(1000 * (v / 4.0) ** 2), 10)

    _drive(c, respond, epochs=12)
    assert fam.values[0] == 4.0, "p99 regression was not stepped back"
    kinds = [k for ep in c.history for (k, *_r) in ep]
    assert "revert" in kinds


def test_hysteresis_settles_without_oscillation():
    """On a flat response surface every direction is rejected once and
    the trajectory goes quiet — no step/revert churn in the tail."""
    fam = _fam(lo=1, hi=64, v0=8)
    c = _climber(fam)
    _drive(c, lambda: Reading(100.0, 1000, 10), epochs=40)
    assert fam.values[0] == 8.0
    tail = [k for ep in c.history[-10:] for (k, *_r) in ep]
    assert tail == [], f"settled trajectory still churning: {tail}"


def test_freeze_reverts_outstanding_probe():
    """A freeze epoch rolls back the in-flight probe, suspends probing,
    and probing resumes from scratch after thaw."""
    fam = _fam(lo=1, hi=64, v0=4)
    c = _climber(fam)
    c.step(Reading(100.0, 1000, 10))          # baseline + probe applied
    assert fam.values[0] != 4.0
    events = c.step(Reading(100.0, 1000, 10), frozen=True)
    kinds = [k for (k, *_r) in events]
    assert kinds == ["revert", "freeze"]
    assert fam.values[0] == 4.0, "freeze did not restore pre-probe value"
    assert all(k == "freeze" for (k, *_r) in
               c.step(Reading(100.0, 1000, 10), frozen=True))


def test_idle_epoch_defers_evaluation():
    """An idle epoch (no completed requests) must not be attributed to
    the outstanding probe — evaluation waits for traffic."""
    fam = _fam(lo=1, hi=64, v0=4)
    c = _climber(fam)
    c.step(Reading(100.0, 1000, 10))
    probed = fam.values[0]
    assert c.step(Reading(0.0, None, 0)) == []
    assert fam.values[0] == probed, "idle epoch moved the knob"
    c.step(Reading(500.0, 1000, 10))           # traffic returns: evaluate
    kinds = [k for ep in c.history for (k, *_r) in ep]
    assert kinds.count("step") >= 2 or "revert" in kinds


def test_knob_bounds_clamp():
    """Values never escape [lo, hi]; a pinned family yields no step."""
    fam = KnobFamily("cap", 64 << 10, 1 << 20)
    fam.ensure(0, 1)              # below lo: clamped up
    assert fam.values[0] == 64 << 10
    fam.ensure(1, 1 << 30)        # above hi: clamped down
    assert fam.values[1] == 1 << 20
    up = fam.stepped("up")
    assert 1 not in up and up[0] == 128 << 10
    for _ in range(16):
        fam.values.update(fam.stepped("up") or {})
    assert all(v <= 1 << 20 for v in fam.values.values())


# ---------------------------------------------------------------------------
# readahead prediction (pure unit)
# ---------------------------------------------------------------------------

def test_stride_detection():
    """Three equal-stride equal-extent spans predict the fourth."""
    p = ReadaheadPredictor()
    for first in (0, 8, 16):
        p.observe(first, 4)
    assert p.predict() == (24, 4)
    p.observe(24, 4)
    assert p.predict() == (32, 4)


def test_successor_fallback():
    """A repeating non-strided walk replays the learned successor."""
    p = ReadaheadPredictor()
    walk = [(0, 2), (100, 2), (7, 2), (0, 2)]
    for first, n in walk:
        p.observe(first, n)
    # last span started at 0; its recorded follower was (100, 2)
    assert p.predict() == (100, 2)


def test_stride_requires_three_spans():
    p = ReadaheadPredictor()
    p.observe(0, 4)
    assert p.predict() is None
    p.observe(8, 4)
    assert p.predict() is None


# ---------------------------------------------------------------------------
# ARC ghost-list isolation of speculative fills
# ---------------------------------------------------------------------------

def _mk_cache(nbytes):
    config.set("cache_bytes", nbytes)
    c = ResidencyCache()
    c.configure()
    return c


def test_speculative_fill_never_trains_ghosts():
    """An evicted speculative extent leaves NO ghost entry (evicting a
    wrong guess must not grow ARC's recency target), while an evicted
    demand extent does."""
    L = 4096
    c = _mk_cache(2 * L)
    skey = ("/ra",)
    c.fill(skey, 0, L, b"a" * L, speculative=True)
    c.fill(skey, L, L, b"b" * L)
    # two more demand fills evict both residents
    c.fill(skey, 2 * L, L, b"c" * L)
    c.fill(skey, 3 * L, L, b"d" * L)
    ghosts = set(c._b1) | set(c._b2)
    assert (skey, 0, L) not in ghosts, "speculative eviction left a ghost"
    assert (skey, L, L) in ghosts, "demand eviction lost its ghost"


def test_speculative_hit_counts_and_stays_recency():
    """The first demand touch of a prefetched extent counts
    nr_readahead_hit and clears provenance IN t1 (first real touch is
    recency, not frequency); the second touch promotes normally."""
    L = 4096
    c = _mk_cache(4 * L)
    skey = ("/ra",)
    c.fill(skey, 0, L, b"a" * L, speculative=True)
    before = stats.snapshot(reset_max=False).counters.get(
        "nr_readahead_hit", 0)
    lease = c.lookup(skey, 0, L)
    assert lease is not None
    lease.release()
    got = stats.snapshot(reset_max=False).counters.get(
        "nr_readahead_hit", 0) - before
    assert got == 1
    assert (skey, 0, L) in c._t1 and not c._t1[(skey, 0, L)].spec
    lease = c.lookup(skey, 0, L)   # second touch: frequency promotion
    assert lease is not None
    lease.release()
    assert (skey, 0, L) in c._t2


def test_speculative_refresh_does_not_clobber_demand_entry():
    """A speculative fill over an existing unreferenced demand extent
    must not refresh/replace it (prefetch never rewrites known data)."""
    L = 4096
    c = _mk_cache(4 * L)
    skey = ("/ra",)
    assert c.fill(skey, 0, L, b"x" * L)
    # returns True (the extent IS resident) but must not rewrite it
    assert c.fill(skey, 0, L, b"y" * L, speculative=True)
    lease = c.lookup(skey, 0, L)
    out = bytearray(L)
    assert lease.copy_into(out)
    lease.release()
    assert out == b"x" * L


# ---------------------------------------------------------------------------
# AutoTuner wiring (session-level, loopback fake)
# ---------------------------------------------------------------------------

@pytest.fixture
def _snap():
    snap = config.snapshot()
    yield
    config.restore(snap)
    residency_cache.clear()
    residency_cache.configure()


def test_off_is_inert(_snap, tmp_path):
    """autotune=off + readahead=off: no controller thread, knob
    accessors return the caller's defaults, and a full read moves no
    autotune/readahead counters — the one-predicted-branch contract."""
    config.set("autotune", False)
    config.set("readahead", False)
    path = os.path.join(str(tmp_path), "off.bin")
    make_test_file(path, 8 * CHUNK)
    before = stats.snapshot(reset_max=False).counters
    src = FakeNvmeSource(path, force_cached_fraction=0.0)
    try:
        with Session() as sess:
            t = sess._tuner
            assert not t.active and t._thread is None
            assert t.submit_window(7) == 7 or t._windows == {}
            assert t.dma_cap(123456) == 123456
            assert t.pool_width(0, 3) == 3
            assert t.hedge_delay(0, 0.25) == 0.25
            handle, buf = sess.alloc_dma_buffer(8 * CHUNK)
            res = sess.memcpy_ssd2ram(src, handle, list(range(8)), CHUNK)
            sess.memcpy_wait(res.dma_task_id)
            sess.unmap_buffer(handle)
    finally:
        src.close()
    after = stats.snapshot(reset_max=False).counters
    for k in ("nr_autotune_step", "nr_autotune_revert", "nr_autotune_freeze",
              "nr_readahead_fill", "nr_readahead_hit", "nr_readahead_skip",
              "bytes_readahead"):
        assert after.get(k, 0) == before.get(k, 0), f"{k} moved while off"


def test_chunk_cap_off_matches_sizer(_snap):
    """With autotune off, AutoTuner.chunk_cap is bit-for-bit the old
    AdaptiveChunkSizer behavior: same floor/limit, halve on burst via
    the hosted sizer, restore on calm."""
    from nvme_strom_tpu.engine import AdaptiveChunkSizer
    config.set("autotune", False)
    with Session() as sess:
        t = sess._tuner
        ref = AdaptiveChunkSizer(64 << 10, 4 << 20)
        assert t.chunk_cap(64 << 10, 4 << 20, 0) == ref.effective
        szr = t.chunk_sizers[0]
        assert (szr.floor, szr.limit) == (ref.floor, ref.limit)
        # changed limit rebuilds the hosted sizer, as the old per-member
        # dict in Session did
        t.chunk_cap(64 << 10, 8 << 20, 0)
        assert t.chunk_sizers[0].limit == 8 << 20


def test_budget_zero_is_predict_only(_snap, tmp_path):
    """readahead_budget_mb_s=0: predictions are made but every issue is
    SKIPPED — no speculative bytes move, the skip counter does."""
    config.set("readahead", True)
    config.set("readahead_budget_mb_s", 0.0)
    config.set("cache_bytes", 16 << 20)
    config.set("cache_arbitration", False)
    config.set("dma_max_size", CHUNK)
    residency_cache.configure()
    path = os.path.join(str(tmp_path), "ra0.bin")
    make_test_file(path, 32 * CHUNK)
    before = stats.snapshot(reset_max=False).counters
    src = FakeNvmeSource(path, force_cached_fraction=0.0)
    try:
        with Session() as sess:
            sess._tuner.stop()      # drive the issue loop synchronously
            handle, buf = sess.alloc_dma_buffer(4 * CHUNK)
            for first in (0, 4, 8, 12):
                res = sess.memcpy_ssd2ram(src, handle,
                                          list(range(first, first + 4)),
                                          CHUNK)
                sess.memcpy_wait(res.dma_task_id)
                sess._tuner.readahead_tick()
            sess.unmap_buffer(handle)
    finally:
        src.close()
    after = stats.snapshot(reset_max=False).counters
    assert after.get("bytes_readahead", 0) == \
        before.get("bytes_readahead", 0)
    assert after.get("nr_readahead_fill", 0) == \
        before.get("nr_readahead_fill", 0)
    assert after.get("nr_readahead_skip", 0) > \
        before.get("nr_readahead_skip", 0)


def test_knob_families_inherit_declared_bounds(_snap):
    """The climber's hard bounds come from the backing Vars' declared
    minval/maxval — the contract the stromlint config-bounds rule
    enforces statically."""
    config.set("autotune", True)
    with Session() as sess:
        sess._tuner.stop()
        c = sess._tuner._climber
        desc = config.describe()
        win = c.family("window")
        assert win.lo == float(desc["submit_window"].minval)
        assert win.hi == float(desc["submit_window"].maxval)
        hedge = c.family("hedge_ms")
        assert hedge.hi == float(desc["hedge_ms"].maxval)
        cap = c.family("cap")
        assert cap.hi == float(desc["coalesce_limit"].maxval)
        assert cap.lo >= float(desc["dma_max_size"].minval)


def test_hedge_family_disarmed_under_policy_off(_snap):
    config.set("autotune", True)
    config.set("hedge_policy", "off")
    with Session() as sess:
        sess._tuner.stop()
        sess._tuner._seed_members()
        assert not sess._tuner._climber.family("hedge_ms").armed


def _bad_statics():
    """A planning window of 2 and a 256K request cap: knobs the
    controller has room to move."""
    config.set("io_backend", "python")
    config.set("submit_window", 2)
    config.set("member_queue_depth", 2)
    config.set("dma_max_size", 256 << 10)
    config.set("cache_bytes", 0)
    config.set("cache_arbitration", False)
    config.set("hedge_policy", "off")
    config.set("readahead", False)


def _read_ids(sess, src, ids):
    handle, buf = sess.alloc_dma_buffer(len(ids) * CHUNK)
    try:
        res = sess.memcpy_ssd2ram(src, handle, list(ids), CHUNK)
        sess.memcpy_wait(res.dma_task_id, timeout=60.0)
        return bytes(buf.view()[:len(ids) * CHUNK])
    finally:
        sess.unmap_buffer(handle)


def test_member_failstop_freezes_tuning(_snap, tmp_path):
    """A mirrored member fail-stops mid-run: the controller freezes
    (counted, no knob step in any later epoch) while the mirror keeps
    serving the same bytes."""
    from nvme_strom_tpu.testing import FakeStripedNvmeSource, FaultPlan

    nchunks = 32
    paths = []
    for i in range(2):
        p = os.path.join(str(tmp_path), f"frz{i}.bin")
        make_test_file(p, nchunks * CHUNK)    # paired: one member's worth
        paths.append(p)
    _bad_statics()
    config.set("autotune", True)
    config.set("quarantine_after", 2)
    config.set("quarantine_s", 60.0)
    plan = FaultPlan()
    src = FakeStripedNvmeSource(paths, CHUNK, fault_plan=plan,
                                force_cached_fraction=0.0, mirror="paired")
    try:
        with Session() as sess:
            sess._tuner.stop()      # drive epochs synchronously
            ref = _read_ids(sess, src, range(nchunks))
            for _ in range(4):
                assert _read_ids(sess, src, range(nchunks)) == ref
                sess._tuner.step_epoch()
            plan.failstop_member = 0
            plan.failstop_after = 0
            freeze0 = stats.snapshot(reset_max=False).counters.get(
                "nr_autotune_freeze", 0)
            nhist = len(sess._tuner._climber.history)
            for _ in range(4):
                assert _read_ids(sess, src, range(nchunks)) == ref
                sess._tuner.step_epoch()
            later = sess._tuner._climber.history[nhist:]
    finally:
        src.close()
    frozen = stats.snapshot(reset_max=False).counters.get(
        "nr_autotune_freeze", 0) - freeze0
    assert frozen > 0, "fail-stop never froze the controller"
    assert not [k for ep in later for (k, *_r) in ep if k == "step"]


@pytest.mark.parametrize("readahead", [True, False], ids=["on", "off"])
def test_strided_scan_readahead(_snap, tmp_path, readahead):
    """A scan read as sequential 4-chunk spans: with readahead on, the
    predicted next spans are prefetched and at least half the demand
    chunks hit; with it off, no readahead counter moves and the scan
    stays exactly cold (no hit, one fill per chunk)."""
    from nvme_strom_tpu.testing.fake import expected_bytes

    nchunks, span = 32, 4
    path = os.path.join(str(tmp_path), "ra.bin")
    make_test_file(path, nchunks * CHUNK)
    want = expected_bytes(0, nchunks * CHUNK)
    _bad_statics()
    config.set("cache_bytes", 64 << 20)
    config.set("readahead", readahead)
    config.set("readahead_budget_mb_s", 64.0)
    residency_cache.configure()
    residency_cache.clear()
    names = ("nr_readahead_fill", "nr_readahead_hit", "nr_readahead_skip",
             "bytes_readahead", "nr_cache_hit", "nr_cache_miss",
             "nr_cache_fill")
    before = stats.snapshot(reset_max=False).counters
    src = FakeNvmeSource(path, force_cached_fraction=0.0)
    try:
        with Session() as sess:
            assert sess._tuner.active == readahead
            sess._tuner.stop()      # drive the issue loop synchronously
            for first in range(0, nchunks, span):
                got = _read_ids(sess, src, range(first, first + span))
                assert got == want[first * CHUNK:(first + span) * CHUNK]
                sess._tuner.step_epoch()
    finally:
        src.close()
    after = stats.snapshot(reset_max=False).counters
    d = {n: after.get(n, 0) - before.get(n, 0) for n in names}
    if readahead:
        hits = d["nr_cache_hit"]
        assert hits / max(hits + d["nr_cache_miss"], 1) >= 0.5, d
    else:
        assert not any(d[n] for n in names[:4]), d
        assert d["nr_cache_hit"] == 0 and d["nr_cache_fill"] == nchunks, d
