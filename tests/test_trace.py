"""Flight-recorder and trace-export tests (ISSUE 7).

Covers the observability contract end to end: traced striped tasks
reconstruct their exact extent coverage from span events, the Chrome
trace-event export passes its own schema check (Perfetto-loadable), the
seeded fail-stop schedule dumps hedge race + mirror fallback in causal
order on the victim's track, ``trace_policy=off`` records nothing, and
the stats exporter stays the single resetter of ``max_dma_count``.
"""

import json
import threading

import pytest

from nvme_strom_tpu import Session, config, stats
from nvme_strom_tpu.testing import FakeStripedNvmeSource, FaultPlan
from nvme_strom_tpu.testing.chaos import (STRIPE, expected_mirrored_stream,
                                          make_mirrored_members, read_all)
from nvme_strom_tpu.trace import (recorder, validate_chrome_trace,
                                  _ARGS, _MEMBER, _LANE, _LEN, _NAME, _OFF,
                                  _TID, _TS)

pytestmark = pytest.mark.trace


def _tracing(policy="all", rate=1.0):
    config.set("trace_policy", policy)
    config.set("trace_sample_rate", rate)
    recorder.configure()
    recorder.clear()


def _merge(intervals):
    """Union of [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(i) for i in out]


# ---------------------------------------------------------------------------
# span reconstruction: a traced striped task names its exact extents
# ---------------------------------------------------------------------------

def test_traced_striped_task_reconstructs_extent_set(tmp_path):
    """The union of a traced task's extent spans must equal the stripe
    map's planned coverage per member, and their lengths must sum to the
    task's byte count (no extent lost, invented, or double-counted)."""
    _tracing("all")
    paths = make_mirrored_members(str(tmp_path))
    src = FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                force_cached_fraction=0.0, mirror="paired")
    try:
        with Session() as sess:
            got, total = read_all(sess, src)
            assert got == expected_mirrored_stream(paths)[:total]
    finally:
        src.close()

    events = recorder.snapshot_events()
    assert events, "trace_policy=all recorded nothing"
    extents = [e for e in events if e[_NAME] == "extent"]
    assert extents, "no extent spans for a traced striped task"
    assert sum(e[_LEN] for e in extents) == total, \
        "extent span lengths do not sum to the task's byte count"
    got_cov = {}
    for e in extents:
        got_cov.setdefault(e[_MEMBER], []).append((e[_OFF], e[_OFF] + e[_LEN]))
    want_cov = {}
    for x in src.extents(0, total):
        want_cov.setdefault(x.member, []).append(
            (x.file_off, x.file_off + x.length))
    assert {m: _merge(v) for m, v in got_cov.items()} == \
           {m: _merge(v) for m, v in want_cov.items()}, \
        "traced extents diverge from the stripe map's planned coverage"
    # lifecycle bookends rode along with the same trace id
    tids = {e[_TID] for e in extents}
    names_by_tid = {e[_NAME] for e in events if e[_TID] in tids}
    assert "submit" in names_by_tid and "wait" in names_by_tid


def test_off_policy_records_nothing(tmp_path):
    """``trace_policy=off`` is the default: zero events, zero trace ids —
    the one-branch-per-site contract's observable half."""
    _tracing("off")
    assert not recorder.active
    paths = make_mirrored_members(str(tmp_path))
    src = FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                force_cached_fraction=0.0, mirror="paired")
    try:
        with Session() as sess:
            read_all(sess, src)
    finally:
        src.close()
    assert recorder.snapshot_events() == []


def test_sampled_policy_traces_a_deterministic_subset():
    """Sampling picks 1 task in round(1/rate) by the submission counter —
    deterministic, not random, so overhead and selection reproduce."""
    _tracing("sampled", rate=0.5)
    picked = [recorder.task_begin(1000 + i) for i in range(8)]
    assert sum(1 for t in picked if t) == 4
    for i in range(8):
        recorder.task_end(1000 + i)


# ---------------------------------------------------------------------------
# Chrome trace-event export
# ---------------------------------------------------------------------------

def test_chrome_trace_schema_and_flow_arrows(tmp_path):
    """The export must satisfy the trace-event schema (validated by the
    same checker the tools use), lay spans on per-member tracks, and link
    each traced task submit->landing with a flow-arrow pair."""
    _tracing("all")
    paths = make_mirrored_members(str(tmp_path))
    src = FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                force_cached_fraction=0.0, mirror="paired")
    try:
        with Session() as sess:
            read_all(sess, src)
    finally:
        src.close()
    doc = recorder.chrome_trace("schema test")
    assert validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert "X" in phases and "M" in phases
    member_tracks = {e["tid"] for e in evs
                     if e["ph"] == "X" and e["tid"] >= 100}
    assert len(member_tracks) >= 2, "spans never landed on member tracks"
    starts = [e for e in evs if e["ph"] == "s"]
    finishes = [e for e in evs if e["ph"] == "f"]
    assert starts and finishes
    assert {e["id"] for e in starts} == {e["id"] for e in finishes}

    # dump/reload round-trip stays valid (what Perfetto actually ingests)
    path = recorder.dump(str(tmp_path / "dump.json"), reason="schema test")
    with open(path) as f:
        assert validate_chrome_trace(json.load(f)) == []


@pytest.mark.landing
def test_landing_spans_attribute_the_path_taken(tmp_path):
    """Every pipeline command leaves 'landing' spans naming the path it
    took (direct vs staged) with member attribution, an ineligible
    command under landing=direct leaves the fallback-reason instant, and
    the export still passes the schema check (ISSUE 8)."""
    from nvme_strom_tpu.engine import PlainSource
    from nvme_strom_tpu.hbm import HbmRegistry, StagingPipeline
    from nvme_strom_tpu.testing import make_test_file

    _tracing("all")
    size, chunk = 1 << 20, 256 << 10
    path = str(tmp_path / "land.bin")
    make_test_file(path, size)
    reg = HbmRegistry()
    with Session() as sess, PlainSource(path) as src:
        for mode, nbytes in (("direct", size), ("staged", size + chunk)):
            # the oversized destination is ineligible (alignment) and
            # must fall back — under landing=direct, with the instant
            config.set("landing", "direct")
            handle = reg.map_device_memory(nbytes)
            try:
                with StagingPipeline(sess, hbm_registry=reg) as pipe:
                    res = pipe.memcpy_ssd2dev(src, handle,
                                              list(range(size // chunk)),
                                              chunk)
                assert res.landing == mode
            finally:
                reg.unmap(handle)

    events = recorder.snapshot_events()
    landing = [e for e in events if e[_NAME] == "landing"]
    routed = {e[_ARGS].get("path") for e in landing}
    assert routed == {"direct", "staged"}, \
        f"landing spans missing a path: {routed}"
    assert all(e[_MEMBER] >= 0 for e in landing), \
        "landing span without member attribution"
    for p in ("direct", "staged"):
        moved = sum(e[_LEN] for e in landing if e[_ARGS].get("path") == p)
        assert moved == size, \
            f"{p} landing spans cover {moved} bytes, task moved {size}"
    falls = [e for e in events if e[_NAME] == "landing_fallback"]
    assert [e[_ARGS].get("reason") for e in falls] == ["alignment"]

    doc = recorder.chrome_trace("landing spans")
    assert validate_chrome_trace(doc) == []


def test_validator_rejects_malformed_documents():
    assert validate_chrome_trace({"traceEvents": "nope"})
    assert validate_chrome_trace(
        {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1,
                          "ts": 0.0}]}), "X without dur must fail"
    assert validate_chrome_trace(
        {"traceEvents": [{"name": "x", "ph": "f", "pid": 1, "tid": 1,
                          "ts": 0.0, "id": "7", "bp": "e"}]}), \
        "flow finish without its start must fail"


# ---------------------------------------------------------------------------
# chaos fail-stop: hedge race + mirror fallback on the victim's track
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_failstop_dump_shows_hedge_race_then_mirror_fallback(tmp_path):
    """The acceptance scenario: a member turns slow (losing hedge races),
    then fail-stops.  The dump must be schema-valid and carry, on the
    victim's track, hedge activity BEFORE the health machine declares the
    member dead, and mirror fallbacks serving it afterwards."""
    _tracing("all")
    config.set("io_retries", 1)
    config.set("canary_interval_s", 0.0)
    config.set("hedge_policy", "fixed")
    config.set("hedge_ms", 5.0)
    # serialize the victim's lane: with deep concurrent lanes every
    # extent is in flight before the health machine flips, so the whole
    # stream is served by winning hedges and the route-away/mirror rung
    # never fires — one-at-a-time makes the fail-stop bite mid-stream
    config.set("member_queue_depth", 1)
    victim = 0
    plan = FaultPlan(failstop_member=victim, failstop_after=4,
                     slow_member=victim, slow_s=0.05)
    paths = make_mirrored_members(str(tmp_path))
    src = FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                fault_plan=plan, force_cached_fraction=0.0,
                                mirror="paired")
    try:
        with Session() as sess:
            got, total = read_all(sess, src)
            assert got == expected_mirrored_stream(paths)[:total]
    finally:
        src.close()

    doc = recorder.dump(str(tmp_path / "failstop.json"),
                        reason="failstop test")
    with open(doc) as f:
        loaded = json.load(f)
    assert validate_chrome_trace(loaded) == []

    events = recorder.snapshot_events()
    vm = victim
    hedge_ts = [e[_TS] for e in events if e[_MEMBER] == vm
                and e[_NAME] in ("hedge_issued", "hedge_won")]
    mirror_ts = [e[_TS] for e in events if e[_MEMBER] == vm
                 and e[_NAME] == "mirror_read"]
    died_ts = [e[_TS] for e in events if e[_NAME] == "health"
               and e[_MEMBER] == vm and e[-1] and e[-1].get("to") == "failed"]
    assert hedge_ts, "no hedge race recorded on the victim's track"
    assert mirror_ts, "no mirror fallback recorded on the victim's track"
    assert died_ts, "no health transition to failed recorded"
    assert min(hedge_ts) < died_ts[0], \
        "hedge race should precede the fail-stop (slow phase first)"
    assert died_ts[0] < max(mirror_ts), \
        "mirror fallbacks should keep serving after the member died"
    # the Perfetto view: those same events sit on the victim's track
    vt = 100 + vm
    names_on_track = {e["name"] for e in loaded["traceEvents"]
                      if e.get("tid") == vt}
    assert {"mirror_read"} <= names_on_track
    assert names_on_track & {"hedge_issued", "hedge_won"}


# ---------------------------------------------------------------------------
# satellite 2: the exporter is the single resetter of max_dma_count
# ---------------------------------------------------------------------------

def test_concurrent_snapshots_do_not_consume_max_dma(tmp_path):
    """Plain snapshots observe the high-water mark without consuming it
    (N concurrent readers all see the same peak); only export() resets it
    to the current in-flight level."""
    # any earlier Session in this process started the background
    # exporter, whose export() is the legitimate resetter; stop it so
    # only the readers below touch the mark (the next Session restarts it)
    stats.stop_export()
    base = stats.snapshot().counters.get("max_dma_count", 0)
    stats.gauge_add("max_dma_count", 7)
    want = base + 7

    seen = []
    def reader():
        for _ in range(50):
            seen.append(stats.snapshot().counters.get("max_dma_count", 0))
    threads = [threading.Thread(target=reader) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert set(seen) == {want}, \
        "a plain snapshot consumed the max_dma_count high-water mark"

    stats.export(str(tmp_path / "stat.json"))
    cur = stats.snapshot().counters.get("cur_dma_count", 0)
    assert stats.snapshot().counters.get("max_dma_count", 0) == cur, \
        "export() failed to reset the high-water mark"


def test_bytes_touched_ratio():
    """The write-amplification metric: (delivered + staging + verify +
    hedge-dup) / delivered; None until bytes have moved."""
    from nvme_strom_tpu.stats import bytes_touched_ratio
    assert bytes_touched_ratio({}) is None
    assert bytes_touched_ratio({"total_dma_length": 0}) is None
    r = bytes_touched_ratio({"total_dma_length": 100,
                             "bytes_staging_copy": 100,
                             "bytes_verify_reread": 10,
                             "bytes_hedge_dup": 40})
    assert r == pytest.approx(2.5)
    assert bytes_touched_ratio({"total_dma_length": 64}) == pytest.approx(1.0)
