"""Deterministic chaos harness for the member-health stack (``make
chaos``, PR 6).

Each scenario drives a seeded fault schedule — fail-stop, flaky, slow
member, corrupt-once, fail-stop-then-rejoin, resident bit-rot healed by
the background scrubber (ISSUE 16) — through a mirrored striped
loopback set (plus one native-engine leg against real files) and checks
the survival contract:

* the copy is BYTE-IDENTICAL to the healthy stream (degraded striping
  served the failed member's extents from its mirror at direct speed, or
  the buffered/re-read tiers healed the damage),
* the run stays inside a bounded deadline — never a hang, and
* every observed health transition walks an edge of
  :data:`fault.ALLOWED_TRANSITIONS` (e.g. a fail-stopped member goes
  ``healthy -> failed`` and, once the device answers canary probes
  again, ``failed -> rejoining -> healthy`` — no teleporting).

The schedule is fixed by ``STROM_CHAOS_SEED`` (default 1234) so CI
failures reproduce; ``STROM_CHAOS_ROUNDS`` sweeps the scenario list
multiple times with fresh derived seeds.

``python -m nvme_strom_tpu.testing.chaos write`` (``make chaos-write``,
ISSUE 11) runs the WRITE-side schedules instead: write-path fail-stop
with mirror failover and journal-replay rejoin, an ENOSPC first-error
latch storm, a torn mirror pair healed from its primary under
``write_verify``, and a SIGKILL-mid-save checkpoint crash with crc
verification of the surviving file.  ``all`` runs both sets.
"""

from __future__ import annotations

import os
import random
import shutil
import sys
import tempfile
import time

STRIPE = 64 << 10
CHUNK = 256 << 10
MEMBER_SIZE = 1 << 20          # per member: 4 members -> 2MB logical (paired)


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def make_mirrored_members(dirpath: str, n_pairs: int = 2,
                          size: int = MEMBER_SIZE, tag: str = "m"):
    """2*n_pairs member files where member 2k+1 is a byte-identical copy
    of member 2k — the ``mirror='paired'`` on-disk layout."""
    from .fake import make_test_file
    paths = []
    for k in range(n_pairs):
        p = os.path.join(dirpath, f"{tag}{2 * k}.bin")
        make_test_file(p, size, seed=100 + k)
        q = os.path.join(dirpath, f"{tag}{2 * k + 1}.bin")
        shutil.copyfile(p, q)
        paths += [p, q]
    return paths


def expected_mirrored_stream(paths, stripe_chunk: int = STRIPE) -> bytes:
    """The logical stream of a paired set: RAID-0 over the even-indexed
    primaries only (odd members are replicas, not address space)."""
    parts = [open(p, "rb").read() for p in paths[::2]]
    nm = len(parts)
    total = sum(len(p) for p in parts)
    out = bytearray(total)
    for i in range(total // stripe_chunk):
        m, row = i % nm, i // nm
        out[i * stripe_chunk:(i + 1) * stripe_chunk] = \
            parts[m][row * stripe_chunk:(row + 1) * stripe_chunk]
    return bytes(out)


def read_all(sess, src, chunk: int = CHUNK, timeout: float = 60.0):
    """Drive a whole-source memcpy and return the reordered byte stream."""
    import numpy as np

    from ..engine import reorder_chunks
    total = src.size // chunk * chunk
    handle, buf = sess.alloc_dma_buffer(total)
    want = list(range(total // chunk))
    res = sess.memcpy_ssd2ram(src, handle, want, chunk)
    sess.memcpy_wait(res.dma_task_id, timeout=timeout)
    host = reorder_chunks(np.frombuffer(buf.view()[:total], np.uint8),
                          chunk, res.chunk_ids, want)
    return bytes(host), total


def assert_transitions_legal(sess, scenario: str) -> None:
    """Every logged health transition must be an ALLOWED_TRANSITIONS edge."""
    from ..fault import ALLOWED_TRANSITIONS
    allowed = {(a.value, b.value) for a, b in ALLOWED_TRANSITIONS}
    for member, frm, to, _t in sess._member_health.transitions():
        if (frm, to) not in allowed:
            raise AssertionError(
                f"{scenario}: illegal health transition {frm}->{to} "
                f"on member {member}")


def _counter(name: str) -> int:
    from ..stats import stats
    return stats.snapshot(reset_max=False).counters.get(name, 0)


# ---------------------------------------------------------------------------
# scenarios — each returns a short tag for the tally
# ---------------------------------------------------------------------------

def scenario_fail_stop(rng: random.Random, dirpath: str) -> str:
    """A mirrored member turns slow, loses a hedge race or two, then
    fail-stops mid-task: the copy must complete byte-identical with the
    dead member's extents served by its mirror, the member must land in
    FAILED — and the flight recorder (forced to ``trace_policy=all`` for
    this scenario) must produce a Perfetto-loadable dump showing the
    hedge race and the mirror fallback on the victim's track."""
    from ..config import config
    from ..engine import Session
    from ..fault import HealthState
    from ..trace import recorder, validate_chrome_trace
    from .fake import FakeStripedNvmeSource, FaultPlan

    config.set("io_retries", 1)
    config.set("task_deadline_s", 30.0)
    config.set("canary_interval_s", 0.0)   # no probes: FAILED must hold
    config.set("hedge_policy", "fixed")
    config.set("hedge_ms", 5.0)
    # one-at-a-time member lane: deep lanes would put every extent in
    # flight before the health machine flips, serving the whole stream
    # by winning hedges — serialized, the fail-stop bites mid-stream and
    # the post-failure extents walk the route-away/mirror rung
    config.set("member_queue_depth", 1)
    config.set("trace_policy", "all")
    recorder.configure()
    recorder.clear()
    victim = rng.choice([0, 2])
    # slow before dead: the victim's pre-fail-stop reads each lose a
    # 5ms hedge race, so the dump carries hedge spans AND mirror
    # fallbacks in causal order on one track
    plan = FaultPlan(failstop_member=victim,
                     failstop_after=rng.randrange(2, 8),
                     slow_member=victim, slow_s=0.05)
    paths = make_mirrored_members(dirpath, tag=f"fs{rng.randrange(1 << 16)}-")
    src = FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                fault_plan=plan, force_cached_fraction=0.0,
                                mirror="paired")
    mirrors_before = _counter("nr_mirror_read")
    try:
        with Session() as sess:
            got, total = read_all(sess, src)
            assert got == expected_mirrored_stream(paths)[:total], \
                "fail_stop: degraded copy diverged from healthy stream"
            # a straggler success from a pre-fail-stop read may have begun
            # a (doomed) warmup, so REJOINING is also a legal endpoint
            assert sess._member_health.state(victim) in \
                (HealthState.FAILED, HealthState.REJOINING), \
                f"fail_stop: member {victim} ended " \
                f"{sess._member_health.state(victim)}"
            assert_transitions_legal(sess, "fail_stop")
    finally:
        src.close()
        doc = recorder.chrome_trace("chaos fail_stop")
        dump_path = recorder.dump(os.path.join(dirpath, "fail_stop.json"),
                                  reason="chaos fail_stop")
        config.set("trace_policy", "off")
        recorder.configure()
        recorder.clear()
    errs = validate_chrome_trace(doc)
    assert not errs, f"fail_stop: trace dump fails schema check: {errs[:5]}"
    names = {(e.get("name"), e.get("tid")) for e in doc["traceEvents"]}
    vt = 100 + victim
    assert ("hedge_issued", vt) in names or ("hedge_won", vt) in names, \
        f"fail_stop: no hedge event on victim track (dump: {dump_path})"
    assert ("mirror_read", vt) in names, \
        f"fail_stop: no mirror_read on victim track (dump: {dump_path})"
    assert _counter("nr_mirror_read") > mirrors_before, \
        "fail_stop: no extent was served from the mirror"
    return "fail_stop"


def scenario_flaky(rng: random.Random, dirpath: str) -> str:
    """Randomized transient EIO across the whole set: the retry ladder
    (plus mirror legs) must heal every chunk."""
    from ..config import config
    from ..engine import Session
    from .fake import FakeStripedNvmeSource, FaultPlan

    config.set("io_retries", rng.choice([2, 3]))
    config.set("retry_backoff_ms", 1.0)
    config.set("task_deadline_s", 30.0)
    plan = FaultPlan(fail_rate=rng.choice([0.05, 0.1, 0.2]),
                     seed=rng.randrange(1 << 30))
    paths = make_mirrored_members(dirpath, tag=f"fl{rng.randrange(1 << 16)}-")
    src = FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                fault_plan=plan, force_cached_fraction=0.0,
                                mirror="paired")
    try:
        with Session() as sess:
            got, total = read_all(sess, src)
            assert got == expected_mirrored_stream(paths)[:total], \
                "flaky: healed copy diverged from healthy stream"
            assert_transitions_legal(sess, "flaky")
    finally:
        src.close()
    return "flaky"


def scenario_slow_hedge(rng: random.Random, dirpath: str) -> str:
    """One member serves every read slowly: hedged reads re-issue its
    chunks on the mirror and the task finishes inside a latency bound a
    pure-primary run could not meet."""
    from ..config import config
    from ..engine import Session
    from .fake import FakeStripedNvmeSource, FaultPlan

    slow_s = 0.15
    config.set("io_retries", 1)
    config.set("task_deadline_s", 30.0)
    config.set("hedge_policy", "fixed")
    config.set("hedge_ms", 5.0)
    victim = rng.choice([0, 2])
    plan = FaultPlan(slow_member=victim, slow_s=slow_s)
    paths = make_mirrored_members(dirpath, tag=f"sl{rng.randrange(1 << 16)}-")
    src = FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                fault_plan=plan, force_cached_fraction=0.0,
                                mirror="paired")
    issued_before = _counter("nr_hedge_issued")
    won_before = _counter("nr_hedge_won")
    try:
        with Session() as sess:
            t0 = time.monotonic()
            got, total = read_all(sess, src)
            wall = time.monotonic() - t0
            assert got == expected_mirrored_stream(paths)[:total], \
                "slow: hedged copy diverged from healthy stream"
            # every one of the victim's chunks costs slow_s on the primary
            # leg; hedges must keep the task well under the serial cost
            n_victim = (total // STRIPE) // 2
            assert wall < n_victim * slow_s, \
                f"slow: {wall:.2f}s suggests hedges never won " \
                f"(serial primary cost ~{n_victim * slow_s:.2f}s)"
            assert_transitions_legal(sess, "slow")
    finally:
        src.close()
    assert _counter("nr_hedge_issued") > issued_before, \
        "slow: no hedge was ever issued"
    assert _counter("nr_hedge_won") > won_before, \
        "slow: hedges issued but none won against a member "\
        f"{slow_s * 1e3:.0f}ms slow"
    return "slow"


def scenario_corrupt_once(rng: random.Random, dirpath: str) -> str:
    """A torn read (bit flip that heals on re-read): page checksums must
    catch it and the re-read tier must repair it transparently."""
    import numpy as np

    from ..config import config
    from ..engine import Session
    from ..scan.heap import PAGE_SIZE, HeapSchema, build_heap_file
    from .fake import FakeNvmeSource, FaultPlan

    config.set("checksum_verify", True)
    config.set("task_deadline_s", 30.0)
    schema = HeapSchema(n_cols=2, visibility=False)
    n = schema.tuples_per_page * 4
    path = os.path.join(dirpath, f"co{rng.randrange(1 << 16)}.heap")
    build_heap_file(path, [np.arange(n, dtype=np.int32),
                           (n - np.arange(n)).astype(np.int32)], schema)
    with open(path, "rb") as f:
        data = f.read()
    page = rng.randrange(len(data) // PAGE_SIZE)
    plan = FaultPlan(corrupt_once_offsets={page * PAGE_SIZE
                                           + rng.randrange(64, PAGE_SIZE)})
    src = FakeNvmeSource(path, fault_plan=plan, force_cached_fraction=0.0)
    rereads_before = _counter("nr_csum_reread")
    try:
        with Session() as sess:
            handle, buf = sess.alloc_dma_buffer(len(data))
            res = sess.memcpy_ssd2ram(src, handle,
                                      list(range(len(data) // PAGE_SIZE)),
                                      PAGE_SIZE)
            sess.memcpy_wait(res.dma_task_id, timeout=30.0)
            assert bytes(buf.view()[:len(data)]) == data, \
                "corrupt_once: repaired copy diverged"
    finally:
        src.close()
    assert _counter("nr_csum_reread") > rereads_before, \
        "corrupt_once: the flip was never detected/re-read"
    return "corrupt_once"


def scenario_rejoin(rng: random.Random, dirpath: str) -> str:
    """Fail-stop then recovery: the member must walk healthy -> failed
    during the task, then — via background canary probes alone — climb
    failed -> rejoining -> healthy once the device answers again."""
    from ..config import config
    from ..engine import Session
    from ..fault import HealthState
    from .fake import FakeStripedNvmeSource, FaultPlan

    config.set("io_retries", 1)
    config.set("task_deadline_s", 30.0)
    config.set("canary_interval_s", 0.05)
    config.set("quarantine_s", 0.2)
    config.set("rejoin_successes", 2)
    config.set("rejoin_tokens_s", 1000.0)
    victim = rng.choice([0, 2])
    after = rng.randrange(2, 6)
    # the dead window outlives the task's own read count (~35 with
    # retries and mirror legs): recovery is canary-driven, not incidental
    plan = FaultPlan(failstop_member=victim, failstop_after=after,
                     rejoin_after=after + rng.randrange(45, 65))
    paths = make_mirrored_members(dirpath, tag=f"rj{rng.randrange(1 << 16)}-")
    src = FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                fault_plan=plan, force_cached_fraction=0.0,
                                mirror="paired")
    canaries_before = _counter("nr_canary_probe")
    rejoins_before = _counter("nr_member_rejoin")
    try:
        with Session() as sess:
            got, total = read_all(sess, src)
            assert got == expected_mirrored_stream(paths)[:total], \
                "rejoin: degraded copy diverged from healthy stream"
            # canary probes advance the plan's read count past
            # rejoin_after, observe the recovery and warm the member back
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if sess._member_health.state(victim) is HealthState.HEALTHY:
                    break
                time.sleep(0.05)
            assert sess._member_health.state(victim) is HealthState.HEALTHY, \
                f"rejoin: member {victim} stuck in " \
                f"{sess._member_health.state(victim)}"
            steps = [(frm, to) for m, frm, to, _t
                     in sess._member_health.transitions(victim)]
            for edge in [("failed", "rejoining"), ("rejoining", "healthy")]:
                assert edge in steps, \
                    f"rejoin: member {victim} never took {edge}: {steps}"
            assert_transitions_legal(sess, "rejoin")
    finally:
        src.close()
    assert _counter("nr_canary_probe") > canaries_before, \
        "rejoin: no canary probe ever ran"
    assert _counter("nr_member_rejoin") > rejoins_before, \
        "rejoin: warmup never completed"
    return "rejoin"


def scenario_native_degraded(rng: random.Random, dirpath: str) -> str:
    """Native-path degraded striping: with a primary marked FAILED before
    submit, the io_uring lanes must read its extents from the mirror fd
    and still deliver the healthy stream."""
    from ..config import config
    from ..engine import Session, StripedSource

    class _Direct(StripedSource):
        def cached_fraction(self, offset, length):
            return 0.0

    config.set("task_deadline_s", 30.0)
    paths = make_mirrored_members(dirpath, tag=f"nd{rng.randrange(1 << 16)}-")
    src = _Direct(paths, stripe_chunk_size=STRIPE, mirror="paired")
    mirrors_before = _counter("nr_mirror_read")
    try:
        with Session() as sess:
            if sess._native is None:
                return "native_skipped"
            victim = rng.choice([0, 2])
            sess._member_health.record_failure(victim, fatal=True)
            got, total = read_all(sess, src)
            assert got == expected_mirrored_stream(paths)[:total], \
                "native_degraded: remapped copy diverged"
            assert_transitions_legal(sess, "native_degraded")
    finally:
        src.close()
    assert _counter("nr_mirror_read") > mirrors_before, \
        "native_degraded: no request was remapped to the mirror fd"
    return "native_degraded"


def scenario_scrub_heal(rng: random.Random, dirpath: str) -> str:
    """Seeded resident bit-rot across the hierarchy (ISSUE 16): a byte
    flipped in a HOST-resident ARC slab must be detected by the
    background scrubber and re-filled from SSD byte-identically; a KV
    spill block whose PRIMARY mirror leg rots on disk must be healed
    from the surviving replica at page-in with the rotten member debited
    into QUARANTINED (``quarantine_after=1``) — and every read stays
    byte-identical throughout."""
    from ..cache import residency_cache
    from ..config import config
    from ..engine import Session
    from ..fault import HealthState
    from ..serving.kvcache import KvBlockPool
    from .fake import FakeStripedNvmeSource, FaultPlan, flip_resident_host

    config.set("io_retries", 2)
    config.set("task_deadline_s", 30.0)
    config.set("integrity", "always")
    config.set("scrub_bytes_per_sec", 1 << 30)
    config.set("cache_arbitration", False)
    config.set("cache_bytes", 16 * CHUNK)   # whole stream stays resident
    config.set("dma_max_size", CHUNK)
    config.set("canary_interval_s", 0.0)    # the debit must HOLD
    config.set("quarantine_after", 1)
    config.set("quarantine_s", 60.0)
    residency_cache.clear()
    paths = make_mirrored_members(dirpath, tag=f"sh{rng.randrange(1 << 16)}-")
    src = FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                force_cached_fraction=0.0, mirror="paired")
    # KV spill set: every member-0 block row carries one seeded-rot byte
    # (flipped after the covering page-out lands); the mirror leg on
    # member 1 stays clean, so page-in heals are mirror-attributable
    bbk = 16 << 10
    rows = 4
    rot = rng.randrange(64, bbk - 64)
    spaths = []
    for i in range(4):
        p = os.path.join(dirpath, f"kv{rng.randrange(1 << 16)}-{i}.bin")
        with open(p, "wb") as f:
            f.truncate(rows * bbk)
        spaths.append(p)
    plan = FaultPlan(corrupt_member_offsets={
        0: {row * bbk + rot for row in range(rows)}})
    spill = FakeStripedNvmeSource(spaths, bbk, fault_plan=plan,
                                  force_cached_fraction=0.0,
                                  mirror="paired", writable=True)
    want = expected_mirrored_stream(paths)
    fails0 = _counter("nr_integrity_fail")
    repairs0 = _counter("nr_scrub_repair")
    try:
        with Session() as sess:
            # phase A: host-slab rot — the scrubber must catch and heal
            got, total = read_all(sess, src)
            assert got == want[:total], "scrub_heal: clean pass diverged"
            keys = residency_cache.scrub_keys()
            assert keys, "scrub_heal: nothing resident to corrupt"
            key = rng.choice(keys)
            assert flip_resident_host(key[0], key[1], key[2],
                                      pos=rng.randrange(key[2])), \
                "scrub_heal: resident flip missed"
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline \
                    and _counter("nr_scrub_repair") <= repairs0:
                time.sleep(0.02)
            assert _counter("nr_scrub_repair") > repairs0, \
                "scrub_heal: the scrubber never repaired the flipped slab"
            assert _counter("nr_integrity_fail") > fails0, \
                "scrub_heal: the flip was never detected"
            got, total = read_all(sess, src)
            assert got == want[:total], \
                "scrub_heal: post-heal stream diverged"
            # phase B: KV spill rot healed from the mirror at page-in,
            # member-attributed — stop the background scrubber so the
            # debit provably comes from the page-in verify
            config.set("scrub_bytes_per_sec", 0)
            repairs_a = _counter("nr_scrub_repair")
            # the pre-unification alias still sizes the pool
            config.set("kv_block_bytes", bbk)
            pool = KvBlockPool(sess, spill, ram_blocks=2, hbm_blocks=0)
            assert pool.block_bytes == bbk

            def pat(i: int) -> bytes:
                return bytes([(i * 7 + 1) % 256]) * bbk

            for i in range(6):
                pool.append("chaos", pat(i))
            for i in range(6):
                assert pool.read("chaos", i) == pat(i), \
                    f"scrub_heal: KV block {i} diverged after heal"
            assert _counter("nr_scrub_repair") > repairs_a, \
                "scrub_heal: no spill block was ever mirror-healed"
            assert sess._member_health.state(0) is HealthState.QUARANTINED, \
                f"scrub_heal: rotten member 0 ended " \
                f"{sess._member_health.state(0)}, wanted QUARANTINED"
            assert_transitions_legal(sess, "scrub_heal")
            pool.close()
    finally:
        src.close()
        spill.close()
        config.set("cache_bytes", 0)
        residency_cache.configure()
    return "scrub_heal"


def scenario_cache_churn(rng: random.Random, dirpath: str) -> str:
    """Seeded residency-tier churn racing a fail-stop (ISSUE 9): with
    capacity far below the table, repeated whole-stream reads fill and
    evict constantly while a mirrored member fail-stops mid-schedule and
    a write-back invalidation lands between passes.  Every pass must
    stay byte-identical to the healthy stream, and the trace dump must
    be schema-valid with fill -> evict -> refill in causal order on at
    least one extent."""
    from ..cache import residency_cache
    from ..config import config
    from ..engine import Session, open_source
    from ..trace import recorder, validate_chrome_trace
    from .fake import FakeStripedNvmeSource, FaultPlan

    config.set("io_retries", 2)
    config.set("task_deadline_s", 30.0)
    config.set("cache_arbitration", False)
    # 3 chunks of capacity under an 8-chunk logical stream: every pass
    # churns the ARC lists end to end
    config.set("cache_bytes", 3 * CHUNK)
    config.set("dma_max_size", CHUNK)
    config.set("trace_policy", "all")
    recorder.configure()
    recorder.clear()
    residency_cache.clear()
    victim = rng.choice([0, 2])
    plan = FaultPlan(failstop_member=victim,
                     failstop_after=rng.randrange(4, 12))
    paths = make_mirrored_members(dirpath, tag=f"cc{rng.randrange(1 << 16)}-")
    src = FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                fault_plan=plan, force_cached_fraction=0.0,
                                mirror="paired")
    fills0, evicts0 = _counter("nr_cache_fill"), _counter("nr_cache_evict")
    inval0 = _counter("nr_cache_invalidate")
    want = expected_mirrored_stream(paths)
    try:
        with Session() as sess:
            for rnd in range(3):
                got, total = read_all(sess, src)
                assert got == want[:total], \
                    f"cache_churn: pass {rnd} diverged from healthy stream"
                if rnd == 1:
                    # write-back invalidation racing the churn: identical
                    # bytes through a different framing of a shared
                    # member file, so the stream is unchanged but the
                    # tier must conservatively drop its extents
                    wpath = paths[victim + 1]  # the survivor mirror
                    with open(wpath, "rb") as f:
                        head = f.read(CHUNK)
                    handle, buf = sess.alloc_dma_buffer(CHUNK)
                    try:
                        buf.view()[:CHUNK] = head
                        with open_source(wpath, writable=True) as sink:
                            res = sess.memcpy_ram2ssd(sink, handle, [0],
                                                      CHUNK)
                            sess.memcpy_wait(res.dma_task_id)
                            sink.sync()
                    finally:
                        sess.unmap_buffer(handle)
    finally:
        src.close()
        doc = recorder.chrome_trace("chaos cache_churn")
        dump_path = recorder.dump(
            os.path.join(dirpath, "cache_churn.json"),
            reason="chaos cache_churn")
        config.set("trace_policy", "off")
        recorder.configure()
        recorder.clear()
        config.set("cache_bytes", 0)
        residency_cache.configure()
    assert _counter("nr_cache_fill") > fills0, "cache_churn: no fills"
    assert _counter("nr_cache_evict") > evicts0, "cache_churn: no evictions"
    assert _counter("nr_cache_invalidate") > inval0, \
        "cache_churn: the write-back dropped nothing"
    errs = validate_chrome_trace(doc)
    assert not errs, \
        f"cache_churn: trace dump fails schema check: {errs[:5]}"
    # causal fill -> evict -> refill on at least one extent
    by_off: dict = {}
    for ev in doc["traceEvents"]:
        nm = ev.get("name")
        if nm in ("cache_fill", "cache_evict"):
            off = ev.get("args", {}).get("offset")
            if off is not None:
                by_off.setdefault(off, []).append((ev["ts"], nm))
    cycled = 0
    for off, evs in by_off.items():
        evs.sort()
        names = [n for _, n in evs]
        for i in range(len(names) - 2):
            if names[i] == "cache_fill" and names[i + 1] == "cache_evict" \
                    and names[i + 2] == "cache_fill":
                cycled += 1
                break
    assert cycled > 0, \
        f"cache_churn: no extent shows fill->evict->refill " \
        f"(dump: {dump_path})"
    return "cache_churn"


# ---------------------------------------------------------------------------
# write-side scenarios (ISSUE 11): the survival contract, mirrored
# ---------------------------------------------------------------------------

def write_all(sess, sink, payload: bytes, chunk: int = CHUNK,
              timeout: float = 60.0) -> None:
    """Drive a whole-stream RAM→SSD write of *payload* and wait it out."""
    handle, buf = sess.alloc_dma_buffer(len(payload))
    try:
        buf.view()[:len(payload)] = payload
        res = sess.memcpy_ram2ssd(sink, handle, list(range(len(payload) // chunk)),
                                  chunk)
        sess.memcpy_wait(res.dma_task_id, timeout=timeout)
        sink.sync()
    finally:
        sess.unmap_buffer(handle)


def assert_pairs_identical(paths, scenario: str) -> None:
    """Every mirror pair must hold byte-identical files — the rejoin
    contract: a rejoined disk never differs from the replica that covered
    for it."""
    for pri in range(0, len(paths), 2):
        with open(paths[pri], "rb") as a, open(paths[pri + 1], "rb") as b:
            if a.read() != b.read():
                raise AssertionError(
                    f"{scenario}: mirror pair {pri}/{pri + 1} diverged "
                    f"after resync")


def _await_healthy(sess, member: int, scenario: str,
                   deadline_s: float = 20.0) -> None:
    from ..fault import HealthState
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if sess._member_health.state(member) is HealthState.HEALTHY:
            return
        time.sleep(0.05)
    raise AssertionError(
        f"{scenario}: member {member} stuck in "
        f"{sess._member_health.state(member)} with "
        f"{sess._resync.pending_bytes(member)} resync bytes pending")


def scenario_write_failstop(rng: random.Random, dirpath: str) -> str:
    """A mirrored primary fail-stops for WRITES mid-stream (reads keep
    answering — the canary's view of the device is fine, the media is
    not): the stream must retire with the victim's extents landed on the
    mirror and journaled, the rejoin replay must copy them back once the
    member writes again, and HEALTHY must not be reached before the
    journal drains — after which both pair files are byte-identical and
    a logical read-back returns exactly the written payload."""
    from ..config import config
    from ..engine import Session
    from .fake import FakeStripedNvmeSource, FaultPlan

    config.set("io_retries", 1)
    config.set("task_deadline_s", 30.0)
    config.set("canary_interval_s", 0.05)
    config.set("quarantine_s", 0.1)
    config.set("rejoin_successes", 2)
    config.set("rejoin_tokens_s", 1000.0)
    config.set("dma_max_size", STRIPE)     # one request per stripe extent
    config.set("member_queue_depth", 1)    # fail-stop bites mid-stream
    victim = rng.choice([0, 2])
    after = rng.randrange(2, 5)
    plan = FaultPlan(write_failstop_member=victim,
                     write_failstop_after=after,
                     write_rejoin_after=after + rng.randrange(4, 9))
    paths = make_mirrored_members(dirpath, tag=f"wf{rng.randrange(1 << 16)}-")
    sink = FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                 fault_plan=plan, force_cached_fraction=0.0,
                                 mirror="paired", writable=True)
    payload = rng.randbytes(2 * MEMBER_SIZE)
    resyncs_before = _counter("nr_resync_extent")
    try:
        with Session() as sess:
            write_all(sess, sink, payload)
            _await_healthy(sess, victim, "write_failstop")
            assert sess._resync.pending_bytes(victim) == 0, \
                "write_failstop: HEALTHY with resync debt outstanding"
            got, total = read_all(sess, sink)
            assert got == payload[:total], \
                "write_failstop: logical read-back diverged from payload"
            assert_transitions_legal(sess, "write_failstop")
    finally:
        sink.close()
    assert _counter("nr_resync_extent") > resyncs_before, \
        "write_failstop: nothing was ever replayed from the journal"
    assert_pairs_identical(paths, "write_failstop")
    return "write_failstop"


def scenario_write_enospc(rng: random.Random, dirpath: str) -> str:
    """An ENOSPC storm on an unmirrored sink: PERSISTENT taxonomy means
    the FIRST error latches the task — no retry storm against a full
    disk (the write-retry counter must not move)."""
    import errno as _errno

    from ..api import StromError
    from ..config import config
    from ..engine import Session
    from .fake import FakeNvmeSource, FaultPlan
    from .fake import make_test_file as _mk

    config.set("io_retries", 3)
    config.set("task_deadline_s", 30.0)
    config.set("dma_max_size", STRIPE)
    path = os.path.join(dirpath, f"en{rng.randrange(1 << 16)}.bin")
    _mk(path, MEMBER_SIZE)
    plan = FaultPlan(write_fail_every_nth=rng.choice([2, 3]),
                     write_errno=_errno.ENOSPC)
    sink = FakeNvmeSource(path, fault_plan=plan, force_cached_fraction=0.0,
                          writable=True)
    retries_before = _counter("nr_write_retry")
    try:
        with Session() as sess:
            handle, buf = sess.alloc_dma_buffer(MEMBER_SIZE)
            try:
                buf.view()[:MEMBER_SIZE] = rng.randbytes(MEMBER_SIZE)
                res = sess.memcpy_ram2ssd(sink, handle,
                                          list(range(MEMBER_SIZE // CHUNK)),
                                          CHUNK)
                try:
                    sess.memcpy_wait(res.dma_task_id, timeout=30.0)
                    raise AssertionError(
                        "write_enospc: a full disk did not fail the task")
                except StromError as e:
                    assert e.errno == _errno.ENOSPC, \
                        f"write_enospc: latched {e.errno}, wanted ENOSPC"
            finally:
                sess.unmap_buffer(handle)
    finally:
        sink.close()
    assert _counter("nr_write_retry") == retries_before, \
        "write_enospc: a PERSISTENT errno was retried"
    return "write_enospc"


def scenario_write_torn_mirror(rng: random.Random, dirpath: str) -> str:
    """Crash between the mirror legs: the MIRROR member dies after the
    primary leg lands (write-side fail-stop on an odd member), leaving
    the pair torn — the journal owns the mirror's missed extents and the
    replay heals the tear from the primary, with ``write_verify`` armed
    the whole way (read-back of surviving legs must stay clean)."""
    from ..config import config
    from ..engine import Session
    from .fake import FakeStripedNvmeSource, FaultPlan

    config.set("io_retries", 1)
    config.set("task_deadline_s", 30.0)
    config.set("canary_interval_s", 0.05)
    config.set("quarantine_s", 0.1)
    config.set("rejoin_successes", 2)
    config.set("rejoin_tokens_s", 1000.0)
    config.set("dma_max_size", STRIPE)
    config.set("member_queue_depth", 1)
    config.set("write_verify", True)
    victim = rng.choice([1, 3])            # a REPLICA tears, not a primary
    after = rng.randrange(2, 5)
    plan = FaultPlan(write_failstop_member=victim,
                     write_failstop_after=after,
                     write_rejoin_after=after + rng.randrange(4, 9))
    paths = make_mirrored_members(dirpath, tag=f"tn{rng.randrange(1 << 16)}-")
    sink = FakeStripedNvmeSource(paths, stripe_chunk_size=STRIPE,
                                 fault_plan=plan, force_cached_fraction=0.0,
                                 mirror="paired", writable=True)
    payload = rng.randbytes(2 * MEMBER_SIZE)
    try:
        with Session() as sess:
            write_all(sess, sink, payload)
            _await_healthy(sess, victim, "write_torn_mirror")
            got, total = read_all(sess, sink)
            assert got == payload[:total], \
                "write_torn_mirror: logical read-back diverged"
            assert_transitions_legal(sess, "write_torn_mirror")
    finally:
        sink.close()
    assert_pairs_identical(paths, "write_torn_mirror")
    return "write_torn_mirror"


_CKPT_CRASH_CHILD = r"""
import sys, time
import numpy as np
import nvme_strom_tpu.data.checkpoint as ck
_orig = ck.np.ascontiguousarray
def _slow(a, *k, **kw):
    time.sleep(0.08)           # widen the tmp-file-present window
    return _orig(a, *k, **kw)
ck.np.ascontiguousarray = _slow
tree = {f"leaf{i:02d}": np.full(1024, i, np.float32) for i in range(48)}
ck.save_checkpoint(sys.argv[1], tree)
print("child save finished (should have been killed)")
"""


def scenario_ckpt_crash(rng: random.Random, dirpath: str) -> str:
    """Crash-consistency of the checkpoint writer: SIGKILL a child
    mid-save over an existing checkpoint.  The prior checkpoint must
    restore byte-identical (crc-verified), the dead child's temp litter
    must survive until it ages out and then be reaped by the next save,
    and ``strom_ckpt verify`` must pass on the final file."""
    import glob
    import signal
    import subprocess

    import numpy as np

    from ..data.checkpoint import (_TMP_SWEEP_AGE_S, restore_checkpoint,
                                   save_checkpoint)
    from ..tools.strom_ckpt import main as ckpt_cli

    path = os.path.join(dirpath, "model.strom")
    prior = {f"leaf{i:02d}": np.full(1024, 1000 + i, np.float32)
             for i in range(48)}
    save_checkpoint(path, prior)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    child = subprocess.Popen(
        [sys.executable, "-c", _CKPT_CRASH_CHILD, path],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        # the slow leaf pre-pass runs before mkstemp; poll for the temp
        deadline = time.monotonic() + 120.0
        tmps = []
        while time.monotonic() < deadline:
            tmps = glob.glob(path + ".tmp.*")
            if tmps:
                break
            if child.poll() is not None:
                raise AssertionError(
                    f"ckpt_crash: child exited rc={child.returncode} "
                    f"before its temp file appeared")
            time.sleep(0.02)
        assert tmps, "ckpt_crash: no temp file ever appeared"
        time.sleep(0.3)        # let it get some leaves deep
        child.send_signal(signal.SIGKILL)
        rc = child.wait(timeout=30.0)
        assert rc == -signal.SIGKILL, f"ckpt_crash: child rc {rc}"
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=30.0)
    # 1. the installed checkpoint is untouched: crc-verified restore
    out = restore_checkpoint(path, verify=True)
    for k, v in prior.items():
        got = np.asarray(out[f"['{k}']"])
        assert np.array_equal(got, v), f"ckpt_crash: leaf {k} diverged"
    # 2. the kill left litter; a fresh save must NOT reap it while young
    litter = glob.glob(path + ".tmp.*")
    assert litter, "ckpt_crash: the SIGKILL left no temp litter to test"
    final = {f"leaf{i:02d}": np.full(1024, 2000 + i, np.float32)
             for i in range(48)}
    save_checkpoint(path, final)
    assert set(glob.glob(path + ".tmp.*")) >= set(litter), \
        "ckpt_crash: young litter was swept (concurrent-save hazard)"
    # 3. ...and must reap it once it ages past the sweep horizon
    old = time.time() - _TMP_SWEEP_AGE_S - 60.0
    for t in litter:
        os.utime(t, (old, old))
    save_checkpoint(path, final)
    assert not glob.glob(path + ".tmp.*"), \
        "ckpt_crash: aged litter survived the sweep"
    # 4. the final checkpoint passes the CLI corruption oracle
    assert ckpt_cli(["verify", path]) == 0, \
        "ckpt_crash: strom_ckpt verify failed on the final checkpoint"
    return "ckpt_crash"


SCENARIOS = (scenario_fail_stop, scenario_flaky, scenario_slow_hedge,
             scenario_corrupt_once, scenario_rejoin,
             scenario_native_degraded, scenario_cache_churn,
             scenario_scrub_heal)

SCENARIOS_WRITE = (scenario_write_failstop, scenario_write_enospc,
                   scenario_write_torn_mirror, scenario_ckpt_crash)


def flaky_mirrored_round(rng: random.Random, dirpath: str) -> str:
    """Entry point for the stress driver: one mirrored flaky round."""
    return scenario_flaky(rng, dirpath)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_all(seed: int, rounds: int = 1, verbose: bool = True,
            scenarios=SCENARIOS) -> dict:
    from ..config import config
    tally: dict = {}
    for r in range(rounds):
        for i, scenario in enumerate(scenarios):
            # integer-derived per-scenario seed: hash() of a str would
            # change per process (PYTHONHASHSEED) and kill reproducibility
            rng = random.Random(seed * 1_000_003 + r * 101 + i)
            snap = config.snapshot()
            with tempfile.TemporaryDirectory() as d:
                t0 = time.monotonic()
                try:
                    tag = scenario(rng, d)
                finally:
                    config.restore(snap)
                if verbose:
                    print(f"  chaos[{r}] {scenario.__name__}: {tag} "
                          f"({time.monotonic() - t0:.1f}s)", flush=True)
            tally[tag] = tally.get(tag, 0) + 1
    return tally


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    sets = {"read": SCENARIOS, "write": SCENARIOS_WRITE,
            "all": SCENARIOS + SCENARIOS_WRITE}
    which = argv[0] if argv else "read"
    if which not in sets:
        print(f"usage: chaos [{'|'.join(sets)}]", file=sys.stderr)
        return 2
    seed = int(os.environ.get("STROM_CHAOS_SEED", "1234"))
    rounds = int(os.environ.get("STROM_CHAOS_ROUNDS", "1"))
    t0 = time.monotonic()
    tally = run_all(seed, rounds, scenarios=sets[which])
    from ..stats import stats
    c = stats.snapshot(reset_max=False).counters
    print(f"chaos OK: {sum(tally.values())} scenarios in "
          f"{time.monotonic() - t0:.1f}s (seed={seed}) — "
          + ", ".join(f"{k}={v}" for k, v in sorted(tally.items()))
          + f"; hedges won {c.get('nr_hedge_won', 0)}/"
          f"{c.get('nr_hedge_issued', 0)}, "
          f"mirror reads {c.get('nr_mirror_read', 0)}, "
          f"mirror writes {c.get('nr_mirror_write', 0)}, "
          f"resync extents {c.get('nr_resync_extent', 0)}, "
          f"canaries {c.get('nr_canary_probe', 0)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
