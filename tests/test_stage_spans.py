"""Stages of the load path on the device trace's clock (``stats.stage``).

A staged ``load_file_to_device`` under ``jax.profiler`` must leave one
``strom.<stage>`` span per stage entry on the calling thread's host line,
nested in the caller's own annotation; its counters must count entries;
with the profiler off no profiler span may even be built; and the
``strom.clock`` anchor must shift a flight-recorder span onto the trace's
time base."""

import glob
import importlib
import os

import jax
import pytest

from nvme_strom_tpu import Session, config, open_source
from nvme_strom_tpu.hbm.staging import load_file_to_device
from nvme_strom_tpu.stats import stats
from nvme_strom_tpu.trace import chrome_trace_from_events, recorder

#: the module (the package's ``stats`` attribute is the registry)
stats_mod = importlib.import_module("nvme_strom_tpu.stats")

STAGES = {"ioctl_memcpy_submit", "ioctl_memcpy_wait", "staging_fence",
          "staging_retire", "load_setup"}
STAGING = 1 << 20                  # a 4 MiB file is 4 staged batches
#: three buffers: the 4th batch refills the first, so one load fences once
BUFFERS = 3
CALLER = "caller.load"


def _load(path, sess):
    with jax.profiler.TraceAnnotation(CALLER), open_source(path) as src:
        return load_file_to_device(src, session=sess, staging_bytes=STAGING)


def _traced_load(path, sess, tdir, others=None):
    """One load under the profiler (host spans only, as the benchmark
    traces); returns the host line that holds the caller's annotation
    as [(start_ns, end_ns, name, stats)], and appends every other line
    to *others* when given."""
    from jax._src.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        _load(path, sess)
    finally:
        jax.profiler.stop_trace()
    pb, = glob.glob(os.path.join(str(tdir), "plugins", "profile", "*",
                                 "*.xplane.pb"))
    caller = None
    for plane in ProfileData.from_file(pb).planes:
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                    dict(e.stats) if e.name == "strom.clock" else None)
                   for e in line.events]
            if caller is None and any(n == CALLER for _, _, n, _ in evs):
                caller = evs
            elif others is not None:
                others.append(evs)
    if caller is None:
        raise AssertionError("no host line holds the caller's annotation")
    return caller


def _counters(sess):
    """The session's counters, the native engine's folded in."""
    return dict(sess.stat_info().counters)


def _delta(sess, before):
    after = _counters(sess)
    return {k: after[k] - before.get(k, 0) for k in after}


@pytest.fixture()
def staged():
    config.set("landing", "staged")
    config.set("staging_buffers", BUFFERS)
    sess = Session()
    yield sess
    sess.close()


def test_staged_load_leaves_stage_spans_on_the_callers_line(
        tmp_data_file, staged, tmp_path):
    _load(tmp_data_file, staged)              # compiles the landing
    before = _counters(staged)
    others = []
    line = _traced_load(tmp_data_file, staged, tmp_path / "trace", others)
    d = _delta(staged, before)
    (c0, c1), = [(a, b) for a, b, n, _ in line if n == CALLER]
    spans = [(a, b, n) for a, b, n, _ in line if n.startswith("strom.")]
    assert {n for _, _, n in spans} == {"strom." + s for s in STAGES} | \
        {"strom.clock"}
    assert all(c0 <= a <= b <= c1 for a, b, _ in spans)
    per = {s: sum(1 for *_, n in spans if n == "strom." + s)
           for s in STAGES}
    # one span per entry; load_setup is one entry in four pieces
    assert d["nr_staging_retire"] == per["staging_retire"] == 4
    assert d["nr_load_setup"] == 1 and per["load_setup"] == 5
    assert d["nr_ioctl_memcpy_submit"] == per["ioctl_memcpy_submit"] == 4
    # each batch is one 1 MiB write-back run, copied on the I/O pool: a
    # strom.writeback_copy span on a pool thread's line, none on the
    # caller's, inside the load, after the first submit has queued it
    assert d["nr_pagecache_copy"] == 0
    copies = [(a, b) for ln in others for a, b, n, _ in ln
              if n == "strom.writeback_copy"]
    assert d["nr_writeback_copy"] == len(copies) == 4
    assert not any(n == "strom.writeback_copy" for *_, n in spans)
    first_sub = min(a for a, _, n in spans
                    if n == "strom.ioctl_memcpy_submit")
    assert all(first_sub <= p <= q <= c1 for p, q in copies)


@pytest.mark.parametrize("arbitration", [True, False])
def test_pagecache_copies_only_where_arbitration_writes_back(
        tmp_data_file, staged, arbitration):
    config.set("cache_arbitration", arbitration)
    before = _counters(staged)
    arr = _load(tmp_data_file, staged)
    d = _delta(staged, before)
    assert arr.size == 4 << 20
    assert d["nr_staging_retire"] == 4 and d["nr_load_setup"] == 1
    assert d["nr_staging_fence"] == 1      # the 4th batch reuses a buffer
    assert d["nr_staging_fence_blocked"] <= d["nr_staging_fence"]
    assert d["clk_load_setup"] > 0
    # a freshly written file is page-cache resident: with arbitration on
    # every chunk is copied from the page cache (inline or on the I/O
    # pool), with it off none is
    assert (d["nr_pagecache_copy"] + d["nr_writeback_copy"] > 0) \
        == arbitration
    assert (d["nr_submit_dma"] == 0) == arbitration


class _SpyTraceMe:
    """Stands in for jaxlib's TraceMe: counts constructions."""
    on = False
    made = 0

    @classmethod
    def is_enabled(cls):
        return cls.on

    def __init__(self, name, **kw):
        type(self).made += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_profiler_off_builds_no_traceme(tmp_data_file, staged, monkeypatch):
    monkeypatch.setattr(stats_mod, "_TraceMe", _SpyTraceMe)
    monkeypatch.setattr(_SpyTraceMe, "made", 0)
    before = _counters(staged)
    _load(tmp_data_file, staged)
    assert _SpyTraceMe.made == 0
    # counted all the same
    assert _delta(staged, before)["nr_staging_retire"] == 4
    monkeypatch.setattr(_SpyTraceMe, "on", True)       # the control
    with stats.stage("staging_fence"):
        pass
    assert _SpyTraceMe.made == 2                       # anchor + the stage


def test_clock_anchor_shifts_recorder_spans_onto_the_trace(
        tmp_data_file, tmp_path):
    config.set("landing", "staged")
    config.set("trace_policy", "all")
    recorder.configure()
    sess = Session()
    try:
        _load(tmp_data_file, sess)            # an entry with the profiler off
        recorder.clear()
        line = _traced_load(tmp_data_file, sess, tmp_path / "trace")
        events = recorder.snapshot_events()
    finally:
        sess.close()
    (anchor_ns, _, _, st), = [e for e in line if e[2] == "strom.clock"]
    offset = anchor_ns - int(st["mono_ns"])
    waits = sorted(a for a, _, n, _ in line if n == "strom.ioctl_memcpy_wait")
    rings = [e for e in events if e[2] == "wait" and e[1] is not None]
    assert rings and waits
    for ev in rings:
        shifted = ev[0] + offset
        assert min(abs(shifted - w) for w in waits) < 1_000_000
    doc = chrome_trace_from_events(events)
    assert doc["otherData"]["clock"] == "CLOCK_MONOTONIC"


def _traced_query(q, tdir, caller="caller.query"):
    """One run of *q* under the profiler; returns the result and the host
    line holding the caller's annotation as [(start_ns, end_ns, name)]."""
    out, lines = _traced_query_lines(q, tdir, caller)
    for evs in lines:
        if any(n == caller for _, _, n in evs):
            return out, evs
    raise AssertionError("no host line holds the caller's annotation")


def _traced_query_lines(q, tdir, caller):
    """One run of *q* under the profiler; returns the result and every
    line of the trace as [(start_ns, end_ns, name)]."""
    from jax._src.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(caller):
            out = q.run()
    finally:
        jax.profiler.stop_trace()
    pb, = glob.glob(os.path.join(str(tdir), "plugins", "profile", "*",
                                 "*.xplane.pb"))
    return out, [[(e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in line.events]
                 for plane in ProfileData.from_file(pb).planes
                 for line in plane.lines]


def test_group_discover_stage_nests_its_scan(tmp_path):
    """Value-keyed GROUP BY finds its keys inside the aggregation scan:
    one ``group_discover`` entry, the bootstrap of the key set from the
    first batch, is a ``strom.group_discover`` span nested in that
    batch's ``staging_retire``, between the first read and the second;
    each batch is read once.  From a fresh sidecar it is still one
    entry, with no table read inside."""
    import numpy as np

    from nvme_strom_tpu.scan.heap import HeapSchema, build_heap_file
    from nvme_strom_tpu.scan.index import build_index
    from nvme_strom_tpu.scan.query import Query
    schema = HeapSchema(n_cols=3, visibility=False)
    n = schema.tuples_per_page * 1500             # two 8 MiB batches
    rng = np.random.default_rng(5)
    cols = [rng.integers(0, 3, n).astype(np.int32),
            rng.integers(0, 2, n).astype(np.int32),
            rng.integers(-9, 9, n).astype(np.int32)]
    path = str(tmp_path / "g.heap")
    build_heap_file(path, cols, schema)

    def query():
        return Query(path, schema).group_by_cols(
            [0, 1], agg_exprs=[("bin", "*", ("col", 2), ("lit", 3))])

    query().run()                                 # compiles
    before = dict(stats.snapshot().counters)
    out, line = _traced_query(query(), tmp_path / "t1")
    d = {k: v - before.get(k, 0)
         for k, v in stats.snapshot().counters.items()}
    assert out["count"].sum() == n
    assert d["nr_group_discover"] == 1 and d["clk_group_discover"] > 0
    # two batches, each read once; the bootstrap's gather is the third
    # kernel call, and no batch missed a key
    assert d["nr_vfs_read"] == 2 and d["nr_kernel_dispatch"] == 3
    assert d["nr_group_key_grow"] == d["nr_group_rerun"] == 0
    (g0, g1), = [(a, b) for a, b, nm in line
                 if nm == "strom.group_discover"]
    reads = sorted((a, b) for a, b, nm in line if nm == "strom.vfs_read")
    retires = sorted((a, b) for a, b, nm in line
                     if nm == "strom.staging_retire")
    assert len(reads) == len(retires) == 2
    assert retires[0][0] <= g0 and g1 <= retires[0][1]
    assert reads[0][1] <= g0 and g1 <= reads[1][0]
    build_index(path, schema, (0, 1))
    before = dict(stats.snapshot().counters)
    out2 = query().run()
    d = {k: v - before.get(k, 0)
         for k, v in stats.snapshot().counters.items()}
    assert d["nr_group_discover"] == 1 and d["nr_vfs_read"] == 2
    assert out2["count"].tolist() == out["count"].tolist()


def test_vfs_readahead_spans_sit_on_the_readers_line(tmp_path):
    """The buffered scan's reads run on the ring's reader thread: one
    ``strom.vfs_readahead`` span per batch, on a host line other than
    the caller's, which keeps one ``strom.vfs_read`` wait per batch, in
    batch order before that batch's ``staging_retire``."""
    import numpy as np

    from nvme_strom_tpu.scan.heap import HeapSchema, build_heap_file
    from nvme_strom_tpu.scan.query import Query
    schema = HeapSchema(n_cols=2, visibility=False)
    config.set("debug_no_threshold", False)
    n = schema.tuples_per_page * 2500             # three 8 MiB batches
    c0 = np.arange(n, dtype=np.int32) % 11
    path = str(tmp_path / "r.heap")
    build_heap_file(path, [c0, c0], schema)

    def query():
        return Query(path, schema).where(lambda cols: cols[0] > 4)

    query().run()                                 # compiles
    out, lines = _traced_query_lines(query(), tmp_path / "t",
                                     "caller.query")
    assert int(out["count"]) == int((c0 > 4).sum())
    caller, = [ln for ln in lines
               if any(nm == "caller.query" for _, _, nm in ln)]
    ahead = [ln for ln in lines if ln is not caller
             and any(nm == "strom.vfs_readahead" for _, _, nm in ln)]
    assert len(ahead) == 1
    assert sum(nm == "strom.vfs_readahead" for _, _, nm in ahead[0]) == 3
    assert not any(nm == "strom.vfs_readahead" for _, _, nm in caller)
    reads = sorted((a, b) for a, b, nm in caller if nm == "strom.vfs_read")
    retires = sorted((a, b) for a, b, nm in caller
                     if nm == "strom.staging_retire")
    assert len(reads) == len(retires) == 3
    assert all(r[1] <= t[0] for r, t in zip(reads, retires))
    assert all(t[1] <= r[0] for t, r in zip(retires, reads[1:]))
