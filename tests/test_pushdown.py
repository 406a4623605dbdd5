"""Compute-pushdown tests (ISSUE 14, ``pushdown`` marker).

Codec round-trips against the pure-numpy oracle (per encoding, edges
included), fused-kernel vs oracle identity (Pallas interpret mode and
the XLA fallback), the planner's per-column host/chip/raw decision under
forced transport rates, EXPLAIN's wire-byte prediction, packed extents
riding the residency tier (hits after eviction churn, logical
accounting) and the fault ladder (a fail-stopped member's packed
extents served by its mirror)."""

import os

import numpy as np
import pytest

from nvme_strom_tpu.config import config
from nvme_strom_tpu.scan.colpack import (build_packed, decode_file_numpy,
                                         load_meta, packed_path_for,
                                         probe_packed)
from nvme_strom_tpu.scan.heap import HeapSchema, build_heap_file
from nvme_strom_tpu.scan.planner import decide_pushdown
from nvme_strom_tpu.scan.query import Query
from nvme_strom_tpu.stats import stats

pytestmark = pytest.mark.pushdown


def _build(tmp_path, cols, dtypes, *, codecs=None, tag="t"):
    schema = HeapSchema(len(cols), dtypes=tuple(dtypes))
    path = str(tmp_path / f"{tag}.tbl")
    build_heap_file(path, [np.asarray(c) for c in cols], schema)
    meta = build_packed(path, schema, codecs=codecs)
    return path, schema, meta


def _roundtrip(path, meta, cols):
    got, n = decode_file_numpy(packed_path_for(path), meta)
    assert n == len(cols[0])
    for c, (g, want) in enumerate(zip(got, cols)):
        np.testing.assert_array_equal(
            g, np.asarray(want), err_msg=f"column {c} diverged")


# ---------------------------------------------------------------------------
# codec round-trips (encoder vs the independent numpy decoder)
# ---------------------------------------------------------------------------

def test_roundtrip_bitpack(tmp_path):
    """Small-span ints pick bitpack (frame-of-reference + planar bits)
    and survive the round trip; a nonzero minimum exercises the FOR
    base."""
    n = 10_000
    c0 = (np.arange(n) % 13 + 100).astype(np.int32)
    path, _s, meta = _build(tmp_path, [c0], ["i4"], codecs=("bitpack",))
    assert meta.cols[0].codec == "bitpack"
    _roundtrip(path, meta, [c0])


def test_roundtrip_negatives_fall_back_to_raw(tmp_path):
    """Negative int32 bit patterns span the whole uint32 domain, so
    bitpack can't pay — raw still round-trips them exactly."""
    n = 8_000
    c0 = (np.arange(n) % 13 - 6).astype(np.int32)
    path, _s, meta = _build(tmp_path, [c0], ["i4"], codecs=("bitpack",))
    assert meta.cols[0].codec == "raw"
    _roundtrip(path, meta, [c0])


def test_roundtrip_rle_and_single_run(tmp_path):
    """Run-heavy and constant (single-run-per-block) columns under a
    forced rle-only codec set."""
    n = 9_000
    runs = np.repeat(np.arange(30, dtype=np.int32) * 7, 300)[:n]
    const = np.full(n, 42, np.int32)
    path, _s, meta = _build(tmp_path, [runs, const], ["i4", "i4"],
                            codecs=("rle",))
    assert meta.cols[1].codec == "rle"
    _roundtrip(path, meta, [runs, const])


def test_roundtrip_dict(tmp_path):
    """Low-cardinality scattered values pick dict; the slot table is
    per-block so the same value set round-trips at any offset."""
    rng = np.random.default_rng(7)
    vals = np.array([3, 1000, -5, 7, 123456], np.int32)
    c0 = vals[rng.integers(0, len(vals), 20_000)]
    path, _s, meta = _build(tmp_path, [c0], ["i4"], codecs=("dict",))
    assert meta.cols[0].codec == "dict"
    _roundtrip(path, meta, [c0])


def test_roundtrip_all_distinct_falls_back_to_raw(tmp_path):
    """High-entropy data defeats every codec: raw must win and still
    round-trip (the packed file then predicts ~no wire savings)."""
    rng = np.random.default_rng(11)
    c0 = rng.integers(-(2**31), 2**31, 8192, dtype=np.int64) \
        .astype(np.int32)
    path, _s, meta = _build(tmp_path, [c0], ["i4"])
    assert meta.cols[0].codec == "raw"
    _roundtrip(path, meta, [c0])


def test_roundtrip_empty_table(tmp_path):
    c0 = np.empty(0, np.int32)
    path, _s, meta = _build(tmp_path, [c0], ["i4"])
    assert meta.n_rows == 0 and meta.n_blocks == 0
    got, n = decode_file_numpy(packed_path_for(path), meta)
    assert n == 0 and len(got[0]) == 0


def test_roundtrip_uneven_tail_and_float(tmp_path):
    """n_rows deliberately not a multiple of rows_per_block; the float
    column packs by bit pattern (dict over f4) and must restore exact
    bit patterns, NaN included."""
    n = 5_001
    c0 = (np.arange(n) % 9).astype(np.int32)
    f = np.array([1.5, -0.0, np.nan, 3.25], np.float32)
    c1 = f[np.arange(n) % len(f)]
    path, _s, meta = _build(tmp_path, [c0, c1], ["i4", "f4"])
    assert meta.n_rows % meta.rows_per_block != 0
    got, nr = decode_file_numpy(packed_path_for(path), meta)
    assert nr == n
    np.testing.assert_array_equal(got[0], c0)
    np.testing.assert_array_equal(got[1].view(np.uint32),
                                  c1.view(np.uint32))


def test_roundtrip_uint32_extremes(tmp_path):
    """Full uint32 domain values (bit-patterns near 2^32) survive the
    frame-of-reference math without wraparound."""
    c0 = np.array([0, 1, 2**31, 2**32 - 1, 2**32 - 2] * 1000,
                  np.uint32).view(np.int32)
    path, _s, meta = _build(tmp_path, [c0], ["i4"])
    _roundtrip(path, meta, [c0])


def test_probe_staleness(tmp_path):
    """Any table write retires the sidecar (size+mtime stamp)."""
    c0 = np.arange(4096, dtype=np.int32) % 4
    path, schema, meta = _build(tmp_path, [c0], ["i4"])
    assert probe_packed(path) is not None
    st = os.stat(path)
    os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns + 1))
    assert probe_packed(path) is None


# ---------------------------------------------------------------------------
# fused kernels vs the numpy oracle
# ---------------------------------------------------------------------------

def _mixed_table(tmp_path, n=20_000):
    rng = np.random.default_rng(3)
    c0 = (np.arange(n) % 16).astype(np.int32)               # bitpack
    c1 = np.repeat(np.arange((n + 511) // 512, dtype=np.int32) % 6,
                   512)[:n]                                  # rle-ish
    c2 = rng.integers(0, 50, n).astype(np.int32)             # dict/bitpack
    return _build(tmp_path, [c0, c1, c2], ["i4"] * 3), (c0, c1, c2)


def _oracle(cols, pred_np):
    sel = pred_np(cols)
    return (int(sel.sum()),
            [int(c[sel].astype(np.int64).sum()) for c in cols])


def test_decode_kernels_match_numpy_oracle(tmp_path):
    """XLA fallback and Pallas (interpret) fused decode+filter produce
    the oracle's count and byte-identical integer sums."""
    from nvme_strom_tpu.ops.decode_pallas import make_decode_filter_fn_pallas
    from nvme_strom_tpu.ops.decode_xla import make_decode_filter_fn_xla

    (path, schema, meta), cols = _mixed_table(tmp_path)
    pred = lambda c: c[0] > 7
    want_count, want_sums = _oracle(cols, lambda c: c[0] > 7)
    with open(packed_path_for(path), "rb") as f:
        pages = np.frombuffer(f.read(), np.uint8).reshape(-1, 8192)
    for fn in (make_decode_filter_fn_xla(meta, pred),
               make_decode_filter_fn_pallas(meta, schema, pred,
                                            interpret=True)):
        out = fn(pages)
        assert int(out["count"]) == want_count
        assert [int(s) for s in out["sums"]] == want_sums


def test_decode_kernels_no_predicate_projection(tmp_path):
    """Projection fusion: un-needed columns sum to zero, needed ones to
    the oracle totals, with no predicate (every valid row)."""
    from nvme_strom_tpu.ops.decode_xla import make_decode_filter_fn_xla

    (path, schema, meta), cols = _mixed_table(tmp_path)
    with open(packed_path_for(path), "rb") as f:
        pages = np.frombuffer(f.read(), np.uint8).reshape(-1, 8192)
    out = make_decode_filter_fn_xla(meta, None, need_cols=(2,))(pages)
    assert int(out["count"]) == len(cols[0])
    assert int(out["sums"][0]) == 0 and int(out["sums"][1]) == 0
    assert int(out["sums"][2]) == int(cols[2].astype(np.int64).sum())


# ---------------------------------------------------------------------------
# planner decision + EXPLAIN surface
# ---------------------------------------------------------------------------

def test_planner_decision_flips_with_forced_rates(tmp_path):
    (path, _schema, meta), _cols = _mixed_table(tmp_path)
    config.set("pushdown_h2d_gbps", 1.0)
    config.set("pushdown_ssd_gbps", 4.0)    # h2d-bound -> chip
    assert decide_pushdown(meta).mode == "chip"
    config.set("pushdown_h2d_gbps", 4.0)
    config.set("pushdown_ssd_gbps", 1.0)    # SSD-bound -> host
    assert decide_pushdown(meta).mode == "host"
    config.set("pushdown", "off")
    assert decide_pushdown(meta).mode == "raw"
    config.set("pushdown", "on")
    dec = decide_pushdown(meta)
    assert dec.mode == "chip" and "forced" in dec.reason


@pytest.mark.parametrize("case", ["known", "unknown", "override"])
def test_h2d_rate_comes_from_the_device_table(monkeypatch, case):
    """With no live sample, the h2d rate is this device kind's figure; an
    unknown kind is an error unless pushdown_h2d_gbps is set.  The SSD
    rate never comes from the table."""
    from nvme_strom_tpu import StromError
    from nvme_strom_tpu import device_figures as df
    from nvme_strom_tpu.hbm.staging import h2d_meter
    from nvme_strom_tpu.scan import planner
    monkeypatch.setattr(h2d_meter, "observed_gbps", lambda: None)
    monkeypatch.setattr(planner, "_live_ssd_gbps", lambda: None)
    if case != "known":
        monkeypatch.setattr(df, "FIGURES", {})
    if case == "override":
        config.set("pushdown_h2d_gbps", 2.5)
    if case == "unknown":
        with pytest.raises(StromError, match="no planning figures"):
            planner.transport_rates()
        return
    want = 2.5 if case == "override" else df.device_figures("cpu").h2d_gbps
    assert planner.transport_rates() == (want, None)


def test_device_table_names_its_sources():
    from nvme_strom_tpu.device_figures import FIGURES
    assert "PR 21" in FIGURES["TPU v5 lite"].source
    assert "test setting" in FIGURES["cpu"].source


@pytest.mark.parametrize("speedup,kernel", [(0.851, "xla"), (92.0, "pallas")])
def test_float_groupby_routes_on_the_device_speedup(monkeypatch, speedup,
                                                   kernel):
    from nvme_strom_tpu import device_figures as df
    from nvme_strom_tpu.ops.groupby import groupby_kernel_auto
    monkeypatch.setitem(df.FIGURES, "cpu", df.DeviceFigures(
        h2d_gbps=1.0, groupby_f32_pallas_speedup=speedup, source="t"))
    assert groupby_kernel_auto("f")[0] == kernel
    assert groupby_kernel_auto("i")[0] == "pallas"


def test_planner_raw_when_codec_never_pays(tmp_path):
    """All-distinct data: whole-scan ratio below threshold -> raw, and
    the predicted wire bytes are the logical bytes."""
    rng = np.random.default_rng(23)
    c0 = rng.integers(-(2**31), 2**31, 8192, dtype=np.int64) \
        .astype(np.int32)
    path, _s, meta = _build(tmp_path, [c0], ["i4"])
    config.set("pushdown_h2d_gbps", 1.0)
    config.set("pushdown_ssd_gbps", 4.0)
    dec = decide_pushdown(meta)
    assert dec.mode == "raw"
    assert dec.wire_bytes == 4 * meta.n_rows * len(meta.cols)


def test_explain_reports_wire_bytes(tmp_path):
    (path, schema, meta), _cols = _mixed_table(tmp_path)
    config.set("pushdown_h2d_gbps", 1.0)
    config.set("pushdown_ssd_gbps", 4.0)
    plan = Query(path, schema).where(lambda c: c[0] > 7) \
        .aggregate([1, 2]).explain()
    assert plan.pushdown == "chip"
    assert f"predicted wire bytes: {meta.packed_bytes}" in plan.reason
    assert f"({meta.logical_bytes} logical" in plan.reason
    # per-column placement is part of the EXPLAIN contract
    assert "col0=chip" in plan.reason


def test_explain_no_sidecar_no_pushdown(tmp_path):
    c0 = np.arange(4096, dtype=np.int32) % 4
    schema = HeapSchema(1, dtypes=("i4",))
    path = str(tmp_path / "plain.tbl")
    build_heap_file(path, [c0], schema)
    plan = Query(path, schema).aggregate([0]).explain()
    assert plan.pushdown == ""
    assert "pushdown" not in plan.reason


# ---------------------------------------------------------------------------
# packed extents in the residency tier
# ---------------------------------------------------------------------------

def _counters():
    return stats.snapshot(reset_max=False).counters


def test_packed_cache_hit_after_eviction_churn(tmp_path):
    """Packed extents are cached under a representation-tagged key:
    after churn evicts them, a rescan refills and the following pass
    hits, with capacity accounted in logical bytes served."""
    from nvme_strom_tpu.cache import residency_cache

    # big enough that the packed file spans several 64KB scan chunks
    (path, schema, meta), cols = _mixed_table(tmp_path, n=200_000)
    mask = cols[0] > 7
    want = (int(mask.sum()), int(cols[1][mask].sum()),
            int(cols[2][mask].sum()))
    q = Query(path, schema).where(lambda c: c[0] > 7).aggregate([1, 2])
    config.set("pushdown", "on")
    config.set("chunk_size", 64 << 10)
    config.set("cache_arbitration", False)

    # churn phase: capacity far below the packed file
    config.set("cache_bytes", 2 * (64 << 10))
    residency_cache.configure()
    residency_cache.clear()
    b = _counters()
    for _ in range(2):
        out = q.run()
        assert (int(out["count"]), int(out["sums"][0]),
                int(out["sums"][1])) == want
    a = _counters()
    assert a.get("nr_cache_evict", 0) > b.get("nr_cache_evict", 0)

    # recovery phase: capacity now fits the packed file; first pass
    # refills, second is served from resident packed slabs
    config.set("cache_bytes", 2 * meta.packed_bytes + (1 << 20))
    residency_cache.configure()
    out = q.run()
    b = _counters()
    out = q.run()
    a = _counters()
    assert (int(out["count"]), int(out["sums"][0]),
            int(out["sums"][1])) == want
    assert a.get("nr_cache_hit", 0) > b.get("nr_cache_hit", 0)
    res = residency_cache.resident_bytes()
    lres = residency_cache.logical_resident_bytes()
    assert lres > res > 0, (lres, res)


def test_packed_and_heap_cache_keys_disjoint(tmp_path):
    """The representation tag keeps packed and heap extents from ever
    aliasing in the tier, even for the same table."""
    from nvme_strom_tpu.cache import residency_cache
    from nvme_strom_tpu.engine import open_source

    (path, _schema, meta), _cols = _mixed_table(tmp_path)
    with open_source(path) as heap_src:
        hk = residency_cache.source_key(heap_src)
    with open_source(packed_path_for(path)) as pk_src:
        pk_src.cache_key_extra = ("#repr=cpk",
                                  f"#gen={meta.table_mtime_ns}")
        pk = residency_cache.source_key(pk_src)
    assert hk != pk
    assert "#repr=cpk" in pk


def test_pushdown_counters_move(tmp_path):
    (path, schema, _meta), cols = _mixed_table(tmp_path)
    config.set("pushdown", "on")
    b = _counters()
    out = Query(path, schema).where(lambda c: c[0] > 7) \
        .aggregate([1, 2]).run()
    a = _counters()
    mask = cols[0] > 7
    assert int(out["count"]) == int(mask.sum())
    assert (a.get("nr_pushdown_decode_chip", 0)
            + a.get("nr_pushdown_decode_host", 0)) > \
        (b.get("nr_pushdown_decode_chip", 0)
         + b.get("nr_pushdown_decode_host", 0))
    assert a.get("bytes_wire_saved", 0) > b.get("bytes_wire_saved", 0)


def test_packed_scan_fails_over_to_mirror(tmp_path):
    """The packed sidecar striped over a mirrored pair loses member 0
    mid-scan: its extents come from the pair partner, the member latches
    FAILED, and the aggregate matches the unpacked truth."""
    import shutil

    from nvme_strom_tpu.cache import residency_cache
    from nvme_strom_tpu.engine import Session
    from nvme_strom_tpu.ops.decode_xla import make_decode_filter_fn_xla
    from nvme_strom_tpu.scan.executor import TableScanner
    from nvme_strom_tpu.testing import FakeStripedNvmeSource, FaultPlan

    chunk = 64 << 10
    (path, schema, meta), cols = _mixed_table(tmp_path, n=200_000)
    with open(packed_path_for(path), "rb") as f:
        blob = f.read()
    blob += b"\0" * ((-len(blob)) % chunk)    # zero pages scan as no rows
    m0, m1 = str(tmp_path / "pk0.bin"), str(tmp_path / "pk1.bin")
    with open(m0, "wb") as f:
        f.write(blob)
    shutil.copyfile(m0, m1)
    config.set("cache_bytes", 0)
    config.set("cache_arbitration", False)
    config.set("dma_max_size", chunk)
    config.set("io_retries", 1)
    config.set("canary_interval_s", 0.0)
    residency_cache.configure()

    def pred(c):
        return c[0] > 7

    src = FakeStripedNvmeSource(
        [m0, m1], stripe_chunk_size=chunk,
        fault_plan=FaultPlan(failstop_member=0, failstop_after=4),
        force_cached_fraction=0.0, mirror="paired")
    b = _counters()
    try:
        with Session() as sess:
            out = TableScanner(src, schema, session=sess,
                               chunk_size=chunk).scan_filter(
                make_decode_filter_fn_xla(meta, pred))
    finally:
        src.close()
    a = _counters()
    count, sums = _oracle(cols, pred)
    assert int(out["count"]) == count
    assert [int(x) for x in out["sums"]] == sums
    assert a.get("nr_mirror_read", 0) > b.get("nr_mirror_read", 0)
    assert a.get("nr_member_failed", 0) > b.get("nr_member_failed", 0)
