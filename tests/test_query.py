"""Declarative query layer: planning transparency + end-to-end results
(the pgsql CustomScan / EXPLAIN analog, pgsql/nvme_strom.c:1642-1667)."""

import numpy as np
import pytest

from nvme_strom_tpu import config
from nvme_strom_tpu.api import StromError
from nvme_strom_tpu.scan.heap import HeapSchema, build_heap_file
from nvme_strom_tpu.scan.query import Query


@pytest.fixture()
def heap(tmp_path):
    rng = np.random.default_rng(5)
    schema = HeapSchema(n_cols=2, visibility=True)
    n = schema.tuples_per_page * 24
    c0 = rng.integers(-1000, 1000, n).astype(np.int32)
    c1 = rng.integers(0, 16, n).astype(np.int32)
    vis = (rng.random(n) > 0.2).astype(np.int32)
    path = str(tmp_path / "t.heap")
    build_heap_file(path, [c0, c1], schema, visibility=vis)
    return path, schema, c0, c1, vis


def test_explain_shows_the_plan(heap):
    path, schema, *_ = heap
    config.set("debug_no_threshold", True)
    plan = Query(path, schema).where(lambda cols: cols[0] > 0).explain()
    assert plan.operator == "aggregate"
    assert plan.access_path == "direct"
    assert plan.kernel in ("pallas", "xla")
    assert plan.mode == "local"
    assert plan.n_pages == 24
    assert plan.cost_direct < plan.cost_vfs  # the reduced seq_page_cost
    assert "direct-scan threshold" in plan.reason or "eligible" in plan.reason
    s = str(plan)
    assert "aggregate scan" in s and "direct path" in s


def test_small_table_plans_vfs(heap):
    path, schema, *_ = heap
    config.set("debug_no_threshold", False)
    plan = Query(path, schema).explain()
    assert plan.access_path == "vfs"  # 192KB table is far below threshold


def test_aggregate_both_paths_match_oracle(heap):
    path, schema, c0, c1, vis = heap
    sel = (vis != 0) & (c0 > 100)
    for debug_thresh in (True, False):   # direct vs vfs access path
        config.set("debug_no_threshold", debug_thresh)
        q = Query(path, schema).where(lambda cols: cols[0] > 100)
        assert q.explain().access_path == ("direct" if debug_thresh else "vfs")
        out = q.run()
        assert int(out["count"]) == int(sel.sum())
        assert int(out["sums"][0]) == int(c0[sel].sum())
        assert int(out["sums"][1]) == int(c1[sel].sum())


def test_aggregate_kernel_override_pallas(heap):
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    sel = (vis != 0) & (c0 > 0)
    out = Query(path, schema).where(lambda cols: cols[0] > 0) \
        .run(kernel="pallas")   # interpret-mode pallas on CPU
    assert int(out["count"]) == int(sel.sum())


def test_aggregate_projection(heap):
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    sel = (vis != 0) & (c0 > 0)
    out = Query(path, schema).where(lambda cols: cols[0] > 0) \
        .aggregate(cols=[1]).run()
    assert len(out["sums"]) == 1
    assert int(out["sums"][0]) == int(c1[sel].sum())


def test_group_by_matches_oracle(heap):
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    G = 16
    q = (Query(path, schema)
         .where(lambda cols: cols[0] > 0)
         .group_by(lambda cols: cols[1], G, agg_cols=[0]))
    plan = q.explain()
    assert plan.operator == "group_by"
    out = q.run()
    sel = (vis != 0) & (c0 > 0)
    for g in range(G):
        m = sel & (c1 == g)
        assert out["count"][g] == int(m.sum())
        assert out["sums"][0][g] == int(c0[m].sum())


def test_group_by_large_g_plans_xla(heap):
    path, schema, *_ = heap
    plan = Query(path, schema).group_by(lambda cols: cols[1], 512).explain()
    assert plan.kernel == "xla"
    assert "unroll bound" in plan.reason


def test_top_k_matches_oracle(heap):
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    k = 8
    out = Query(path, schema).where(lambda cols: cols[0] > 0) \
        .top_k(0, k).run()
    sel = (vis != 0) & (c0 > 0)
    want = np.sort(c0[sel])[::-1][:k]
    np.testing.assert_array_equal(np.sort(out["values"])[::-1], want)


def test_join_matches_oracle(heap):
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    keys = np.arange(0, 8, dtype=np.int32)          # join on c1 in [0, 8)
    vals = (keys * 10).astype(np.int32)
    out = Query(path, schema).join(1, keys, vals).run()
    sel = (vis != 0) & (c1 < 8)
    assert int(out["matched"]) == int(sel.sum())


def test_one_terminal_operator_only(heap):
    path, schema, *_ = heap
    q = Query(path, schema).group_by(lambda cols: cols[1], 8)
    with pytest.raises(StromError):
        q.top_k(0, 4)
    q2 = Query(path, schema).aggregate(cols=[0])
    with pytest.raises(StromError):
        q2.group_by(lambda cols: cols[1], 8)


def test_mesh_mode_matches_local(heap):
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    mesh = make_scan_mesh(jax.devices())
    q = Query(path, schema).where(lambda cols: cols[0] > 0)
    plan = q.explain(mesh=mesh)
    assert plan.mode == "mesh" and plan.kernel == "xla"
    out_mesh = q.run(mesh=mesh, batch_pages=8)
    out_local = q.run()
    assert int(out_mesh["count"]) == int(out_local["count"])
    assert int(out_mesh["sums"][0]) == int(out_local["sums"][0])


def test_one_terminal_even_default_aggregate(heap):
    path, schema, *_ = heap
    q = Query(path, schema).aggregate()   # default projection
    with pytest.raises(StromError):
        q.group_by(lambda cols: cols[1], 8)


def test_mesh_group_by_multibatch_mins_correct(heap):
    """Mesh mode must use the operator's combiner: per-group mins across
    batches are the MIN of batch mins, not their sum (review finding)."""
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    mesh = make_scan_mesh(jax.devices())
    G = 8
    q = Query(path, schema).group_by(lambda cols: cols[1] % G, G,
                                     agg_cols=[0])
    out = q.run(mesh=mesh, batch_pages=8)   # 24 pages -> 3 batches
    sel = vis != 0
    for g in range(G):
        m = sel & (c1 % G == g)
        assert out["count"][g] == int(m.sum())
        assert out["sums"][0][g] == int(c0[m].sum())
        if m.any():
            assert out["mins"][0][g] == int(c0[m].min())
            assert out["maxs"][0][g] == int(c0[m].max())


def test_mesh_small_table_and_tail_covered(heap):
    """Default mesh batch sizing must not return {} on a small table, and
    a non-divisible page count must still cover every page."""
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    mesh = make_scan_mesh(jax.devices())
    q = Query(path, schema)
    # default batch_pages (128*shards) far exceeds the 24-page table
    out = q.run(mesh=mesh)
    assert int(out["count"]) == int((vis != 0).sum())
    # batch_pages=16 leaves an 8-page tail that must still be scanned
    out2 = Query(path, schema).run(mesh=mesh, batch_pages=16)
    assert int(out2["count"]) == int((vis != 0).sum())


def test_vfs_scan_multifile_stripe(tmp_path):
    """The buffered fallback reads through the Source abstraction, so a
    2-file stripe set scans completely (review finding)."""
    rng = np.random.default_rng(31)
    schema = HeapSchema(n_cols=1, visibility=False)
    n = schema.tuples_per_page * 16
    c0 = rng.integers(-100, 100, n).astype(np.int32)
    whole = str(tmp_path / "w.heap")
    build_heap_file(whole, [c0], schema)
    raw = open(whole, "rb").read()
    half = len(raw) // 2
    pa, pb = str(tmp_path / "a.heap"), str(tmp_path / "b.heap")
    open(pa, "wb").write(raw[:half])
    open(pb, "wb").write(raw[half:])

    config.set("debug_no_threshold", False)   # force the vfs path
    from nvme_strom_tpu.engine import open_source
    src = open_source([pa, pb], segment_size=half)
    try:
        q = Query(src, schema).where(lambda cols: cols[0] > 0)
        assert q.explain().access_path == "vfs"
        out = q.run()
    finally:
        src.close()
    assert int(out["count"]) == int((c0 > 0).sum())
    assert int(out["sums"][0]) == int(c0[c0 > 0].sum())


def test_query_multifile_and_pathlike(tmp_path):
    """Stripe-set lists and PathLike sources work on every execution path
    (review finding: they planned fine but crashed run())."""
    import pathlib

    rng = np.random.default_rng(41)
    schema = HeapSchema(n_cols=1, visibility=False)
    n = schema.tuples_per_page * 16
    c0 = rng.integers(-100, 100, n).astype(np.int32)
    whole = tmp_path / "w.heap"
    build_heap_file(str(whole), [c0], schema)
    raw = whole.read_bytes()
    half = len(raw) // 2
    pa, pb = tmp_path / "a.heap", tmp_path / "b.heap"
    pa.write_bytes(raw[:half])
    pb.write_bytes(raw[half:])
    want_count, want_sum = int((c0 > 0).sum()), int(c0[c0 > 0].sum())

    for debug in (True, False):   # direct and vfs paths
        config.set("debug_no_threshold", debug)
        out = Query([pa, pb], schema, stripe_chunk_size=half) \
            .where(lambda cols: cols[0] > 0).run()
        assert int(out["count"]) == want_count
        assert int(out["sums"][0]) == want_sum

    config.set("debug_no_threshold", True)
    out = Query(pathlib.Path(str(whole)), schema) \
        .where(lambda cols: cols[0] > 0).run()
    assert int(out["count"]) == want_count


def test_mesh_odd_batch_pages_rounded(heap):
    """A user batch_pages not divisible by the dp axis is rounded down,
    not rejected (review finding)."""
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    mesh = make_scan_mesh(jax.devices())
    out = Query(path, schema).run(mesh=mesh, batch_pages=7)
    assert int(out["count"]) == int((vis != 0).sum())


def test_no_predicate_counts_nan_rows(tmp_path):
    """With no WHERE, every valid row counts — including float NaN rows
    (a cols[0]==cols[0] default mask would drop them)."""
    from nvme_strom_tpu.scan.heap import build_pages  # noqa: F401
    schema = HeapSchema(n_cols=1, visibility=False, dtypes=("float32",))
    n = schema.tuples_per_page * 2
    vals = np.linspace(0, 1, n).astype(np.float32)
    vals[::7] = np.nan
    path = str(tmp_path / "nan.heap")
    build_heap_file(path, [vals], schema)
    config.set("debug_no_threshold", True)
    out = Query(path, schema).run(kernel="xla")
    assert int(out["count"]) == n
    out_p = Query(path, schema).run(kernel="pallas")
    assert int(out_p["count"]) == n


def test_explain_shows_invalid_plan_without_raising(heap, tmp_path):
    """EXPLAIN on a non-executable query reports the problem as a plan,
    and run() refuses with the same reason (review finding)."""
    rng = np.random.default_rng(7)
    schema = HeapSchema(n_cols=2, visibility=False,
                        dtypes=("int32", "float32"))
    n = schema.tuples_per_page * 2
    path = str(tmp_path / "mix.heap")
    build_heap_file(path, [rng.integers(0, 9, n).astype(np.int32),
                           rng.random(n).astype(np.float32)], schema)
    q = Query(path, schema).group_by(lambda cols: cols[0], 4)  # mixed aggs
    plan = q.explain()
    assert plan.kernel == "invalid"
    assert "share one dtype" in plan.reason
    with pytest.raises(StromError, match="not executable"):
        q.run()


def test_mesh_explain_also_reports_invalid(tmp_path):
    """The 'invalid' plan contract holds under a mesh too (review
    finding: mode early-return used to bypass validation)."""
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    rng = np.random.default_rng(7)
    schema = HeapSchema(n_cols=2, visibility=False,
                        dtypes=("int32", "float32"))
    n = schema.tuples_per_page * 2
    path = str(tmp_path / "mix.heap")
    build_heap_file(path, [rng.integers(0, 9, n).astype(np.int32),
                           rng.random(n).astype(np.float32)], schema)
    mesh = make_scan_mesh(jax.devices())
    q = Query(path, schema).group_by(lambda cols: cols[0], 4)
    plan = q.explain(mesh=mesh)
    assert plan.kernel == "invalid"
    with pytest.raises(StromError, match="not executable"):
        q.run(mesh=mesh)


def test_order_by_local_and_mesh_match_numpy(heap):
    """ORDER BY: full ordering with row positions, local lax sort and the
    distributed sample sort both match numpy."""
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    sel = (vis != 0) & (c0 > 0)
    q = Query(path, schema).where(lambda cols: cols[0] > 0).order_by(0)
    plan = q.explain()
    assert plan.operator == "order_by"
    out = q.run()
    want = np.sort(c0[sel])
    np.testing.assert_array_equal(out["values"], want)
    # positions name rows carrying those values, all selected
    assert sel[out["positions"]].all()
    np.testing.assert_array_equal(c0[out["positions"]], out["values"])

    mesh = make_scan_mesh(jax.devices())
    mout = Query(path, schema).where(lambda cols: cols[0] > 0) \
        .order_by(0).run(mesh=mesh)
    np.testing.assert_array_equal(mout["values"], want)
    np.testing.assert_array_equal(c0[mout["positions"]], mout["values"])
    assert int(mout["n_dropped"]) == 0


def test_order_by_descending_and_vfs_path(heap):
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", False)   # vfs access path
    q = Query(path, schema).order_by(0, descending=True)
    assert q.explain().access_path == "vfs"
    out = q.run()
    np.testing.assert_array_equal(out["values"], np.sort(c0[vis != 0])[::-1])


def test_order_by_float_column(tmp_path):
    rng = np.random.default_rng(43)
    schema = HeapSchema(n_cols=1, visibility=False, dtypes=("float32",))
    n = schema.tuples_per_page * 4
    f = rng.standard_normal(n).astype(np.float32)
    path = str(tmp_path / "f.heap")
    build_heap_file(path, [f], schema)
    config.set("debug_no_threshold", True)
    out = Query(path, schema).order_by(0).run()
    np.testing.assert_array_equal(out["values"], np.sort(f))


def test_order_by_nothing_selected_and_empty(heap, tmp_path):
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    out = Query(path, schema).where(lambda cols: cols[0] > 10**6) \
        .order_by(0).run()
    assert len(out["values"]) == 0 and len(out["positions"]) == 0


def test_order_by_sp_mesh_keeps_all_buckets(heap):
    """An (sp=2, dp) caller mesh must not truncate the sorted output to
    the caller's dp bucket count (review finding)."""
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    mesh = make_scan_mesh(jax.devices(), sp=2)
    out = Query(path, schema).order_by(0).run(mesh=mesh)
    want = np.sort(c0[vis != 0])
    np.testing.assert_array_equal(out["values"], want)


def test_order_by_mesh_empty_keeps_info_keys(heap):
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    mesh = make_scan_mesh(jax.devices())
    out = Query(path, schema).where(lambda cols: cols[0] > 10**6) \
        .order_by(0).run(mesh=mesh)
    assert len(out["values"]) == 0
    assert int(out["n_dropped"]) == 0
    assert (np.asarray(out["per_device_count"]) == 0).all()


def test_run_analyze_reports_io_breakdown(heap):
    """EXPLAIN ANALYZE face: analyze=True attaches elapsed time + the
    engine's stage counters for THIS run (STAT_INFO delta)."""
    import os

    path, schema, c0, c1, vis = heap
    # fsync + fadvise so the direct path engages (a freshly written file
    # is 100% cached/dirty and would ride the write-back path)
    fd = os.open(path, os.O_RDONLY)
    os.fsync(fd)
    os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    os.close(fd)
    config.set("debug_no_threshold", True)
    # the 24-page table must span several chunks or it is all buffered
    # tail (the default 16MB chunk swallows it whole)
    config.set("chunk_size", "64k")   # order matters: buffer is a
    config.set("buffer_size", "1m")   # multiple-of-chunk invariant
    out = Query(path, schema).where(lambda cols: cols[0] > 0) \
        .run(analyze=True)
    a = out["_analyze"]
    assert a["elapsed_s"] > 0
    assert a["requests"] >= 1
    assert a["bytes_direct"] >= 24 * 8192 * 0.5   # most pages direct
    assert 0 < a["avg_dma_bytes"] <= config.get("dma_max_size")
    # the query result itself is unchanged
    sel = (vis != 0) & (c0 > 0)
    assert int(out["count"]) == int(sel.sum())


def test_count_distinct_local_and_mesh(heap):
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    sel = (vis != 0) & (c0 > 0)
    q = Query(path, schema).where(lambda cols: cols[0] > 0) \
        .count_distinct(1)
    assert q.explain().operator == "count_distinct"
    out = q.run()
    want = len(np.unique(c1[sel]))
    assert int(out["distinct"]) == want
    mesh = make_scan_mesh(jax.devices())
    mout = Query(path, schema).where(lambda cols: cols[0] > 0) \
        .count_distinct(1).run(mesh=mesh)
    assert int(mout["distinct"]) == want
    # empty selection
    e = Query(path, schema).where(lambda cols: cols[0] > 10**6) \
        .count_distinct(0).run(mesh=mesh)
    assert int(e["distinct"]) == 0


def test_select_matches_oracle_both_paths(heap):
    """SELECT: materialized rows (values + positions) are exactly the
    selected rows, on both access paths (the tuples-to-executor face,
    pgsql/nvme_strom.c:941-979)."""
    path, schema, c0, c1, vis = heap
    sel = (vis != 0) & (c0 > 100)
    want_pos = np.flatnonzero(sel)
    for debug_thresh in (True, False):
        config.set("debug_no_threshold", debug_thresh)
        q = Query(path, schema).where(lambda cols: cols[0] > 100).select()
        plan = q.explain()
        assert plan.operator == "select"
        assert "materialization" in plan.reason
        out = q.run()
        assert int(out["count"]) == int(sel.sum())
        # arrival order is physical, not sorted: compare by row identity
        order = np.argsort(out["positions"])
        np.testing.assert_array_equal(out["positions"][order], want_pos)
        np.testing.assert_array_equal(out["col0"][order], c0[sel])
        np.testing.assert_array_equal(out["col1"][order], c1[sel])


def test_select_projection_typed_columns(tmp_path):
    """Projection keeps only the named columns, with their schema dtypes."""
    rng = np.random.default_rng(11)
    schema = HeapSchema(n_cols=2, visibility=False,
                        dtypes=("int32", "float32"))
    n = schema.tuples_per_page * 4
    c0 = rng.integers(-50, 50, n).astype(np.int32)
    c1 = rng.standard_normal(n).astype(np.float32)
    path = str(tmp_path / "typed.heap")
    build_heap_file(path, [c0, c1], schema)
    config.set("debug_no_threshold", True)
    out = Query(path, schema).where(lambda cols: cols[0] >= 0) \
        .select([1]).run()
    assert set(out) == {"col1", "positions", "count"}
    assert out["col1"].dtype == np.float32
    sel = c0 >= 0
    order = np.argsort(out["positions"])
    np.testing.assert_array_equal(out["col1"][order], c1[sel])


def test_select_limit_offset(heap):
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", False)   # vfs: deterministic order
    q_all = Query(path, schema).where(lambda cols: cols[0] > 0).select()
    full = q_all.run()
    out = Query(path, schema).where(lambda cols: cols[0] > 0) \
        .select(limit=7, offset=5).run()
    assert int(out["count"]) == 7
    np.testing.assert_array_equal(out["positions"],
                                  full["positions"][5:12])
    np.testing.assert_array_equal(out["col0"], full["col0"][5:12])
    # limit past the end clamps
    n_sel = int(full["count"])
    out = Query(path, schema).where(lambda cols: cols[0] > 0) \
        .select(limit=n_sel + 100, offset=n_sel - 2).run()
    assert int(out["count"]) == 2


def test_select_limit_stops_io_early(tmp_path):
    """LIMIT early-exit: the direct scan stops issuing DMA once enough
    rows are gathered (bytes_direct well below the full table)."""
    import os

    schema = HeapSchema(n_cols=1, visibility=False)
    n_pages = 64                       # 8 chunks of 8 pages at 64k
    n = schema.tuples_per_page * n_pages
    c0 = np.arange(n, dtype=np.int32)
    path = str(tmp_path / "big.heap")
    build_heap_file(path, [c0], schema)
    fd = os.open(path, os.O_RDONLY)
    os.fsync(fd)
    os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    os.close(fd)
    config.set("debug_no_threshold", True)
    config.set("chunk_size", "64k")
    config.set("buffer_size", "1m")
    config.set("async_depth", 2)       # ring much smaller than the table
    out = Query(path, schema).select(limit=4).run(analyze=True)
    assert int(out["count"]) == 4
    # the first 8-page chunk already holds thousands of rows; only the
    # ring (2 in flight + resubmits) is ever read, not all 8 chunks
    assert out["_analyze"]["bytes_direct"] <= 4 * 65536


def test_select_empty_and_mesh(heap):
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    out = Query(path, schema).where(lambda cols: cols[0] > 10**6) \
        .select().run()
    assert int(out["count"]) == 0
    assert len(out["positions"]) == 0 and len(out["col0"]) == 0
    # mesh mode gathers locally but must return identical rows
    mesh = make_scan_mesh(jax.devices())
    sel = (vis != 0) & (c0 > 100)
    mout = Query(path, schema).where(lambda cols: cols[0] > 100) \
        .select([0]).run(mesh=mesh)
    order = np.argsort(mout["positions"])
    np.testing.assert_array_equal(mout["col0"][order], c0[sel])


def test_select_rejects_bad_args(heap):
    path, schema, *_ = heap
    # EXPLAIN surfaces the bad projection without raising; run() refuses
    plan = Query(path, schema).select([9]).explain()
    assert plan.kernel == "invalid" and "out of range" in plan.reason
    with pytest.raises(StromError):
        Query(path, schema).select([9]).run()
    with pytest.raises(StromError):
        Query(path, schema).select(limit=-1)
    with pytest.raises(StromError):
        Query(path, schema).select(offset=-1)
    with pytest.raises(StromError):   # still one terminal per query
        Query(path, schema).select().order_by(0)


def test_order_by_limit_offset_local_and_mesh(heap):
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    want = np.sort(c0[vis != 0])
    out = Query(path, schema).order_by(0, limit=10, offset=3).run()
    np.testing.assert_array_equal(out["values"], want[3:13])
    np.testing.assert_array_equal(c0[out["positions"]], out["values"])
    # descending slice
    out = Query(path, schema).order_by(0, descending=True, limit=5).run()
    np.testing.assert_array_equal(out["values"], want[::-1][:5])
    # mesh path slices the concatenated bucket order the same way
    mesh = make_scan_mesh(jax.devices())
    mout = Query(path, schema).order_by(0, limit=10, offset=3) \
        .run(mesh=mesh)
    np.testing.assert_array_equal(mout["values"], want[3:13])


def test_group_by_avgs_present_and_correct(heap):
    """group_by results always carry derived avgs = sums/count, NaN for
    empty groups, on both kernel paths."""
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    sel = (vis != 0) & (c0 > 0)
    for kernel in ("xla", "pallas"):
        out = Query(path, schema).where(lambda cols: cols[0] > 0) \
            .group_by(lambda cols: cols[1] % 8, 8, agg_cols=[0]) \
            .run(kernel=kernel)
        for g in range(8):
            m = sel & (c1 % 8 == g)
            if m.sum():
                np.testing.assert_allclose(out["avgs"][0][g],
                                           c0[m].mean(), rtol=1e-6)
            else:
                assert np.isnan(out["avgs"][0][g])


def test_group_by_having_filters_groups(heap):
    """HAVING applies after the fold: surviving groups are compressed,
    original ids in "groups"."""
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    sel = (vis != 0) & (c0 > 0)
    counts = np.array([(sel & (c1 % 8 == g)).sum() for g in range(8)])
    cut = int(np.median(counts))
    out = Query(path, schema).where(lambda cols: cols[0] > 0) \
        .group_by(lambda cols: cols[1] % 8, 8, agg_cols=[0],
                  having=lambda gr: gr["count"] > cut).run()
    want = np.flatnonzero(counts > cut)
    np.testing.assert_array_equal(out["groups"], want)
    np.testing.assert_array_equal(out["count"], counts[want])
    assert out["sums"].shape == (1, len(want))
    assert out["avgs"].shape == (1, len(want))


def test_group_by_having_mesh_matches_local(heap):
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    q = lambda: Query(path, schema).where(lambda cols: cols[0] > 0) \
        .group_by(lambda cols: cols[1] % 8, 8, agg_cols=[0, 1],
                  having=lambda gr: gr["avgs"][0] > 0)
    local = q().run()
    mesh = make_scan_mesh(jax.devices())
    dist = q().run(mesh=mesh)
    np.testing.assert_array_equal(local["groups"], dist["groups"])
    np.testing.assert_array_equal(local["count"], dist["count"])
    np.testing.assert_allclose(local["avgs"], dist["avgs"], rtol=1e-6)


def test_group_by_having_bad_mask_shape(heap):
    path, schema, *_ = heap
    config.set("debug_no_threshold", True)
    with pytest.raises(StromError, match="bool mask"):
        Query(path, schema) \
            .group_by(lambda cols: cols[1] % 8, 8, agg_cols=[0],
                      having=lambda gr: gr["count"][:3] > 0).run()


def test_select_limit_drains_ring_before_owner_recovery(tmp_path, monkeypatch):
    """LIMIT early-exit ordering: the DMA ring is drained (waited +
    released) INSIDE the ResourceOwner scope, so abort-recovery never
    returns a chunk the SSD may still be writing into (review finding).
    CPython's refcounting happened to close the generator first even
    before the explicit gen.close(); this pins the invariant so it
    survives any future code holding a generator reference (or a
    non-refcounting runtime).  Observable: zero chunks still
    owner-attached when __exit__ runs."""
    import os

    from nvme_strom_tpu.scan import pool as pool_mod

    schema = HeapSchema(n_cols=1, visibility=False)
    n = schema.tuples_per_page * 64
    path = str(tmp_path / "d.heap")
    build_heap_file(path, [np.arange(n, dtype=np.int32)], schema)
    fd = os.open(path, os.O_RDONLY)
    os.fsync(fd)
    os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    os.close(fd)
    config.set("debug_no_threshold", True)
    config.set("chunk_size", "64k")
    config.set("buffer_size", "1m")
    config.set("async_depth", 2)

    attached_at_exit = []
    orig_exit = pool_mod.ResourceOwner.__exit__

    def spy_exit(self, exc_type, exc, tb):
        attached_at_exit.append(len(self._chunks))
        return orig_exit(self, exc_type, exc, tb)

    monkeypatch.setattr(pool_mod.ResourceOwner, "__exit__", spy_exit)
    out = Query(path, schema).select(limit=4).run()
    assert int(out["count"]) == 4
    assert attached_at_exit and all(n == 0 for n in attached_at_exit)


def test_group_by_variance_and_stddev(heap):
    """vars/stds derive from the sumsqs accumulator and match numpy's
    population variance, on both kernel paths (float accumulation:
    rtol, not equality)."""
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    sel = (vis != 0) & (c0 > 0)
    for kernel in ("xla", "pallas"):
        out = Query(path, schema).where(lambda cols: cols[0] > 0) \
            .group_by(lambda cols: cols[1] % 8, 8, agg_cols=[0]) \
            .run(kernel=kernel)
        for g in range(8):
            m = sel & (c1 % 8 == g)
            if m.sum():
                np.testing.assert_allclose(out["vars"][0][g],
                                           c0[m].var(), rtol=1e-4)
                np.testing.assert_allclose(out["stds"][0][g],
                                           c0[m].std(), rtol=1e-4)
            else:
                assert np.isnan(out["vars"][0][g])


def test_group_by_having_on_stddev(heap):
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    sel = vis != 0
    stds = np.array([c0[sel & (c1 % 4 == g)].std() for g in range(4)])
    cut = float(np.median(stds))
    out = Query(path, schema) \
        .group_by(lambda cols: cols[1] % 4, 4, agg_cols=[0],
                  having=lambda gr: gr["stds"][0] > cut).run()
    np.testing.assert_array_equal(out["groups"], np.flatnonzero(stds > cut))


def test_order_by_multi_column_matches_lexsort(heap):
    """ORDER BY c1, c0: later columns break ties (numpy lexsort oracle);
    descending reverses the whole ordering."""
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    sel = vis != 0
    out = Query(path, schema).order_by([1, 0]).run()
    order = np.lexsort((c0[sel], c1[sel]))
    np.testing.assert_array_equal(out["values"], c1[sel][order])
    np.testing.assert_array_equal(c1[out["positions"]], out["values"])
    # full row order is pinned, not just the key column: tie-broken c0
    np.testing.assert_array_equal(c0[out["positions"]], c0[sel][order])
    # descending
    outd = Query(path, schema).order_by([1, 0], descending=True).run()
    np.testing.assert_array_equal(c1[outd["positions"]], c1[sel][order][::-1])
    np.testing.assert_array_equal(c0[outd["positions"]], c0[sel][order][::-1])


def test_order_by_multi_column_mesh_refused(heap):
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, *_ = heap
    config.set("debug_no_threshold", True)
    mesh = make_scan_mesh(jax.devices())
    with pytest.raises(StromError, match="one key column"):
        Query(path, schema).order_by([0, 1]).run(mesh=mesh)
    # single-column mesh sort still fine
    out = Query(path, schema).order_by([0]).run(mesh=mesh)
    assert len(out["values"]) > 0


def test_join_materialize_rows(heap):
    """materialize=True returns the joined rows (positions/keys/payload),
    matching the numpy oracle; limit early-exits like select."""
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    keys = np.arange(0, 8, dtype=np.int32)
    vals = (keys * 10).astype(np.int32)
    out = Query(path, schema).where(lambda cols: cols[0] > 0) \
        .join(1, keys, vals, materialize=True).run()
    sel = (vis != 0) & (c0 > 0) & (c1 < 8)
    order = np.argsort(out["positions"])
    np.testing.assert_array_equal(out["positions"][order],
                                  np.flatnonzero(sel))
    np.testing.assert_array_equal(out["keys"][order], c1[sel])
    np.testing.assert_array_equal(out["payload"][order], c1[sel] * 10)
    assert int(out["count"]) == int(sel.sum())
    # limit/offset slice (vfs path: deterministic arrival order)
    config.set("debug_no_threshold", False)
    full = Query(path, schema).join(1, keys, vals, materialize=True).run()
    part = Query(path, schema).join(1, keys, vals, materialize=True,
                                    limit=5, offset=3).run()
    np.testing.assert_array_equal(part["positions"],
                                  full["positions"][3:8])
    np.testing.assert_array_equal(part["payload"], full["payload"][3:8])
    # nothing joins -> empty arrays with count 0
    none = Query(path, schema).join(1, keys + 100, vals,
                                    materialize=True).run()
    assert int(none["count"]) == 0 and len(none["positions"]) == 0


def test_join_empty_build_table_joins_nothing(heap):
    """An empty dimension table joins zero rows on both join faces
    (review finding: was a zero-size gather crash)."""
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    ek = np.zeros(0, np.int32)
    agg = Query(path, schema).join(1, ek, ek).run()
    assert int(agg["matched"]) == 0 and int(agg["payload_sum"]) == 0
    rows = Query(path, schema).join(1, ek, ek, materialize=True).run()
    assert int(rows["count"]) == 0 and len(rows["payload"]) == 0


def test_join_aggregate_mesh_matches_local(heap):
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    keys = np.arange(0, 8, dtype=np.int32)
    vals = (keys * 10).astype(np.int32)
    local = Query(path, schema).join(1, keys, vals).run()
    mesh = make_scan_mesh(jax.devices())
    dist = Query(path, schema).join(1, keys, vals).run(mesh=mesh,
                                                       batch_pages=8)
    assert int(dist["matched"]) == int(local["matched"])
    assert int(dist["payload_sum"]) == int(local["payload_sum"])
    np.testing.assert_array_equal(dist["sums"], local["sums"])


def test_join_limit_requires_materialize(heap):
    path, schema, *_ = heap
    with pytest.raises(StromError, match="materialize"):
        Query(path, schema).join(1, np.arange(4, dtype=np.int32),
                                 np.arange(4, dtype=np.int32), limit=5)


def test_quantiles_local_and_mesh_match_numpy(heap):
    """Exact nearest-rank quantiles: local sort and the distributed
    sample sort agree with the numpy oracle (and each other)."""
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    sel = (vis != 0) & (c0 > 0)
    qs = [0.0, 0.25, 0.5, 0.9, 1.0]
    svals = np.sort(c0[sel])
    n = len(svals)
    want = svals[[min(n - 1, max(0, int(np.ceil(q * n)) - 1)) for q in qs]]
    q = Query(path, schema).where(lambda cols: cols[0] > 0) \
        .quantiles(0, qs)
    assert q.explain().operator == "quantiles"
    out = q.run()
    assert int(out["n"]) == n
    np.testing.assert_array_equal(out["quantiles"], want)
    mesh = make_scan_mesh(jax.devices())
    mout = Query(path, schema).where(lambda cols: cols[0] > 0) \
        .quantiles(0, qs).run(mesh=mesh)
    np.testing.assert_array_equal(mout["quantiles"], want)
    # empty selection -> NaN quantiles, n == 0
    e = Query(path, schema).where(lambda cols: cols[0] > 10**6) \
        .quantiles(0, [0.5]).run()
    assert int(e["n"]) == 0 and np.isnan(e["quantiles"]).all()
    # invalid q refused at build time
    with pytest.raises(StromError):
        Query(path, schema).quantiles(0, [1.5])


def test_fetch_point_lookup_matches_oracle(heap):
    """fetch: rows come back in caller order (duplicates and unsorted
    positions included), validity reflects visibility, and only the
    touched pages are read."""
    import os

    from nvme_strom_tpu import Session
    path, schema, c0, c1, vis = heap
    fd = os.open(path, os.O_RDONLY)
    os.fsync(fd)
    os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    os.close(fd)
    t = schema.tuples_per_page
    rng = np.random.default_rng(17)
    pos = rng.integers(0, len(c0), 50)
    pos = np.concatenate([pos, pos[:5]])   # duplicates, unsorted
    with Session() as sess:
        before = sess.stat_info().counters["total_dma_length"]
        out = Query(path, schema).fetch(pos, session=sess)
        after = sess.stat_info().counters["total_dma_length"]
    np.testing.assert_array_equal(out["col0"], c0[pos])
    np.testing.assert_array_equal(out["col1"], c1[pos])
    np.testing.assert_array_equal(out["valid"], vis[pos] != 0)
    # only the unique pages containing the rows were read directly
    n_touched = len(np.unique(pos // t))
    assert after - before <= n_touched * 8192


def test_fetch_projection_bounds_and_empty(heap):
    path, schema, c0, c1, vis = heap
    out = Query(path, schema).fetch([3, 1], cols=[1])
    assert set(out) == {"col1", "valid"}
    np.testing.assert_array_equal(out["col1"], c1[[3, 1]])
    e = Query(path, schema).fetch([])
    assert len(e["valid"]) == 0
    with pytest.raises(StromError, match="outside"):
        Query(path, schema).fetch([10**9])
    with pytest.raises(StromError, match="out of range"):
        Query(path, schema).fetch([0], cols=[9])


def test_aggregate_bad_columns_invalid_plan_both_paths(heap):
    """aggregate(cols=...) validation happens at plan time, so the
    refusal is identical whether or not an index exists (review
    finding: the seqscan silently returned the LAST column for -1)."""
    path, schema, *_ = heap
    for bad in ([-1], [9]):
        plan = Query(path, schema).aggregate(cols=bad).explain()
        assert plan.kernel == "invalid" and "out of range" in plan.reason
        with pytest.raises(StromError, match="out of range"):
            Query(path, schema).aggregate(cols=bad).run()


def test_topk_bad_column_invalid_plan(heap):
    path, schema, *_ = heap
    plan = Query(path, schema).top_k(9, 4).explain()
    assert plan.kernel == "invalid" and "out of range" in plan.reason
    with pytest.raises(StromError, match="out of range"):
        Query(path, schema).top_k(9, 4).run()


def test_sort_family_bad_columns_invalid_plan(heap):
    """order_by/quantiles/count_distinct column problems surface in
    EXPLAIN as invalid plans, not only at run time (review finding)."""
    path, schema, *_ = heap
    for q in (Query(path, schema).order_by(9),
              Query(path, schema).quantiles(9, [0.5]),
              Query(path, schema).count_distinct(9)):
        plan = q.explain()
        assert plan.kernel == "invalid" and "out of range" in plan.reason
        with pytest.raises(StromError):
            q.run()


def test_query_results_identical_across_io_backends(heap):
    """The io backend (io_uring / threadpool / pure python) is invisible
    to query results — same rows, same aggregates (the engine-level
    differential test lifted to the query surface)."""
    import os

    from nvme_strom_tpu import Session
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    config.set("chunk_size", "64k")
    config.set("buffer_size", "1m")
    outs = {}
    for backend in ("io_uring", "threadpool", "python"):
        fd = os.open(path, os.O_RDONLY)
        os.fsync(fd)
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        os.close(fd)
        try:
            sess = Session(io_backend=backend)
        except StromError:
            continue   # backend unavailable on this host
        # a query failure must FAIL the test, not drop the backend
        with sess:
            outs[backend] = Query(path, schema) \
                .where(lambda c: c[0] > 0).select([0]) \
                .run(session=sess)
    assert "python" in outs
    if len(outs) < 2:
        pytest.skip("no native backend on this host")
    base = outs["python"]
    for name, out in outs.items():
        np.testing.assert_array_equal(
            np.sort(out["positions"]), np.sort(base["positions"]), name)
        np.testing.assert_array_equal(
            np.sort(out["col0"]), np.sort(base["col0"]), name)


def test_partitioned_join_parity_local_and_mesh(heap):
    """Build sides above join_broadcast_max switch to the partitioned
    hash join (VERDICT r2 #8): EXPLAIN shows the strategy, local Grace
    passes and the mesh all_to_all exchange both reproduce the broadcast
    answer exactly, on the aggregate AND materializing faces."""
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    rng = np.random.default_rng(9)
    keys = rng.permutation(np.arange(-1200, 1200, dtype=np.int32))[:900]
    vals = (keys * 3).astype(np.int32)

    def q(**kw):
        return Query(path, schema).join(0, keys, vals, **kw)

    # broadcast reference (default cap far above this build side)
    assert q().explain().join_strategy == "broadcast"
    base = q().run()
    base_m = q(materialize=True).run()

    old = config.get("join_broadcast_max")
    config.set("join_broadcast_max", 1024)   # force partitioning
    try:
        plan = q().explain()
        assert plan.join_strategy.startswith("partitioned(")
        assert "Grace" in plan.reason or "partition" in plan.reason
        part = q().run()
        assert int(part["matched"]) == int(base["matched"])
        np.testing.assert_array_equal(part["sums"], base["sums"])
        assert int(part["payload_sum"]) == int(base["payload_sum"])

        # materializing face: same row set (order is per-partition)
        part_m = q(materialize=True).run()
        assert int(part_m["count"]) == int(base_m["count"])
        np.testing.assert_array_equal(np.sort(part_m["positions"]),
                                      np.sort(base_m["positions"]))
        np.testing.assert_array_equal(np.sort(part_m["payload"]),
                                      np.sort(base_m["payload"]))
        # limit slices the concatenated partition stream
        lm = q(materialize=True, limit=7).run()
        assert int(lm["count"]) == 7
        assert np.isin(lm["positions"], base_m["positions"]).all()

        # mesh: single scan, build sharded 1/dp, all_to_all row routing
        mesh = make_scan_mesh(jax.devices())
        mplan = q().explain(mesh=mesh)
        assert mplan.join_strategy.startswith("partitioned(")
        assert "all_to_all" in mplan.reason
        mesh_out = q().run(mesh=mesh, batch_pages=8)
        assert int(mesh_out["matched"]) == int(base["matched"])
        np.testing.assert_array_equal(mesh_out["sums"], base["sums"])
        assert int(mesh_out["payload_sum"]) == int(base["payload_sum"])

        # mesh row face (VERDICT r3 #3): all_to_all-routed rows come back
        # as the same row SET as broadcast (order is arrival order)
        mesh_m = q(materialize=True).run(mesh=mesh, batch_pages=8)
        assert int(mesh_m["count"]) == int(base_m["count"])
        np.testing.assert_array_equal(np.sort(mesh_m["positions"]),
                                      np.sort(base_m["positions"]))
        np.testing.assert_array_equal(np.sort(mesh_m["keys"]),
                                      np.sort(base_m["keys"]))
        np.testing.assert_array_equal(np.sort(mesh_m["payload"]),
                                      np.sort(base_m["payload"]))
        # (position, key, payload) triples must agree row-for-row, not
        # just column-sets: join each back through base's position order
        bo = np.argsort(base_m["positions"])
        mo = np.argsort(mesh_m["positions"])
        np.testing.assert_array_equal(np.asarray(mesh_m["keys"])[mo],
                                      np.asarray(base_m["keys"])[bo])
        np.testing.assert_array_equal(np.asarray(mesh_m["payload"])[mo],
                                      np.asarray(base_m["payload"])[bo])
        # LIMIT/OFFSET early-exit on the mesh stream
        mlm = q(materialize=True, limit=7, offset=2).run(mesh=mesh,
                                                         batch_pages=8)
        assert int(mlm["count"]) == 7
        assert np.isin(mlm["positions"], base_m["positions"]).all()
    finally:
        config.set("join_broadcast_max", old)


def test_partitioned_join_surfaces_injected_faults(tmp_path):
    """A mid-pass read fault in the local partitioned join surfaces as
    StromError (first-error latch), the session stays usable, and the
    mesh exchange path surfaces the same fault class."""
    import jax
    import pytest as _pytest

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    from nvme_strom_tpu.scan.heap import PAGE_SIZE as _PS
    from nvme_strom_tpu.testing import FakeNvmeSource, FaultPlan

    schema = HeapSchema(n_cols=2, visibility=True)
    rng = np.random.default_rng(3)
    n = schema.tuples_per_page * 32
    c0 = rng.integers(-100, 100, n).astype(np.int32)
    c1 = rng.integers(0, 50, n).astype(np.int32)
    path = str(tmp_path / "pj.heap")
    build_heap_file(path, [c0, c1], schema)
    config.set("debug_no_threshold", True)
    keys = np.arange(-100, 100, dtype=np.int32)
    vals = keys * 2

    old = config.get("join_broadcast_max")
    old_chunk = config.get("chunk_size")
    config.set("join_broadcast_max", 1024)
    # small chunks: the table must be larger than one chunk or every
    # byte rides the buffered tail path and the DIRECT fault never fires
    config.set("chunk_size", 64 << 10)
    try:
        src = FakeNvmeSource(path, force_cached_fraction=0.0,
                             fault_plan=FaultPlan(
                                 fail_offsets={4 * _PS}))
        try:
            with _pytest.raises(StromError):
                Query(src, schema).join(0, keys, vals).run()
        finally:
            src.close()
        # healthy source afterwards: same process keeps working
        out = Query(path, schema).join(0, keys, vals).run()
        oracle = np.isin(c0, keys)
        # visibility defaults to all-ones in build_heap_file
        assert int(out["matched"]) == int(oracle.sum())

        src2 = FakeNvmeSource(path, force_cached_fraction=0.0,
                              fault_plan=FaultPlan(fail_offsets={4 * _PS}))
        try:
            mesh = make_scan_mesh(jax.devices())
            with _pytest.raises(StromError):
                Query(src2, schema).join(0, keys, vals).run(
                    mesh=mesh, batch_pages=8)
        finally:
            src2.close()
    finally:
        config.set("join_broadcast_max", old)
        config.set("chunk_size", old_chunk)


def test_uint32_ordered_terminals(tmp_path):
    """uint32 columns now support every ordered terminal — order_by
    (local + mesh + sidecar), top_k, quantiles, count_distinct — with
    values above 2^31 exercising the unsigned ordering."""
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    from nvme_strom_tpu.scan.index import build_index
    schema = HeapSchema(n_cols=1, visibility=False, dtypes=("uint32",))
    rng = np.random.default_rng(21)
    n = schema.tuples_per_page * 8
    u = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    path = str(tmp_path / "u.heap")
    build_heap_file(path, [u], schema)
    config.set("debug_no_threshold", True)

    srt = np.sort(u)
    ob = Query(path, schema).order_by(0, limit=9).run()
    np.testing.assert_array_equal(ob["values"], srt[:9])
    assert ob["values"].dtype == np.uint32
    mesh = make_scan_mesh(jax.devices())
    obm = Query(path, schema).order_by(0, limit=9).run(mesh=mesh)
    np.testing.assert_array_equal(obm["values"], srt[:9])
    tk = Query(path, schema).top_k(0, 5).run()
    np.testing.assert_array_equal(tk["values"], srt[-5:][::-1])
    qt = Query(path, schema).quantiles(0, [0.5]).run()
    cd = Query(path, schema).count_distinct(0).run()
    assert int(cd["distinct"]) == len(np.unique(u))
    cdm = Query(path, schema).count_distinct(0).run(mesh=mesh)
    assert int(cdm["distinct"]) == len(np.unique(u))

    # and the sidecar serves them at zero table I/O
    build_index(path, schema, 0)
    q = Query(path, schema).order_by(0, limit=9)
    assert q.explain().access_path == "index"
    np.testing.assert_array_equal(q.run()["values"], srt[:9])
    q2 = Query(path, schema).quantiles(0, [0.5])
    assert q2.explain().access_path == "index"
    np.testing.assert_array_equal(q2.run()["quantiles"],
                                  qt["quantiles"])


def test_partitioned_build_streams_from_disk_bounded(tmp_path):
    """VERDICT r3 #8: a join build side streamed from an on-disk table
    larger than the host budget partitions in Grace passes — python-host
    peak (tracemalloc; on the CPU test backend the PLACED device arrays
    alias host numpy, so they appear in both paths and the measured
    difference is exactly the dp x cap host materialization the streamed
    path eliminates) stays a fraction of the in-memory partitioner's and
    within one-partition transients over the placed bytes.  The placed
    partitions are BIT-identical, and the join step consumes them
    unchanged."""
    import gc
    import tracemalloc

    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    from nvme_strom_tpu.parallel.pjoin import (
        make_partitioned_join_step, partition_build_sharded,
        partition_build_sharded_from_table)

    config.set("debug_no_threshold", True)
    # 1 MiB scan chunks: the CPU backend copies each chunk it lands, and
    # the JAX runtime frees those copies on its own clock — a 16 MiB
    # chunk made both peaks swing by most of a chunk, run to run
    config.set("chunk_size", 1 << 20)
    bschema = HeapSchema(n_cols=2, visibility=False)
    t = bschema.tuples_per_page
    n_pages = 2048                     # 16MB build table
    n = t * n_pages
    rng = np.random.default_rng(23)
    keys = rng.permutation(n).astype(np.int32)      # unique
    vals = (keys * 3).astype(np.int32)
    bpath = str(tmp_path / "build.heap")
    build_heap_file(bpath, [keys, vals], bschema)
    table_bytes = n_pages * 8192
    mesh = make_scan_mesh(jax.devices())

    # warm both code paths on a tiny table first: the FIRST XLA compile
    # of the scan kernels allocates ~20MB python-side, which would
    # otherwise swamp the data signal tracemalloc is here to measure
    wpath = str(tmp_path / "warm.heap")
    build_heap_file(wpath, [np.arange(t * 8, dtype=np.int32),
                            np.arange(t * 8, dtype=np.int32)], bschema)
    for budget in (1 << 12, 1 << 30):   # streamed AND fast path
        partition_build_sharded_from_table(wpath, bschema, 0, 1, mesh,
                                           budget=budget)
    gc.collect()

    # in-memory path peak: full-table projection + dp x cap host tables
    tracemalloc.start()
    out = Query(bpath, bschema).select([0, 1]).run()
    ref = partition_build_sharded(out["col0"], out["col1"], mesh,
                                  bschema, 0)
    inmem_peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    placed = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in ref)
    ref_np = [np.asarray(a) for a in ref]
    del out, ref
    gc.collect()

    tracemalloc.start()
    parts = partition_build_sharded_from_table(
        bpath, bschema, 0, 1, mesh, budget=1 << 20)
    streamed_peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    # measured on this harness: 20-31MB vs 81MB on a 16MB table
    assert streamed_peak < inmem_peak * 0.55, (streamed_peak, inmem_peak)
    assert streamed_peak < placed + 1.25 * table_bytes, \
        (streamed_peak, placed)

    for got, want in zip(parts, ref_np):
        np.testing.assert_array_equal(np.asarray(got), want)

    # under-budget tables take the single-scan fast path, same result
    fast = partition_build_sharded_from_table(
        bpath, bschema, 0, 1, mesh, budget=table_bytes + 1)
    for got, want in zip(fast, ref_np):
        np.testing.assert_array_equal(np.asarray(got), want)

    # the step consumes prebuilt parts: every fact row probes its own
    # key, so matched == fact row count
    fpath = str(tmp_path / "fact.heap")
    fn = t * 16
    fkeys = rng.integers(0, n, fn).astype(np.int32)
    build_heap_file(fpath, [fkeys, np.ones(fn, np.int32)], bschema)
    step = make_partitioned_join_step(mesh, bschema, 0,
                                      build_parts=parts)
    from nvme_strom_tpu.scan.heap import PAGE_SIZE
    raw = open(fpath, "rb").read()
    pages = np.frombuffer(raw, np.uint8).reshape(-1, PAGE_SIZE)
    out = step(pages)
    assert int(np.asarray(out["matched"])) == fn


def test_join_table_disk_build_all_faces(tmp_path):
    """Query.join_table: the build side lives on disk.  Broadcast-sized
    tables load with one scan and match Query.join exactly; above
    join_broadcast_max the partitioned strategy streams the build (local
    Grace passes AND the mesh) and still reproduces the in-memory
    answers on both faces; EXPLAIN names the streamed build."""
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh

    config.set("debug_no_threshold", True)
    rng = np.random.default_rng(41)
    schema = HeapSchema(n_cols=2, visibility=True)
    t = schema.tuples_per_page
    n = t * 24
    c0 = rng.integers(-1000, 1000, n).astype(np.int32)
    c1 = rng.integers(0, 16, n).astype(np.int32)
    vis = (rng.random(n) > 0.2).astype(np.int32)
    fpath = str(tmp_path / "fact.heap")
    build_heap_file(fpath, [c0, c1], schema, visibility=vis)

    bschema = HeapSchema(n_cols=2, visibility=False)
    keys = rng.permutation(np.arange(-1200, 1200, dtype=np.int32))[:900]
    vals = (keys * 3).astype(np.int32)
    bpath = str(tmp_path / "dim.heap")
    pad = (-len(keys)) % bschema.tuples_per_page
    # pad the build table with keys outside the fact domain (heap files
    # are whole pages); uniqueness must hold across pads too
    pk = np.concatenate([keys, np.arange(5000, 5000 + pad, dtype=np.int32)])
    pv = np.concatenate([vals, np.zeros(pad, np.int32)])
    build_heap_file(bpath, [pk, pv], bschema)

    def jt(**kw):
        return Query(fpath, schema).join_table(0, bpath, bschema, 0, 1,
                                               **kw)

    base = Query(fpath, schema).join(0, pk, pv).run()
    base_m = Query(fpath, schema).join(0, pk, pv, materialize=True).run()

    # broadcast-sized: identical to the in-memory join
    assert jt().explain().join_strategy == "broadcast"
    out = jt().run()
    assert int(out["matched"]) == int(base["matched"])
    np.testing.assert_array_equal(out["sums"], base["sums"])
    out_m = jt(materialize=True).run()
    np.testing.assert_array_equal(np.sort(out_m["positions"]),
                                  np.sort(base_m["positions"]))

    old = config.get("join_broadcast_max")
    config.set("join_broadcast_max", 1024)
    try:
        plan = jt().explain()
        assert plan.join_strategy.startswith("partitioned(")
        assert "STREAMED" in plan.reason
        part = jt().run()
        assert int(part["matched"]) == int(base["matched"])
        np.testing.assert_array_equal(part["sums"], base["sums"])
        assert int(part["payload_sum"]) == int(base["payload_sum"])
        part_m = jt(materialize=True).run()
        np.testing.assert_array_equal(np.sort(part_m["positions"]),
                                      np.sort(base_m["positions"]))
        np.testing.assert_array_equal(np.sort(part_m["payload"]),
                                      np.sort(base_m["payload"]))
        lm = jt(materialize=True, limit=7).run()
        assert int(lm["count"]) == 7
        assert np.isin(lm["positions"], base_m["positions"]).all()

        # mesh: streamed build parts, both faces
        mesh = make_scan_mesh(jax.devices())
        mesh_out = jt().run(mesh=mesh, batch_pages=8)
        assert int(mesh_out["matched"]) == int(base["matched"])
        np.testing.assert_array_equal(mesh_out["sums"], base["sums"])
        mesh_m = jt(materialize=True).run(mesh=mesh, batch_pages=8)
        np.testing.assert_array_equal(np.sort(mesh_m["positions"]),
                                      np.sort(base_m["positions"]))
    finally:
        config.set("join_broadcast_max", old)

    # bad columns / dtypes refuse clearly — and BEFORE the terminal
    # slot is claimed, so the query stays reusable after a reject
    q2 = Query(fpath, schema)
    with pytest.raises(StromError):
        q2.join_table(0, bpath, bschema, 0, 9)
    q2.join(0, pk, pv)
    fschema = HeapSchema(n_cols=2, visibility=False,
                         dtypes=("float32", "int32"))
    with pytest.raises(StromError):
        Query(fpath, schema).join_table(0, bpath, fschema, 0, 1)

    # an indexed eq-filter plus a PARTITIONED-sized on-disk build must
    # keep the bounded contract: the dispatch routes to the streamed
    # scan path (never a whole-table host resolve) and still answers
    # exactly like the in-memory join
    from nvme_strom_tpu.scan.index import build_index
    build_index(fpath, schema, 0)
    probe_key = int(pk[3])
    ref = Query(fpath, schema).where_eq(0, probe_key).join(0, pk, pv).run()
    config.set("join_broadcast_max", 1024)
    try:
        qi = Query(fpath, schema).where_eq(0, probe_key) \
            .join_table(0, bpath, bschema, 0, 1)
        got = qi.run()
        assert int(got["matched"]) == int(ref["matched"])
        assert int(got["payload_sum"]) == int(ref["payload_sum"])
    finally:
        config.set("join_broadcast_max", old)


# ---------------------------------------------------------------------------
# group_by_cols (value-keyed GROUP BY)
# ---------------------------------------------------------------------------

def test_group_by_cols_single_matches_oracle(heap):
    """GROUP BY col over VALUES: keys discovered, aggregates per key,
    key_cols carries the actual key values (ascending discovery order)."""
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    out = Query(path, schema).group_by_cols(1, agg_cols=[0]).run()
    sel = vis != 0
    want_keys = np.unique(c1[sel])
    np.testing.assert_array_equal(out["key_cols"][0], want_keys)
    for i, k in enumerate(want_keys):
        m = sel & (c1 == k)
        assert int(out["count"][i]) == int(m.sum())
        assert int(out["sums"][0][i]) == int(c0[m].sum())


def test_group_by_cols_predicate_and_having(heap):
    """WHERE narrows the groups (keys absent under the predicate do not
    appear) and HAVING composes on top of the empty-group drop."""
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    out = Query(path, schema).where(lambda cols: cols[0] > 800) \
        .group_by_cols(1, agg_cols=[0],
                       having=lambda g: g["count"] >= 3).run()
    sel = (vis != 0) & (c0 > 800)
    want = [k for k in np.unique(c1[sel])
            if int((sel & (c1 == k)).sum()) >= 3]
    np.testing.assert_array_equal(out["key_cols"][0], np.array(want))
    for i, k in enumerate(want):
        m = sel & (c1 == k)
        assert int(out["count"][i]) == int(m.sum())


def test_group_by_cols_pair(tmp_path):
    """Two-column GROUP BY: the dense rank table maps value pairs to
    groups; key_cols returns both columns' values per group."""
    rng = np.random.default_rng(5)
    schema = HeapSchema(n_cols=3, visibility=False)
    n = schema.tuples_per_page * 6
    c0 = rng.integers(0, 5, n).astype(np.int32)
    c1 = rng.integers(-3, 3, n).astype(np.int32)
    c2 = rng.integers(0, 100, n).astype(np.int32)
    path = str(tmp_path / "p.heap")
    build_heap_file(path, [c0, c1, c2], schema)
    config.set("debug_no_threshold", True)
    out = Query(path, schema).group_by_cols([0, 1], agg_cols=[2]).run()
    pairs = sorted({(int(a), int(b)) for a, b in zip(c0, c1)})
    got = list(zip(out["key_cols"][0].tolist(),
                   out["key_cols"][1].tolist()))
    assert got == pairs
    for i, (a, b) in enumerate(pairs):
        m = (c0 == a) & (c1 == b)
        assert int(out["count"][i]) == int(m.sum())
        assert int(out["sums"][0][i]) == int(c2[m].sum())


def test_group_by_cols_sidecar_discovery(tmp_path):
    """A fresh sidecar supplies the distinct keys at zero table I/O;
    results equal the scan-discovered ones (superset keys from the
    sidecar are dropped by the empty-group HAVING when a predicate
    excludes them)."""
    from nvme_strom_tpu.scan.index import build_index
    rng = np.random.default_rng(9)
    schema = HeapSchema(n_cols=2, visibility=False)
    n = schema.tuples_per_page * 4
    c0 = rng.integers(0, 12, n).astype(np.int32)
    c1 = rng.integers(0, 50, n).astype(np.int32)
    path = str(tmp_path / "s.heap")
    build_heap_file(path, [c0, c1], schema)
    config.set("debug_no_threshold", True)
    base = Query(path, schema).where(lambda cols: cols[1] > 25) \
        .group_by_cols(0, agg_cols=[1]).run()
    build_index(path, schema, 0)
    idx = Query(path, schema).where(lambda cols: cols[1] > 25) \
        .group_by_cols(0, agg_cols=[1]).run()
    np.testing.assert_array_equal(idx["key_cols"][0], base["key_cols"][0])
    np.testing.assert_array_equal(idx["count"], base["count"])
    np.testing.assert_array_equal(idx["sums"], base["sums"])


def test_group_by_cols_mesh_matches_local(heap):
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, vis = heap
    config.set("debug_no_threshold", True)
    local = Query(path, schema).group_by_cols(1, agg_cols=[0]).run()
    mesh = make_scan_mesh(jax.devices())
    dist = Query(path, schema).group_by_cols(1, agg_cols=[0]) \
        .run(mesh=mesh, batch_pages=8)
    np.testing.assert_array_equal(dist["key_cols"][0],
                                  local["key_cols"][0])
    np.testing.assert_array_equal(dist["count"], local["count"])
    np.testing.assert_array_equal(dist["sums"], local["sums"])


def test_group_by_cols_validation(heap):
    path, schema, c0, c1, vis = heap
    with pytest.raises(StromError):
        Query(path, schema).group_by_cols([0, 1, 0, 1, 0])   # 5 cols
    with pytest.raises(StromError):
        Query(path, schema).group_by_cols(7)           # out of range
    with pytest.raises(StromError):
        Query(path, schema).group_by_cols(1, max_groups=0)
    # discovery past max_groups now SPILLS to sorted aggregation (round
    # 5) instead of failing with ENOMEM — same result, never truncation
    config.set("debug_no_threshold", True)
    spilled = Query(path, schema).group_by_cols(0, max_groups=4).run()
    normal = Query(path, schema).group_by_cols(0).run()
    np.testing.assert_array_equal(spilled["key_cols"][0],
                                  normal["key_cols"][0])
    np.testing.assert_array_equal(spilled["count"], normal["count"])
    np.testing.assert_array_equal(spilled["sums"], normal["sums"])


def test_group_by_cols_pair_sidecar_discovery(tmp_path):
    """A fresh composite (c0, c1) sidecar supplies the distinct PAIRS at
    zero table I/O; results equal the scan-discovered ones."""
    from nvme_strom_tpu.scan.index import build_index
    rng = np.random.default_rng(19)
    schema = HeapSchema(n_cols=3, visibility=False)
    n = schema.tuples_per_page * 4
    c0 = rng.integers(0, 6, n).astype(np.int32)
    c1 = rng.integers(-4, 4, n).astype(np.int32)
    c2 = rng.integers(0, 100, n).astype(np.int32)
    path = str(tmp_path / "pc.heap")
    build_heap_file(path, [c0, c1, c2], schema)
    config.set("debug_no_threshold", True)
    base = Query(path, schema).group_by_cols([0, 1], agg_cols=[2]).run()
    build_index(path, schema, (0, 1))
    idx = Query(path, schema).group_by_cols([0, 1], agg_cols=[2]).run()
    for k in ("count",):
        np.testing.assert_array_equal(idx[k], base[k])
    np.testing.assert_array_equal(idx["sums"], base["sums"])
    for i in (0, 1):
        np.testing.assert_array_equal(idx["key_cols"][i],
                                      base["key_cols"][i])


def test_group_by_cols_three_columns(tmp_path):
    """3-column value-keyed GROUP BY (mixed-radix rank table): keys and
    aggregates match the numpy oracle, local and mesh."""
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    rng = np.random.default_rng(41)
    schema = HeapSchema(n_cols=4, visibility=False,
                        dtypes=("int32", "uint32", "int32", "int32"))
    n = schema.tuples_per_page * 6
    c0 = rng.integers(-3, 3, n).astype(np.int32)
    c1 = rng.integers(0, 4, n).astype(np.uint32)
    c2 = rng.integers(0, 3, n).astype(np.int32)
    c3 = rng.integers(0, 100, n).astype(np.int32)
    path = str(tmp_path / "t3.heap")
    build_heap_file(path, [c0, c1, c2, c3], schema)
    config.set("debug_no_threshold", True)
    out = Query(path, schema).group_by_cols([0, 1, 2],
                                            agg_cols=[3]).run()
    rows = sorted({(int(a), int(b), int(d))
                   for a, b, d in zip(c0, c1, c2)})
    got = list(zip(out["key_cols"][0].tolist(),
                   out["key_cols"][1].tolist(),
                   out["key_cols"][2].tolist()))
    assert got == rows
    for i, (a, b, d) in enumerate(rows):
        m = (c0 == a) & (c1 == b) & (c2 == d)
        assert int(out["count"][i]) == int(m.sum())
        assert int(out["sums"][0][i]) == int(c3[m].sum())
    assert out["key_cols"][1].dtype == np.uint32
    mesh = make_scan_mesh(jax.devices())
    dist = Query(path, schema).group_by_cols([0, 1, 2], agg_cols=[3]) \
        .run(mesh=mesh, batch_pages=12)
    np.testing.assert_array_equal(dist["count"], out["count"])
    np.testing.assert_array_equal(dist["sums"], out["sums"])
    with pytest.raises(StromError):
        Query(path, schema).group_by_cols([0, 1, 2, 3, 0])  # 5 keys


# -- the buffered scan's read-ahead ring ------------------------------------

#: ring depth under ``h2d_depth_max`` 2 (the ring holds depth + 1 batches)
RING = 3
BATCH_PAGES = (8 << 20) // 8192


def _ring_counters():
    from nvme_strom_tpu.stats import stats
    return dict(stats.snapshot().counters)


def _ring_delta(before):
    after = _ring_counters()
    return {k: after[k] - before.get(k, 0) for k in after}


def _readers_alive():
    import threading
    return [t for t in threading.enumerate()
            if t.name == "strom-vfs-readahead"]


@pytest.mark.parametrize("batches", [1, RING - 1, RING + 1, 3 * RING + 1])
def test_vfs_ring_batches_survive_buffer_reuse(tmp_path, batches):
    """Every batch's device array, kept until the scan ends, still holds
    the file's bytes: no slot the reader refilled is aliased by an
    un-copied ``device_put``.  The last batch is partial."""
    from nvme_strom_tpu.testing import make_test_file
    config.set("h2d_depth_max", RING - 1)
    n_pages = (batches - 1) * BATCH_PAGES + 3
    path = str(tmp_path / "ring.bin")
    make_test_file(path, n_pages * 8192)
    kept = []

    def collect(pages_dev):
        kept.append(pages_dev)
        return {}

    before = _ring_counters()
    Query(path, HeapSchema(n_cols=1))._vfs_scan(collect, None, None)
    d = _ring_delta(before)
    want = np.fromfile(path, np.uint8).reshape(n_pages, 8192)
    assert len(kept) == batches
    assert [a.shape[0] for a in kept] == \
        [BATCH_PAGES] * (batches - 1) + [3]
    for i, a in enumerate(kept):
        np.testing.assert_array_equal(
            np.asarray(a), want[i * BATCH_PAGES:(i + 1) * BATCH_PAGES])
    assert d["nr_vfs_read"] == d["nr_vfs_readahead"] == batches
    assert not _readers_alive()


class _FailingSource:
    """An owned source whose ``read_buffered`` raises at one offset, and
    that records at close whether the ring's reader was still alive."""

    def __init__(self, inner, fail_offset, err):
        self._inner = inner
        self.size = inner.size
        self._fail = fail_offset
        self.err = err
        self.alive_at_close = None

    def read_buffered(self, offset, dest):
        if offset == self._fail:
            raise self.err
        self._inner.read_buffered(offset, dest)

    def close(self):
        self.alive_at_close = bool(_readers_alive())
        self._inner.close()


class _KernelFault(Exception):
    pass


@pytest.mark.parametrize("where", ["read", "kernel"])
def test_vfs_ring_error_joins_reader_before_close(tmp_path, monkeypatch,
                                                   where):
    """A read error at batch k is raised in the caller as the same
    StromError; a kernel error stops the scan too.  Either way the
    reader is joined before the owned source closes."""
    import errno

    from nvme_strom_tpu.engine import open_source
    schema = HeapSchema(n_cols=1, visibility=False)
    config.set("h2d_depth_max", RING - 1)
    config.set("debug_no_threshold", False)          # the vfs path
    n = schema.tuples_per_page * (4 * BATCH_PAGES + 5)
    path = str(tmp_path / "f.heap")
    build_heap_file(path, [np.arange(n, dtype=np.int32) % 7], schema)
    k = 2
    err = StromError(errno.EIO, "planted read error")
    src = _FailingSource(open_source(path),
                         k * BATCH_PAGES * 8192 if where == "read" else -1,
                         err)
    monkeypatch.setattr(Query, "_open_owned", lambda self: (src, True))
    calls = []

    def kernel(pages_dev):
        calls.append(pages_dev.shape[0])
        if len(calls) == k + 1:
            raise _KernelFault
        return {}

    q = Query(path, schema).where(lambda cols: cols[0] > 2)
    if where == "read":
        with pytest.raises(StromError) as got:
            q.run()
        assert got.value is err
    else:
        with pytest.raises(_KernelFault):
            q._vfs_scan(kernel, None, None)
        assert len(calls) == k + 1
    assert src.alive_at_close is False
    assert not _readers_alive()


def test_vfs_ring_limit_stops_reads_early(tmp_path):
    """LIMIT over the buffered path: the scan stops at the first batch,
    the reader has read at most the ring's depth past it, and no reader
    is left alive."""
    schema = HeapSchema(n_cols=1, visibility=False)
    config.set("h2d_depth_max", RING - 1)
    config.set("debug_no_threshold", False)
    batches = RING + 3
    n = schema.tuples_per_page * (batches - 1) * BATCH_PAGES + 11
    c0 = np.arange(n, dtype=np.int32)
    path = str(tmp_path / "l.heap")
    build_heap_file(path, [c0], schema)
    before = _ring_counters()
    out = Query(path, schema).select(limit=4).run()
    d = _ring_delta(before)
    assert int(out["count"]) == 4
    np.testing.assert_array_equal(out["col0"], c0[:4])
    assert d["nr_vfs_read"] == 1
    assert 1 <= d["nr_vfs_readahead"] <= 1 + RING < batches
    assert not _readers_alive()


def test_vfs_ring_counters_and_answer(tmp_path):
    """An aggregate over the ring: one caller wait and one reader read a
    batch, blocked waits a subset of the waits, and the answer that of
    numpy."""
    rng = np.random.default_rng(29)
    schema = HeapSchema(n_cols=2, visibility=False)
    config.set("debug_no_threshold", False)
    n = schema.tuples_per_page * (3 * BATCH_PAGES + 17)
    c0 = rng.integers(-50, 50, n).astype(np.int32)
    c1 = rng.integers(0, 1000, n).astype(np.int32)
    path = str(tmp_path / "c.heap")
    build_heap_file(path, [c0, c1], schema)
    before = _ring_counters()
    out = Query(path, schema).where(lambda cols: cols[0] > 10).run()
    d = _ring_delta(before)
    m = c0 > 10
    assert int(out["count"]) == int(m.sum())
    assert int(out["sums"][1]) == int(c1[m].sum())
    assert d["nr_vfs_read"] == d["nr_vfs_readahead"] == 4
    assert 0 <= d["nr_vfs_read_blocked"] <= d["nr_vfs_read"]
    assert d["clk_vfs_readahead"] > 0
    assert not _readers_alive()


class _LateTransfer:
    """A device array whose copy of the slot is taken only when it is
    fenced, as an accelerator's host->device read may still be running
    after ``device_put`` returns."""

    def __init__(self, pages):
        self._pages = pages
        self.copy = None
        self._polls = 0

    def is_ready(self):
        self._polls += 1
        return self.copy is not None

    def block_until_ready(self):
        if self.copy is None:
            self.copy = np.array(self._pages)
        return self


def test_vfs_ring_stress_refills_only_fenced_slots(tmp_path):
    """More rings than cores, one-page batches, a switch interval of a
    microsecond: every batch arrives once, in order, and a slot is
    refilled only after its transfer was fenced, so each late copy still
    holds its own batch."""
    import os
    import sys
    import threading

    from nvme_strom_tpu.engine import open_source
    from nvme_strom_tpu.scan.read_ring import ReadAheadRing
    from nvme_strom_tpu.testing import make_test_file
    n_pages = 64
    path = str(tmp_path / "s.bin")
    make_test_file(path, n_pages * 8192)
    want = np.fromfile(path, np.uint8).reshape(n_pages, 8192)
    errors = []

    def scan(depth):
        try:
            with open_source(path) as src, \
                    ReadAheadRing(src, 1, depth) as ring:
                held = []
                for slot, pages in ring:
                    dev = _LateTransfer(pages)
                    ring.recycle(slot, dev)
                    held.append(dev)
            for i, dev in enumerate(held):
                dev.block_until_ready()
                if not np.array_equal(dev.copy[0], want[i]):
                    errors.append((depth, i))
            if len(held) != n_pages:
                errors.append((depth, "count", len(held)))
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append((depth, repr(e)))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=scan, args=(2 + i % 3,))
                   for i in range((os.cpu_count() or 2) + 2)]
        for t in workers:
            t.start()
        for t in workers:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in workers)
    assert errors == []
    assert not _readers_alive()
