"""TPC-H Q6 through ``sql_query`` on a ``(sp=1, dp=4)`` mesh of four of the
virtual CPU devices, and the benchmark cell that times it
(``tpch_q6.sf10.mesh4``).

The tables have the ``tpch_sf10`` configuration's column layout (int64
decimals, so x64 is on for these tests) and sizes chosen by the mesh's
geometry: a step is one 8 MiB batch (1,024 pages) a shard, the pages a
step cannot fill are the tail, scanned on one device in 8 MiB batches.
"""

import glob
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nvme_strom_tpu import config
from nvme_strom_tpu.parallel.mesh import make_scan_mesh
from nvme_strom_tpu.scan.heap import PAGE_SIZE, HeapSchema, build_heap_file
from nvme_strom_tpu.scan.query import BATCH_PAGES, Query
from nvme_strom_tpu.scan.sql import parse_sql, sql_query
from nvme_strom_tpu.stats import stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(BENCH, "configs", "tpch_sf10.json")) as _f:
    COLUMNS = json.load(_f)["columns"]
SCHEMA = HeapSchema(n_cols=len(COLUMNS), visibility=False,
                    dtypes=tuple(d for _, d in COLUMNS))
COL = {n: i for i, (n, _) in enumerate(COLUMNS)}
DP = 4
STEP = BATCH_PAGES * DP
#: two full steps and a tail of three local batches (1,024, 1,024, 100)
FULL_AND_TAIL = 2 * STEP + 2 * BATCH_PAGES + 100
PAGES = {"steps_and_tail": FULL_AND_TAIL,
         "under_one_step": 2503,
         "step_multiple": 2 * STEP}
DAY0 = 8035                    # 1992-01-01 in days since 1970-01-01
REQS = ((1994, 6, 24), (1996, 3, 25))
STAGES = ("mesh_submit", "mesh_wait", "staging_fence", "staging_retire",
          "vfs_read")


@pytest.fixture(autouse=True)
def _x64():
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)


@pytest.fixture(scope="module")
def rows():
    """LINEITEM-shaped columns for the largest table, seeded."""
    n = FULL_AND_TAIL * SCHEMA.tuples_per_page
    rng = np.random.default_rng(30)
    cols = {name: rng.integers(0, 1 << 20, n).astype(dt)
            for name, dt in COLUMNS}
    qty = rng.integers(1, 51, n, dtype=np.int64)
    cols["l_quantity"] = qty * 100
    cols["l_extendedprice"] = qty * rng.integers(90_000, 210_000, n,
                                                 dtype=np.int64)
    cols["l_discount"] = rng.integers(0, 11, n, dtype=np.int64)
    cols["l_shipdate"] = rng.integers(DAY0, DAY0 + 2525, n,
                                      dtype=np.int64).astype(np.int32)
    return cols


@pytest.fixture(scope="module")
def heaps(rows, tmp_path_factory):
    """{case: (path, rows in it)}: each table is the first pages of one
    draw."""
    d = tmp_path_factory.mktemp("mesh_q6")
    out = {}
    for case, pages in PAGES.items():
        n = pages * SCHEMA.tuples_per_page
        path = str(d / f"{case}.heap")
        assert build_heap_file(path, [rows[name][:n] for name, _ in COLUMNS],
                               SCHEMA) == pages
        out[case] = (path, n)
    return out


@pytest.fixture(scope="module")
def mesh():
    return make_scan_mesh(jax.devices()[:DP], sp=1)


def year_days(y: int) -> int:
    return int((np.datetime64(f"{y}-01-01", "D")
                - np.datetime64("1970-01-01", "D")).astype(int))


def q6_sql(y, d, q) -> str:
    c = {n: f"c{i}" for n, i in COL.items()}
    return (f"SELECT SUM({c['l_extendedprice']} * {c['l_discount']}) "
            f"AS revenue FROM lineitem "
            f"WHERE {c['l_shipdate']} >= {year_days(y)} "
            f"AND {c['l_shipdate']} < {year_days(y + 1)} "
            f"AND {c['l_discount']} BETWEEN {d - 1} AND {d + 1} "
            f"AND {c['l_quantity']} < {q * 100}")


def numpy_q6(rows, n, y, d, q) -> int:
    ship, disc = rows["l_shipdate"][:n], rows["l_discount"][:n]
    sel = ((ship >= year_days(y)) & (ship < year_days(y + 1))
           & (disc >= d - 1) & (disc <= d + 1)
           & (rows["l_quantity"][:n] < q * 100))
    return int(np.dot(rows["l_extendedprice"][:n][sel], disc[sel]))


def geometry(pages, batch_pages=None):
    """(steps, tail batches) of a mesh scan of *pages* pages."""
    bp = min(batch_pages or STEP, pages // DP * DP)
    bp = max(bp // DP * DP, DP)
    steps = pages // bp
    tail = pages - steps * bp
    return steps, -(-tail // BATCH_PAGES)


def delta(before):
    return {k: v - before.get(k, 0)
            for k, v in stats.snapshot().counters.items()}


@pytest.mark.parametrize("case", sorted(PAGES))
def test_sql_q6_on_the_mesh_equals_numpy(case, heaps, rows, mesh):
    """Exact int64 revenue, summed on each shard and across the four; the
    dispatches say which geometry ran: steps, then the tail's batches."""
    path, n = heaps[case]
    q, _ = parse_sql(q6_sql(*REQS[0]), path, SCHEMA)
    plan = q.explain(mesh=mesh)
    assert (plan.mode, plan.kernel) == ("mesh", "xla")
    steps, tail = geometry(PAGES[case])
    for req in REQS:
        before = dict(stats.snapshot().counters)
        out = sql_query(q6_sql(*req), path, SCHEMA, mesh=mesh)
        d = delta(before)
        got = np.asarray(out["revenue"]).reshape(-1)
        assert got.dtype == np.int64
        assert int(got[0]) == numpy_q6(rows, n, *req)
        assert d["nr_mesh_submit"] == steps
        assert d["nr_kernel_dispatch"] == steps + tail


@pytest.mark.parametrize("batch_pages,pooled", [(STEP, True), (256, False)],
                         ids=["8MiB_shards_on_the_pool",
                              "512KiB_shards_inline"])
def test_sql_q6_mesh_shard_copies_take_the_pool_by_size(
        batch_pages, pooled, heaps, rows, mesh):
    """On the page-cache-resident heap every shard of a step is one
    write-back run: an 8 MiB shard is copied on the engine's I/O pool and
    completes at the fan-in's wait, a 512 KiB one stays inline in the
    submit.  The revenue is the same exact int64 either way."""
    path, n = heaps["steps_and_tail"]
    steps, tail = geometry(PAGES["steps_and_tail"], batch_pages)
    sql = q6_sql(*REQS[0])
    before = dict(stats.snapshot().counters)
    out = sql_query(sql, path, SCHEMA, mesh=mesh, batch_pages=batch_pages)
    d = delta(before)
    assert int(np.asarray(out["revenue"]).reshape(-1)[0]) == \
        numpy_q6(rows, n, *REQS[0])
    assert d["nr_mesh_submit"] == steps
    assert d["nr_kernel_dispatch"] == steps + tail
    runs = (d["nr_writeback_copy"], d["nr_pagecache_copy"])
    assert runs == ((steps * DP, 0) if pooled else (0, steps * DP))


def test_geometry_of_the_cases():
    assert geometry(PAGES["steps_and_tail"]) == (2, 3)
    assert geometry(PAGES["under_one_step"]) == (1, 1)     # a 3-page tail
    assert geometry(PAGES["step_multiple"]) == (2, 0)


def _traced_lines(fn, tdir, caller):
    from jax._src.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(caller):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    pb, = glob.glob(os.path.join(str(tdir), "plugins", "profile", "*",
                                 "*.xplane.pb"))
    for plane in ProfileData.from_file(pb).planes:
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events]
            if any(nm == caller for _, _, nm in evs):
                return out, evs
    raise AssertionError("no host line holds the caller's annotation")


def test_mesh_stages_sit_on_the_callers_line_apart(heaps, rows, mesh,
                                                   tmp_path):
    """A step is one ``mesh_submit`` (every shard's engine submit), the
    waits of its fan-in, one ``staging_retire`` (placements, assembly,
    kernel call and fold) and from the third step on a ``staging_fence``;
    each tail batch is one ``staging_retire``.  The caller's stages do
    not overlap and sum to no more than the query."""
    path, n = heaps["steps_and_tail"]
    bp = STEP // 2                                  # 5 steps, 3 fences
    steps, tail = geometry(PAGES["steps_and_tail"], bp)
    assert (steps, tail) == (5, 1)
    sql = q6_sql(*REQS[1])
    sql_query(sql, path, SCHEMA, mesh=mesh, batch_pages=bp)   # compiles
    before = dict(stats.snapshot().counters)
    out, line = _traced_lines(
        lambda: sql_query(sql, path, SCHEMA, mesh=mesh, batch_pages=bp),
        tmp_path / "t", "caller.q6")
    d = delta(before)
    assert int(np.asarray(out["revenue"]).reshape(-1)[0]) == \
        numpy_q6(rows, n, *REQS[1])
    assert d["nr_mesh_submit"] == steps
    assert d["nr_mesh_wait"] >= steps * DP
    assert d["nr_staging_fence"] == steps - 2
    assert d["nr_staging_retire"] == d["nr_kernel_dispatch"] == steps + tail
    (c0, c1), = [(a, b) for a, b, nm in line if nm == "caller.q6"]
    spans = sorted((a, b, nm[len("strom."):]) for a, b, nm in line
                   if nm[len("strom."):] in STAGES)
    per = {s: sum(nm == s for *_, nm in spans) for s in STAGES}
    assert per["mesh_submit"] == steps
    assert per["mesh_wait"] == d["nr_mesh_wait"]
    assert per["staging_fence"] == steps - 2
    # a step's retire is a span per shard placement, the assembly and
    # the kernel call; a tail batch's is one span
    assert per["staging_retire"] == steps * (DP + 2) + tail
    assert per["vfs_read"] == tail
    assert all(c0 <= a <= b <= c1 for a, b, _ in spans)
    assert all(b <= a2 for (_, b, _), (a2, _, _) in zip(spans, spans[1:]))
    assert sum(b - a for a, b, _ in spans) <= c1 - c0
    # the engine's own submit stage nests inside mesh_submit
    subs = [(a, b) for a, b, nm in spans if nm == "mesh_submit"]
    inner = [(a, b) for a, b, nm in line
             if nm == "strom.ioctl_memcpy_submit"]
    assert len(inner) == steps * DP
    assert all(any(a <= p and q <= b for a, b in subs) for p, q in inner)


def test_kept_tail_batches_equal_the_file_after_the_ring_reuses_them(
        heaps, mesh, monkeypatch):
    """The tail runs through the buffered scan's read-ahead ring; with two
    slots its third batch refills the first slot.  A kernel that keeps
    the device arrays it is given must still hold the file's bytes."""
    config.set("h2d_depth_max", 1)
    path, _n = heaps["steps_and_tail"]
    q = Query(path, SCHEMA).aggregate()
    kept = []

    def keep(pages):
        if len(pages.devices()) == 1:
            kept.append(pages)
        return {"count": jnp.zeros((), jnp.int32)}
    monkeypatch.setattr(q, "_build_fn", lambda kernel: (keep, None))
    q.run(mesh=mesh)
    covered = 2 * STEP
    assert [k.shape[0] for k in kept] == [BATCH_PAGES, BATCH_PAGES, 100]
    with open(path, "rb") as f:
        for i, k in enumerate(kept):
            f.seek((covered + i * BATCH_PAGES) * PAGE_SIZE)
            want = np.frombuffer(f.read(k.shape[0] * PAGE_SIZE), np.uint8)
            assert np.array_equal(np.asarray(k).reshape(-1), want), i


# -- the benchmark cell, at SF 0.01, on four of the CPU devices ------------

CELL = "tpch_q6.sf10.mesh4"
SMALL = {"scale_factor": 0.01}


@pytest.fixture(scope="module")
def harness():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import harness as h
    return h


def _run(h, tmp_path, *, trace=False, control=False, seconds=0.3):
    spec = h.load_spec()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    got = h.run_cell(spec, cell, 2 ** 33 + 30, seconds, trace,
                     time.monotonic(), devices=jax.devices(),
                     control=control, cfg_override=SMALL,
                     data_dir=str(tmp_path))
    return spec, cell, got


def test_mesh4_cell_correct_with_every_metric(harness, tmp_path):
    """One traced run: correct, the end-to-end metrics, and the per-layer
    metrics a CPU trace can feed (no TPU plane, so no idle share, kernel
    roofline or collective share); a query dispatches its steps and its
    tail's batches, and the caller's stage shares sum to at most 1."""
    spec, cell, got = _run(harness, tmp_path, trace=True)
    assert harness.is_correct(got), got["checks"]
    assert got["checks"]["wrong_revenue"]["value"] == 0
    f = got["facts"]
    assert (f["mode"], f["kernel"], f["dp"]) == ("mesh", "xla", DP)
    assert set(harness.metrics_for(spec, cell, got, False, None)) == \
        {"setup_s", "query_s"}
    m = harness.metrics_for(spec, cell, got, True, None)
    assert set(m) == {"staging_retire_share.query", "kernel_dispatches.query",
                      "mesh_submit_share.query", "mesh_wait_share.query"}
    steps, tail = geometry(f["pages_per_request"])
    assert m["kernel_dispatches.query"]["value"] == steps + tail
    c, w = got["counters"], got["window_s"]
    shares = sum(c.get("clk_" + s, 0) for s in STAGES) / 1e9 / w
    assert 0 < shares <= 1.0
    assert m["mesh_submit_share.query"]["value"] > 0
    # the cell keeps only its warm copy: the table is tpch_sf10's
    heaps = sorted(os.path.relpath(os.path.join(dp, fn), tmp_path)
                   for dp, _, fs in os.walk(tmp_path) for fn in fs
                   if fn.endswith(".heap"))
    assert heaps == [os.path.join("tpch_q6_sf10_mesh4", "warm.heap"),
                     os.path.join("tpch_sf10", "lineitem.heap"),
                     os.path.join("tpch_sf10", "warm.heap")]


def test_mesh4_control_is_wrong(harness, tmp_path):
    _spec, _cell, got = _run(harness, tmp_path, control=True, seconds=0.2)
    n = len(got["records"])
    assert n > 0 and got["checks"]["wrong_revenue"]["value"] >= 1
    assert not harness.is_correct(got)


def test_mesh4_warm_copy_is_one_step_and_the_tables_tail(harness):
    mod = harness.load_module(
        os.path.join(BENCH, "configs", "tpch_q6_sf10_mesh4.py"),
        "tpch_q6_sf10_mesh4")
    assert mod.warm_pages(1_224_466, STEP) == 7954       # SF 10
    assert mod.warm_pages(1_223, STEP) == 1_223
    with open(os.path.join(BENCH, "configs",
                           "tpch_q6_sf10_mesh4.json")) as f:
        cfg = json.load(f)
    assert cfg["step_pages"] == STEP
    assert (cfg["mesh"], cfg["table"]) == ({"sp": 1, "dp": DP}, "tpch_sf10")


# -- the three readers this cell adds ---------------------------------------

def _view(h, counters, trace=None, window_s=4.0):
    return h.RunView(cell={}, cfg={}, traffic={}, setup_s=1.0,
                     window_s=window_s, records=[], counters=counters,
                     facts={}, trace=trace)


def _reader(h, name):
    return h.load_module(h.reader_path(h.HERE, name), name)


@pytest.mark.parametrize("name,counter", [
    ("mesh_submit_share.query", "clk_mesh_submit"),
    ("mesh_wait_share.query", "clk_mesh_wait")])
def test_mesh_stage_share_reads_its_counter(harness, name, counter):
    c = {"clk_mesh_submit": 2_000_000_000, "clk_mesh_wait": 300_000_000}
    r = _reader(harness, name)
    assert r.read(_view(harness, c)) == pytest.approx(c[counter] / 1e9 / 4)
    # a program without the stage (the parent of this cell): nothing
    assert r.read(_view(harness, {"clk_staging_retire": 5})) is None


def test_mesh_collective_share_reads_the_kernels_collectives(harness):
    from trace_reduce import Summary
    op_s = {"jit_efn/all-reduce.4": 0.02, "jit_efn/all-gather-start.1": 0.01,
            "jit_efn/all-gather-done.1": 0.005, "jit_efn/fusion.3": 0.5,
            "jit_add/all-reduce.1": 7.0, "?/all-reduce.9": 3.0}
    t = Summary(window_ns=(0.0, 4e9), busy_s=0.5, n_devices=4,
                module_s={"jit_efn": 0.7, "jit_add": 0.1},
                module_runs={"jit_efn": 8}, op_s=op_s, gaps={}, n_serve=2)
    r = _reader(harness, "mesh_collective_share.query")
    assert r.read(_view(harness, {}, trace=t)) == pytest.approx(0.035 / 0.7)
    assert r.read(_view(harness, {})) is None                   # untraced
    t.module_s = {"jit_add": 0.1}
    assert r.read(_view(harness, {}, trace=t)) is None          # no kernel


def test_the_cell_and_its_metrics_are_in_the_benchmark(harness):
    spec = harness.load_spec()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("tpch_q6_sf10_mesh4", "q6_stream", 4)
    metrics = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    for name in ("query_s", "device_idle_share.query", "scan_kernel_roofline",
                 "kernel_dispatches.query", "staging_retire_share.query"):
        assert CELL in metrics[name]["workloads"]
    for name in ("mesh_submit_share.query", "mesh_wait_share.query",
                 "mesh_collective_share.query"):
        m = metrics[name]
        assert m["workloads"] == [CELL] and m["moves"] == "query_s"
        assert os.path.exists(harness.reader_path(harness.HERE, name))
