"""The TPC-H Q1 cell (``tpch_q1.sf10``) at SF 0.01 on the CPU.

``test_cells.py`` keeps, in ``SMALL``, the size at which its
parametrised cell tests (and ``test_control.py``'s) run each cell.
Importing this file registers the Q1 configuration's size there, and a
run of ``benchmark/tests`` imports every test file before it runs any,
so those tests never build the SF10 table here.  (The cell refuses to
build a table above SF 0.1 off the chip in any case.)

Here: the cell correct with every metric it reports, its control wrong
on every query, the reference's per-day cumulative sums against a
direct numpy Q1 at every DELTA, and one heap for the Q6 and Q1 cells
on one data directory."""

import math
import os
import time

import jax
import numpy as np

import harness
import test_cells
from test_cells import SEED, SPEC, cell_named, run

SMALL = {"scale_factor": 0.01}
test_cells.SMALL.setdefault("tpch_q1_sf10", SMALL)

CELL = "tpch_q1.sf10"
Q1 = harness.load_module(os.path.join(harness.HERE, "configs",
                                      "tpch_q1_sf10.py"), "tpch_q1_sf10")


def test_q1_cell_correct_with_every_metric(tmp_path):
    """Untraced: the end-to-end metrics; traced: the per-layer metrics
    a CPU trace can feed (no TPU plane, so no device idle share and no
    kernel roofline), two kernel dispatches a batch (discovery, then
    the grouped kernel)."""
    cell = cell_named(CELL)
    got = run(cell, tmp_path)
    assert harness.is_correct(got), got["checks"]
    assert got["checks"]["wrong_answers"]["value"] == 0
    assert set(harness.metrics_for(SPEC, cell, got, False, None)) == \
        {"setup_s", "query_s"}
    answer = got["records"][0]["answer"]
    assert list(zip(answer["l_returnflag"], answer["l_linestatus"])) == \
        [(ord("A"), ord("F")), (ord("N"), ord("F")), (ord("N"), ord("O")),
         (ord("R"), ord("F"))]
    got = harness.run_cell(SPEC, cell, SEED, 0.2, True, time.monotonic(),
                           devices=jax.devices(), cfg_override=SMALL,
                           data_dir=str(tmp_path))
    assert harness.is_correct(got), got["checks"]
    m = harness.metrics_for(SPEC, cell, got, True, None)
    assert set(m) == {"group_discover_share.query", "vfs_read_share.query",
                      "staging_retire_share.query",
                      "kernel_dispatches.query"}
    batches = math.ceil(got["facts"]["pages_per_request"] / 1024)
    assert m["kernel_dispatches.query"]["value"] == 2 * batches
    assert 0 < m["group_discover_share.query"]["value"] < 1
    assert got["facts"]["kernel"] == "xla"


def test_q1_control_is_wrong_on_every_query(tmp_path):
    got = run(cell_named(CELL), tmp_path, control=True)
    n = len(got["records"])
    assert n > 0 and got["checks"]["wrong_answers"]["value"] == n
    ref = Q1.Q1Reference(Q1.table_cfg({"table": "tpch_sf10", **SMALL}))
    for r in got["records"]:
        assert r["answer"]["sum_charge"] != \
            ref.answer(r["req"]["delta"])["sum_charge"]


def _direct_q1(cols, delta):
    """Q1 straight from its definition: mask the rows, loop over the
    groups in key order."""
    keep = cols["l_shipdate"] <= Q1.cutoff(delta)
    rf, ls = cols["l_returnflag"], cols["l_linestatus"]
    price, disc = cols["l_extendedprice"], cols["l_discount"]
    out = {k: [] for k in Q1.FIELDS}
    for a, b in sorted(set(zip(rf[keep].tolist(), ls[keep].tolist()))):
        m = keep & (rf == a) & (ls == b)
        n = np.int64(m.sum())
        dp = price[m] * (100 - disc[m])
        row = {"l_returnflag": a, "l_linestatus": b,
               "sum_qty": cols["l_quantity"][m].sum(),
               "sum_base_price": price[m].sum(), "sum_disc_price": dp.sum(),
               "sum_charge": (dp * (100 + cols["l_tax"][m])).sum(),
               "avg_qty": cols["l_quantity"][m].sum() / n,
               "avg_price": price[m].sum() / n,
               "avg_disc": disc[m].sum() / n, "count_order": n}
        for k, v in row.items():
            out[k].append(v.item() if hasattr(v, "item") else v)
    return out


def test_cumulative_reference_equals_direct_q1_at_every_delta():
    tcfg = Q1.table_cfg({"table": "tpch_sf10", **SMALL})
    ref = Q1.Q1Reference(tcfg)
    cols = Q1.reference_columns(tcfg)
    for delta in range(60, 121):
        assert ref.answer(delta) == _direct_q1(cols, delta), delta


def test_q6_then_q1_leave_one_heap(tmp_path):
    """The Q1 cell reads the Q6 cell's heap (same meta key) and writes no
    table of its own."""
    run(cell_named("tpch_q6.sf10"), tmp_path)
    before = {p: os.stat(p).st_mtime_ns for p in
              (tmp_path / "tpch_sf10").iterdir()}
    got = run(cell_named(CELL), tmp_path)
    assert harness.is_correct(got), got["checks"]
    heaps = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".heap")]
    assert sorted(os.path.basename(h) for h in heaps) == \
        ["lineitem.heap", "warm.heap"]
    assert {p: os.stat(p).st_mtime_ns for p in before} == before
    assert not [f for f in os.listdir(tmp_path / "tpch_q1_sf10")
                if not os.path.isdir(tmp_path / "tpch_q1_sf10" / f)]
