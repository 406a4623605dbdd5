"""The four-chip Q6 cell (``tpch_q6.sf10.mesh4``) at SF 0.01 here.

``test_cells.py`` keeps, in ``SMALL``, the size at which its
parametrised cell tests (and ``test_control.py``'s) run each cell.
Importing this file registers this configuration's size there, as
``test_q1_cell.py`` does for Q1, so those tests never build the SF10
table here (the cell refuses to build a table above SF 0.1 off the chip
in any case).  This directory's conftest gives one CPU device, so here
the cell runs on a mesh of one (dp=1); ``tests/test_mesh_q6.py`` runs it
on four virtual devices, with the readers it adds."""

import harness
import test_cells
from test_cells import SPEC, cell_named, run

SMALL = {"scale_factor": 0.01}
test_cells.SMALL.setdefault("tpch_q6_sf10_mesh4", SMALL)

CELL = "tpch_q6.sf10.mesh4"


def test_mesh4_cell_on_one_device_is_a_mesh_of_one(tmp_path):
    got = run(cell_named(CELL), tmp_path)
    assert harness.is_correct(got), got["checks"]
    f = got["facts"]
    assert (f["mode"], f["kernel"], f["dp"]) == ("mesh", "xla", 1)
    assert set(harness.metrics_for(SPEC, cell_named(CELL), got, False,
                                   None)) == {"setup_s", "query_s"}
