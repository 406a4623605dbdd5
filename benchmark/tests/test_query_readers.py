"""The per-layer readers of the Q6 cell: what each reads, that each finds
nothing where its counter or trace is missing, and that a traced CPU run
of the cell reports the ones a CPU trace can feed."""

import math
import time

import jax
import pytest

import harness
from test_cells import SMALL, SPEC, SEED, cell_named
from trace_reduce import Summary

CELL = "tpch_q6.sf10"
QUERY_METRICS = ("device_idle_share.query", "scan_kernel_roofline",
                 "staging_retire_share.query", "vfs_read_share.query",
                 "kernel_dispatches.query")


def view(counters, window_s=4.0, trace=None):
    return harness.RunView(cell={}, cfg={}, traffic={}, setup_s=1.0,
                           window_s=window_s, records=[], counters=counters,
                           facts={}, trace=trace)


def summary(n_serve):
    return Summary(window_ns=(0.0, 4e9), busy_s=0.5, n_devices=1,
                   module_s={}, module_runs={}, op_s={}, gaps={},
                   n_serve=n_serve)


def read(name, v):
    return harness.load_module(harness.reader_path(harness.HERE, name),
                               name).read(v)


def test_every_query_metric_is_in_the_benchmark_for_the_q6_cell():
    got = {m["name"]: m for m in SPEC["per_layer"]}
    for name in QUERY_METRICS:
        assert got[name]["moves"] == "query_s"
        assert got[name]["workloads"] == [CELL]
    qs = next(m for m in SPEC["end_to_end"] if m["name"] == "query_s")
    assert qs["workloads"] == [CELL] and 0.01 <= qs["bound"] <= 0.25


@pytest.mark.parametrize("name,counter", [
    ("vfs_read_share.query", "clk_vfs_read"),
    ("staging_retire_share.query", "clk_staging_retire")])
def test_stage_share_reads_its_counter_over_the_window(name, counter):
    counters = {"clk_vfs_read": 2_600_000_000,
                "clk_staging_retire": 900_000_000}
    assert read(name, view(counters)) == pytest.approx(
        counters[counter] / 1e9 / 4.0)
    assert read(name, view({"nr_kernel_dispatch": 5})) is None


def test_kernel_dispatches_per_query_served_in_the_trace():
    c = {"nr_kernel_dispatch": 3 * 1196}
    assert read("kernel_dispatches.query",
                view(c, trace=summary(3))) == pytest.approx(1196)
    assert read("kernel_dispatches.query", view(c)) is None     # untraced
    assert read("kernel_dispatches.query", view(c, trace=summary(0))) is None
    assert read("kernel_dispatches.query", view({}, trace=summary(3))) is None


def test_traced_cpu_query_reports_its_counter_metrics(tmp_path):
    """A CPU trace has no TPU plane, so the device and kernel metrics are
    left out; the program-counter ones read the traced part."""
    cell = cell_named(CELL)
    got = harness.run_cell(SPEC, cell, SEED, 0.2, True, time.monotonic(),
                           devices=jax.devices(),
                           cfg_override=SMALL[cell["config"]],
                           data_dir=str(tmp_path))
    assert harness.is_correct(got), got["checks"]
    m = harness.metrics_for(SPEC, cell, got, True, None)
    assert set(m) == {"staging_retire_share.query", "vfs_read_share.query",
                      "kernel_dispatches.query"}
    batches = math.ceil(got["facts"]["pages_per_request"] / 1024)
    assert m["kernel_dispatches.query"]["value"] == batches
    shares = m["vfs_read_share.query"]["value"] + \
        m["staging_retire_share.query"]["value"]
    assert 0 < shares <= 1.0
