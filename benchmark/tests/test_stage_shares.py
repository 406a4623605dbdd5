"""The six stage-share readers of the restore cells: what each reads, that
each finds nothing in a program without the stage counters, and that on
a traced CPU restore through the staging ring they are shares of the
traced part that do not overlap."""

import time

import jax
import pytest

import harness
from test_cells import SMALL, SPEC, SEED, cell_named

#: reader -> (counter it reads, the counter it subtracts)
SHARES = {
    "submit_share.restore": ("clk_ioctl_memcpy_submit", "clk_pagecache_copy"),
    "pagecache_copy_share.restore": ("clk_pagecache_copy", None),
    "engine_wait_share.restore": ("clk_ioctl_memcpy_wait", None),
    "staging_fence_share.restore": ("clk_staging_fence", None),
    "staging_retire_share.restore": ("clk_staging_retire", None),
    "load_setup_share.restore": ("clk_load_setup", None),
}


def view(counters, window_s=4.0):
    return harness.RunView(cell={}, cfg={}, traffic={}, setup_s=1.0,
                           window_s=window_s, records=[], counters=counters,
                           facts={})


def read(name, v):
    return harness.load_module(harness.reader_path(harness.HERE, name),
                               name).read(v)


def test_every_share_is_in_the_benchmark_for_both_restore_cells():
    got = {m["name"]: m for m in SPEC["per_layer"]}
    for name in SHARES:
        m = got[name]
        assert m["source"] == "program_counter"
        assert m["moves"] == "restore_GiBps"
        assert m["workloads"] == ["restore8g.direct", "restore8g.cached"]


@pytest.mark.parametrize("name", sorted(SHARES))
def test_share_reads_its_counter_over_the_window(name):
    counters = {"clk_ioctl_memcpy_submit": 3_000_000_000,
                "clk_pagecache_copy": 2_000_000_000,
                "clk_ioctl_memcpy_wait": 1_000_000_000,
                "clk_staging_fence": 200_000_000,
                "clk_staging_retire": 400_000_000,
                "clk_load_setup": 100_000_000}
    own, less = SHARES[name]
    want = (counters[own] - (counters[less] if less else 0)) / 1e9 / 4.0
    assert read(name, view(counters)) == pytest.approx(want)
    # a program that does not count the stage: nothing to read
    assert read(name, view({"occ_busy_ns": 5})) is None
    if less:
        gone = dict(counters)
        del gone[less]
        assert read(name, view(gone)) is None


def test_traced_staged_restore_splits_into_shares(tmp_path):
    from nvme_strom_tpu import config
    cell = cell_named("restore8g.direct")
    was = config.get("landing")
    config.set("landing", "staged")       # the chip's path; CPU goes direct
    try:
        got = harness.run_cell(SPEC, cell, SEED, 0.2, True, time.monotonic(),
                               devices=jax.devices(),
                               cfg_override=SMALL[cell["config"]],
                               data_dir=str(tmp_path))
    finally:
        config.set("landing", was)
    assert harness.is_correct(got)
    m = harness.metrics_for(SPEC, cell, got, True, None)
    shares = {n: m[n]["value"] for n in SHARES}
    assert all(0.0 <= v <= 1.0 for v in shares.values()), shares
    assert shares["pagecache_copy_share.restore"] == 0.0   # arbitration off
    assert shares["staging_retire_share.restore"] > 0
    assert shares["load_setup_share.restore"] > 0
    assert sum(shares.values()) <= 1.0 + 1e-3
