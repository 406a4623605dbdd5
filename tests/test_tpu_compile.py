"""The main path's kernels compiled for a described TPU v5e, at real sizes.

Nothing runs: the TPU compiler that ships with libtpu compiles for a chip
that is described and not attached, and refuses here what the chip would
refuse (misaligned tiles, too much VMEM or HBM, a kernel it cannot
lower).  The topology is described inside a module fixture, never at
import, so every xdist worker collects the same tests and only the one
that runs this file loads libtpu.  The Pallas kernels are built with
``interpret=False``: the CPU backend of this process would otherwise pick
interpret mode.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from nvme_strom_tpu.scan.heap import PAGE_SIZE, HeapSchema

SCAN_BATCH_PAGES = 2048          # config chunk_size 16 MiB / 8 KiB pages
LOAD_BYTES = 8 << 30             # chip_smoke's load: half of v5e HBM


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep these out of any cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # libtpu is installed with this repo's JAX: a topology that cannot be
    # described is a failure, never a skip
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pages(one_chip, n=SCAN_BATCH_PAGES):
    return _sds((n, PAGE_SIZE), jnp.uint8, one_chip)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_filter_pallas_compiles(one_chip):
    from nvme_strom_tpu.ops.filter_pallas import make_filter_fn_pallas
    fn = make_filter_fn_pallas(HeapSchema(n_cols=2),
                               lambda cols, th: cols[0] > th,
                               interpret=False)
    c = fn.lower(_pages(one_chip), _sds((), jnp.int32, one_chip)).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("agg_dtype", ["int32", "float32"])
def test_groupby_pallas_compiles(one_chip, agg_dtype):
    """chip_smoke's GROUP BY c0 % 16 (int) and its float calibration."""
    from nvme_strom_tpu.ops.groupby_pallas import make_groupby_fn_pallas
    schema = HeapSchema(n_cols=2, dtypes=("int32", agg_dtype))
    fn = make_groupby_fn_pallas(schema, lambda c: c[0] % 16, 16,
                                agg_cols=[1], interpret=False)
    c = jax.jit(fn).lower(_pages(one_chip)).compile()
    assert _has_kernel(c)


def test_decode_pallas_compiles(one_chip, tmp_path):
    from nvme_strom_tpu.ops.decode_pallas import make_decode_filter_fn_pallas
    from nvme_strom_tpu.scan.colpack import build_packed
    from nvme_strom_tpu.scan.heap import build_heap_file
    schema = HeapSchema(n_cols=3)
    n = 200_000
    rng = np.random.default_rng(0)
    cols = [(np.arange(n) % 16).astype(np.int32),
            np.repeat(np.arange(n // 512 + 1), 512)[:n].astype(np.int32),
            rng.integers(0, 8, n).astype(np.int32)]
    path = str(tmp_path / "p.heap")
    build_heap_file(path, cols, schema)
    meta = build_packed(path, schema)
    assert {c.codec for c in meta.cols} - {"raw"}, "nothing packed"
    fn = make_decode_filter_fn_pallas(meta, schema,
                                      lambda cols, *_: cols[0] > 3,
                                      interpret=False)
    c = jax.jit(fn).lower(_pages(one_chip)).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("what", ["landing", "checksum"])
def test_8gib_landing_and_check_stay_in_place(one_chip, what):
    """An 8 GiB uint8 destination is past int32 addressing, so landing is
    row mode.  Its row view must be a bitcast: a relayout would need a
    second 8 GiB buffer, which a 16 GiB chip does not have."""
    dest = _sds((LOAD_BYTES,), jnp.uint8, one_chip)
    if what == "landing":
        from nvme_strom_tpu.hbm.staging import _write_row
        chunk = _sds((16 << 20,), jnp.uint8, one_chip)
        c = _write_row.lower(dest, chunk,
                             _sds((), jnp.int32, one_chip)).compile()
        m = c.memory_analysis()
        assert m.alias_size_in_bytes == LOAD_BYTES   # donated, updated
    else:
        import chip_smoke
        c = chip_smoke._device_checksums.lower(
            dest, cols=128, rows_per_block=chip_smoke.CK_BLOCK // 128
        ).compile()
        m = c.memory_analysis()
    assert m.temp_size_in_bytes < (256 << 20)


def test_ring_redistribute_pallas_compiles(topo):
    """The sharded loader's ring on the Pallas remote-copy transport,
    over a 2x2 v5e mesh."""
    from jax.sharding import Mesh

    from nvme_strom_tpu.parallel.shardload import _make_redistribute
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4),
                axis_names=("sp", "dp"))
    rows = 2048
    fn = _make_redistribute(mesh, "dp", rows, rows, "pallas")
    c = fn.lower(
        _sds((4 * rows, PAGE_SIZE), jnp.uint8,
             NamedSharding(mesh, P("dp", None))),
        _sds((4 * rows,), jnp.int32, NamedSharding(mesh, P("dp")))
    ).compile()
    assert _has_kernel(c)
