#!/usr/bin/env python3
"""chip_smoke.py — bring the SSD→HBM load and scan path up on the chip.

One process drives the main path through the entry points a user calls,
at full size, and checks every answer against an independent reference:

  device     jax.devices() must be a TPU (an unset JAX_PLATFORMS lets JAX
             drop to the CPU when the TPU fails to start, so this is
             checked, not assumed)
  native     rebuild libstrom_tpu.so from csrc/ (a .so copied along with
             the tree is not trusted), load it, match its ABI version
  load       8 GiB seeded file (half of a v5e's HBM), page cache dropped,
             hbm.staging.load_file_to_device onto device 0; per-16 MiB
             checksums computed on the device and, from
             testing.expected_bytes, on the host must agree; the direct
             path must have moved chunks (nr_ssd2dev > 0)
  heap       ~4 GiB heap table (524,288 pages, two int32 columns) from the
             seed, via scan.heap.build_heap_file
  scan       SELECT COUNT(*), SUM(c1) FROM t WHERE c0 > 0 through
             scan.sql.sql_query, and Query(...).group_by(c0 % 16) through
             scan.query.Query; both equal a plain numpy reference (int32
             sums wrap mod 2^32 there too) and EXPLAIN reports the pallas
             kernel for both
  info       load GiB/s, host->device GiB/s of staged 16 MiB slices,
             compile seconds and the float GROUP BY pallas speedup —
             informational, labelled with the device; not a benchmark

``--chips 4`` runs only the sharded path: the same heap loaded over a
4-device mesh by parallel.shardload.load_pages_multihost (shards on 4
distinct devices, bytes checked per shard, the ring on the Pallas
remote-copy transport) and both queries in mesh mode against the same
reference.

The last line of stdout is ``{"ok": true, "device": {...}}`` and only
appears when every phase passed; any failure exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
GIB = 1 << 30
PAGE = 8192
DEFAULT_LOAD_GIB = 8.0            # half of a v5e's 16 GiB of HBM
HEAP_PAGES_PER_GIB = 65536        # 4 GiB of 8 KiB pages at the default
CK_BLOCK = 16 << 20               # checksum block (bytes)
_CK_MUL = 0x9E3779B1              # position weights: odd, so no byte hides
N_GROUPS = 16
THRESHOLD = 0


class PhaseError(Exception):
    """A phase's check failed."""


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseError(msg)


# -- checksums: position-weighted byte sums per block, mod 2^32 -------------

def _weights(n: int) -> np.ndarray:
    return np.arange(n, dtype=np.uint32) * np.uint32(_CK_MUL) + np.uint32(1)


def host_checksums(read_block, n_blocks: int, block: int) -> np.ndarray:
    """``read_block(k) -> bytes`` of block *k*; numpy, threaded."""
    w = _weights(block)

    def one(k):
        b = np.frombuffer(read_block(k), np.uint8)
        return np.sum(b.astype(np.uint32) * w, dtype=np.uint32)

    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        return np.array(list(ex.map(one, range(n_blocks))), np.uint32)


def device_checksums(x, block: int):
    """Same checksums on the device, one dispatch.  *x* is viewed as
    ``(-1, cols)`` rows — 128 lanes for a 1-D array (the TPU's own tiling,
    so the view moves no bytes), the page width for a page array."""
    cols = 128 if x.ndim == 1 else x.shape[-1]
    return _device_checksums(x, cols, block // cols)


@partial(jax.jit, static_argnames=("cols", "rows_per_block"))
def _device_checksums(x, cols, rows_per_block):
    x2 = x.reshape(-1, cols)
    w = (jax.lax.iota(jnp.uint32, rows_per_block * cols)
         * jnp.uint32(_CK_MUL) + jnp.uint32(1)).reshape(rows_per_block, cols)

    def one(k):
        blk = jax.lax.dynamic_slice_in_dim(x2, k * rows_per_block,
                                           rows_per_block)
        return jnp.sum(blk.astype(jnp.uint32) * w, dtype=jnp.uint32)

    n = x2.shape[0] // rows_per_block
    return jax.lax.map(one, jnp.arange(n, dtype=jnp.int32))


# -- phases ------------------------------------------------------------------

class CompileClock:
    """Backend compile seconds and persistent-cache hits in this process."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _ev(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def phase_device(chips: int) -> dict:
    import importlib.metadata as md

    from nvme_strom_tpu.compile_cache import enable_compile_cache
    devs = jax.devices()
    d0 = devs[0]
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    say("device", f"platform={d0.platform} kind={d0.device_kind!r} "
                  f"count={len(devs)} jax={jax.__version__} libtpu={libtpu}")
    check(d0.platform == "tpu",
          f"platform is {d0.platform!r}, not 'tpu': no accelerator")
    check(len(devs) >= chips, f"{chips} chips asked, {len(devs)} present")
    say("device", f"compile cache: {enable_compile_cache()}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def phase_native() -> str:
    out = subprocess.run(["make", "-B", "-C", os.path.join(REPO, "csrc")],
                         capture_output=True, text=True)
    check(out.returncode == 0,
          f"make -C csrc failed: {(out.stdout + out.stderr)[-600:]}")
    from nvme_strom_tpu import Session, _native
    from nvme_strom_tpu.analysis.abi import parse_header
    check(_native.native_available(), "native engine did not load")
    with open(os.path.join(REPO, "csrc", "strom_tpu.h")) as f:
        want = parse_header(f.read()).defines["NSTPU_API_VERSION"]
    got = _native.native_api_version()
    check(got == want,
          f"nstpu_engine_version {got} != NSTPU_API_VERSION {want}")
    with Session() as s:
        check(s._native is not None, "Session runs on the Python I/O pool")
        backend = s._native.backend_name
    say("native", f"built and loaded: api v{got}, backend {backend}")
    return backend


def phase_load(data_dir: str, nbytes: int, seed: int, device,
               block: int = CK_BLOCK) -> dict:
    from nvme_strom_tpu import Session, config, open_source
    from nvme_strom_tpu.hbm.staging import load_file_to_device
    from nvme_strom_tpu.stats import stats
    from nvme_strom_tpu.testing.fake import expected_bytes, make_test_file
    from nvme_strom_tpu.tools.common import drop_page_cache
    check(nbytes % block == 0, f"load size {nbytes} not a multiple of the "
                               f"{block}-byte checksum block")
    path = os.path.join(data_dir, "load.bin")
    t0 = time.monotonic()
    make_test_file(path, nbytes, seed=seed)
    drop_page_cache(path)            # fsync + POSIX_FADV_DONTNEED
    say("load", f"wrote {nbytes} bytes in {time.monotonic() - t0:.1f}s")
    # This phase tests the direct path.  The chip machine's 9p root keeps
    # a file cached after fsync + POSIX_FADV_DONTNEED, so page-cache
    # arbitration would send every chunk to the buffered path (all 8192
    # chunks did on the first chip run); arbitration is off for this
    # phase only
    arbitrate = config.get("cache_arbitration")
    config.set("cache_arbitration", False)
    counters = ("nr_ssd2dev", "total_dma_length")
    before = {k: stats._c.get(k, 0) for k in counters}
    try:
        with open_source(path) as src, Session() as sess:
            t0 = time.monotonic()
            arr = load_file_to_device(src, session=sess, device=device)
            load_s = time.monotonic() - t0
    finally:
        config.set("cache_arbitration", arbitrate)
    # read after the session closed: it folds the native engine's counters
    moved = {k: stats._c.get(k, 0) - v for k, v in before.items()}
    check(arr.shape == (nbytes,) and arr.devices() == {device},
          f"landed {arr.shape} on {arr.devices()}")
    direct = moved["total_dma_length"]
    say("load", f"nr_ssd2dev={moved['nr_ssd2dev']} tasks; direct path "
                f"{direct} bytes, page cache {nbytes - direct} bytes")
    check(moved["nr_ssd2dev"] > 0 and direct > 0,
          "no chunk took the direct SSD path")
    n = nbytes // block
    dev_ck = np.asarray(device_checksums(arr, block))
    arr.delete()
    host_ck = host_checksums(
        lambda k: expected_bytes(k * block, block, seed=seed), n, block)
    bad = np.flatnonzero(dev_ck != host_ck)
    check(bad.size == 0, f"{bad.size}/{n} blocks differ, first at byte "
                         f"{int(bad[0]) * block if bad.size else 0}")
    say("load", f"PASS: {n} checksums of {block} bytes agree "
                f"(device vs host expected_bytes)")
    os.unlink(path)
    return {"load_s": load_s, "bytes": nbytes}


def phase_h2d(device, nbytes: int, reps: int = 5) -> list:
    """Host->device GiB/s the way the staging ring moves bytes: a
    page-aligned host buffer in staging-batch slices (config chunk_size)
    through hbm.staging.h2d_transfer, every slice in flight before one
    fence.  Returns the *reps* rates, sorted."""
    import mmap

    from nvme_strom_tpu import config
    from nvme_strom_tpu.hbm.staging import h2d_transfer
    batch = int(config.get("chunk_size"))
    buf = mmap.mmap(-1, nbytes)
    host = np.frombuffer(buf, np.uint8)
    host[:] = 1
    slices = [host[o:o + batch] for o in range(0, nbytes, batch)]
    jax.block_until_ready(h2d_transfer(slices[0], device)[0])
    rates = []
    for _ in range(reps):
        t0 = time.monotonic()
        outs = [h2d_transfer(x, device)[0] for x in slices]
        jax.block_until_ready(outs)
        rates.append(nbytes / (time.monotonic() - t0) / GIB)
        for y in outs:
            y.delete()
    # no buf.close(): the runtime may still hold views of the host slices
    # (it did on the chip), and the mapping goes with the last of them
    return sorted(rates)


def make_columns(n_rows: int, seed: int):
    rng = np.random.default_rng(seed)
    c0 = rng.integers(-(1 << 20), 1 << 20, n_rows, dtype=np.int32)
    c1 = rng.integers(-(1 << 31), 1 << 31, n_rows, dtype=np.int32)
    return c0, c1


def phase_heap(data_dir: str, n_pages: int, seed: int):
    from nvme_strom_tpu.scan.heap import HeapSchema, build_heap_file
    from nvme_strom_tpu.tools.common import drop_page_cache
    schema = HeapSchema(n_cols=2, visibility=False)
    c0, c1 = make_columns(n_pages * schema.tuples_per_page, seed)
    path = os.path.join(data_dir, "t.heap")
    t0 = time.monotonic()
    got = build_heap_file(path, [c0, c1], schema)
    drop_page_cache(path)
    check(got == n_pages, f"heap has {got} pages, want {n_pages}")
    say("heap", f"{n_pages} pages ({len(c0)} rows, "
                f"{os.path.getsize(path)} bytes) in "
                f"{time.monotonic() - t0:.1f}s")
    return path, schema, c0, c1


def wrap32(v) -> np.ndarray:
    """Two's-complement int32 image of exact integer sums."""
    return ((np.asarray(v, np.int64) + (1 << 31)) % (1 << 32)
            - (1 << 31)).astype(np.int64)


def reference(c0: np.ndarray, c1: np.ndarray, k: int = THRESHOLD) -> dict:
    """Plain numpy answers of both queries (exact sums, then wrapped the
    way int32 accumulators wrap)."""
    sel = c0 > k
    keys = c0 % N_GROUPS
    gsum = np.zeros(N_GROUPS, np.int64)
    step = 1 << 20      # per-slab float sums stay exact (< 2^53)
    for lo in range(0, len(c0), step):
        gsum += np.rint(np.bincount(keys[lo:lo + step],
                                    weights=c1[lo:lo + step],
                                    minlength=N_GROUPS)).astype(np.int64)
    return {"count": int(np.count_nonzero(sel)),
            "sum": int(wrap32(c1[sel].sum(dtype=np.int64))),
            "g_count": np.bincount(keys, minlength=N_GROUPS).astype(np.int64),
            "g_sum": wrap32(gsum)}


SQL = f"SELECT COUNT(*), SUM(c1) FROM t WHERE c0 > {THRESHOLD}"


def phase_scan(path: str, schema, ref: dict, *, mesh=None,
               require_pallas: bool = True, tag: str = "scan") -> None:
    from nvme_strom_tpu.scan.query import Query
    from nvme_strom_tpu.scan.sql import parse_sql, sql_query
    want_kernel = "pallas" if require_pallas else None
    q, _ = parse_sql(SQL, path, schema)
    plan = q.explain(mesh=mesh)
    say(tag, f"EXPLAIN sql: {plan.kernel} kernel, {plan.mode} "
             f"({plan.reason})")
    if want_kernel:
        check(plan.kernel == want_kernel,
              f"sql plan took {plan.kernel}, not {want_kernel}")
    t0 = time.monotonic()
    out = sql_query(SQL, path, schema, mesh=mesh)
    dt = time.monotonic() - t0
    cnt, s = (int(np.asarray(v).reshape(-1)[0]) for v in
              (out["count(*)"], out["sum(c1)"]))
    check(cnt == ref["count"], f"COUNT(*) {cnt} != reference {ref['count']}")
    check(int(wrap32(s)) == ref["sum"],
          f"SUM(c1) {s} != reference {ref['sum']} (mod 2^32)")
    say(tag, f"PASS sql: count={cnt} sum={s} ({dt:.2f}s)")

    gq = Query(path, schema).group_by(lambda c: c[0] % N_GROUPS, N_GROUPS,
                                      agg_cols=[1])
    plan = gq.explain(mesh=mesh)
    say(tag, f"EXPLAIN group_by: {plan.kernel} kernel, {plan.mode} "
             f"({plan.reason})")
    if want_kernel:
        check(plan.kernel == want_kernel,
              f"group_by plan took {plan.kernel}, not {want_kernel}")
    t0 = time.monotonic()
    g = gq.run(mesh=mesh)
    dt = time.monotonic() - t0
    gc = np.asarray(g["count"], np.int64)
    gs = wrap32(np.asarray(g["sums"], np.int64)[0])
    check(np.array_equal(gc, ref["g_count"]),
          f"group counts {gc} != reference {ref['g_count']}")
    check(np.array_equal(gs, ref["g_sum"]),
          f"group sums {gs} != reference {ref['g_sum']} (mod 2^32)")
    say(tag, f"PASS group_by: {N_GROUPS} groups, {int(gc.sum())} rows "
             f"({dt:.2f}s)")


def phase_calibrate(device, batch_pages: int = 2048, iters: int = 20):
    """Float32 GROUP BY, pallas vs XLA, on one device-resident batch at
    the scan batch width: returns t_xla / t_pallas (the figure
    device_figures keeps as groupby_f32_pallas_speedup)."""
    from nvme_strom_tpu.ops.groupby import make_groupby_fn
    from nvme_strom_tpu.ops.groupby_pallas import make_groupby_fn_pallas
    from nvme_strom_tpu.scan.heap import HeapSchema, build_pages
    schema = HeapSchema(n_cols=2, dtypes=("int32", "float32"))
    n = batch_pages * schema.tuples_per_page
    rng = np.random.default_rng(0)
    vals = rng.standard_normal(n).astype(np.float32)
    pages = build_pages([rng.integers(0, 1 << 20, n, dtype=np.int32), vals],
                        schema)
    x = jax.device_put(pages, device)
    times, outs = {}, {}
    for name, make in (("xla", make_groupby_fn),
                       ("pallas", make_groupby_fn_pallas)):
        fn = make(schema, lambda c: c[0] % N_GROUPS, N_GROUPS, agg_cols=[1])
        outs[name] = jax.tree.map(np.asarray, fn(x))
        t0 = time.monotonic()
        for _ in range(iters):
            r = fn(x)
        jax.block_until_ready(r)
        times[name] = (time.monotonic() - t0) / iters
    x.delete()
    # the two kernels must agree before either's time means anything:
    # counts and min/max exactly, float sums within float32 rounding
    xo, po = outs["xla"], outs["pallas"]
    for k in ("count", "mins", "maxs"):
        check(np.array_equal(xo[k], po[k]), f"f32 GROUP BY {k} differ")
    tol = 1e-6 * float(np.abs(vals).sum())
    check(float(np.abs(xo["sums"] - po["sums"]).max()) <= tol,
          "f32 GROUP BY sums differ beyond float32 rounding")
    return times["xla"] / times["pallas"], times


def phase_mesh_load(path: str, n_pages: int, devices,
                    block: int = CK_BLOCK, transport: str = "pallas") -> None:
    """The heap over a 4-device mesh through load_pages_multihost: shards
    on distinct devices, bytes per shard equal to the file, and the ring
    on the Pallas remote-copy transport."""
    from nvme_strom_tpu import open_source
    from nvme_strom_tpu.parallel import shardload
    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    from nvme_strom_tpu.parallel.ring import permute_backend
    from nvme_strom_tpu.stats import stats
    mesh = make_scan_mesh(devices, sp=1)
    n_dev = len(devices)
    before = stats._c.get("nr_ici_permute", 0)
    t0 = time.monotonic()
    with open_source(path) as src:
        arr = shardload.load_pages_multihost(src, mesh, hosts=n_dev)
    arr.block_until_ready()
    say("mesh-load", f"{n_pages} pages over {n_dev} devices in "
                     f"{time.monotonic() - t0:.1f}s")
    shards = arr.addressable_shards
    on = {s.device for s in shards}
    check(len(on) == n_dev and on == set(devices),
          f"shards sit on {sorted(str(d) for d in on)}")
    for d in devices:
        say("mesh-load", f"{d}: bytes_in_use="
                         f"{(d.memory_stats() or {}).get('bytes_in_use')}")
    rows = n_pages // n_dev
    bpb = block // PAGE
    check(rows % bpb == 0, f"{rows} pages per shard not a multiple of {bpb}")
    fd = os.open(path, os.O_RDONLY)
    try:
        host_ck = host_checksums(lambda k: os.pread(fd, block, k * block),
                                 n_pages // bpb, block)
    finally:
        os.close(fd)
    for s in shards:
        first = (s.index[0].start or 0) // bpb
        dev_ck = np.asarray(device_checksums(s.data, block))
        want = host_ck[first:first + len(dev_ck)]
        check(np.array_equal(dev_ck, want),
              f"shard on {s.device} differs from the file")
    say("mesh-load", f"PASS: {len(shards)} shards on {n_dev} distinct "
                     f"devices, bytes equal the file")
    arr.delete()
    backend = permute_backend()
    keys = [k for k in shardload._redistribute_cache if k[0] == mesh]
    check(backend == transport and keys
          and all(k[-1] == transport for k in keys),
          f"ring transport {backend!r}, want {transport!r} (programs {keys})")
    _, axis, rows_max, rows_per_dev, _ = keys[0]
    from jax.sharding import NamedSharding, PartitionSpec as P
    fn = shardload._redistribute_cache[keys[0]]
    hlo = fn.lower(
        jax.ShapeDtypeStruct((n_dev * rows_max, PAGE), np.uint8,
                             sharding=NamedSharding(mesh, P(axis, None))),
        jax.ShapeDtypeStruct((n_dev * rows_max,), np.int32,
                             sharding=NamedSharding(mesh, P(axis)))
    ).as_text()
    check(("tpu_custom_call" in hlo) == (transport == "pallas"),
          f"redistribution HLO does not match the {transport} transport")
    moved = stats._c.get("nr_ici_permute", 0) - before
    check(moved > 0, "nr_ici_permute did not move")
    say("mesh-load", f"PASS: ring on the {transport} transport "
                     f"({moved} permute steps)")


# -- driver ------------------------------------------------------------------

def run(args) -> dict:
    chips = args.chips
    dev_info = phase_device(chips)
    clock = CompileClock()
    phase_native()
    # a directory of our own: --data-dir may name a disk holding other files
    os.makedirs(args.data_dir, exist_ok=True)
    work = tempfile.mkdtemp(prefix="chip_smoke-", dir=args.data_dir)
    load_bytes = int(args.size_gib * GIB) // CK_BLOCK * CK_BLOCK
    # whole checksum blocks on each of up to 4 shards
    unit = 4 * CK_BLOCK // PAGE
    n_pages = max(unit, int(args.size_gib * HEAP_PAGES_PER_GIB) // unit * unit)
    info = {}
    try:
        if chips == 1:
            dev = jax.devices()[0]
            got = phase_load(work, load_bytes, args.seed, dev)
            info["load GiB/s"] = got["bytes"] / got["load_s"] / GIB
            h2d_bytes = min(GIB, load_bytes)
            rates = phase_h2d(dev, h2d_bytes)
            info[f"h2d GiB/s, {h2d_bytes} bytes in staged slices (median; "
                 f"all runs)"] = (rates[len(rates) // 2], rates)
            path, schema, c0, c1 = phase_heap(work, n_pages, args.seed)
            ref = reference(c0, c1)
            del c0, c1
            phase_scan(path, schema, ref)
            sp, times = phase_calibrate(dev)
            info["groupby f32 pallas speedup (t_xla/t_pallas)"] = sp
            info["groupby f32 ms (xla, pallas)"] = (times["xla"] * 1e3,
                                                    times["pallas"] * 1e3)
        else:
            devices = jax.devices()[:chips]
            path, schema, c0, c1 = phase_heap(work, n_pages, args.seed)
            ref = reference(c0, c1)
            del c0, c1
            phase_mesh_load(path, n_pages, devices)
            from nvme_strom_tpu.parallel.mesh import make_scan_mesh
            phase_scan(path, schema, ref, mesh=make_scan_mesh(devices),
                       require_pallas=False, tag="mesh-scan")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    info["compile seconds (backend)"] = clock.seconds
    info["compile cache hits"] = clock.cache_hits
    for k, v in info.items():
        say("info", f"{k}: {v} on {dev_info['kind']} (informational, "
                    f"not a benchmark)")
    return dev_info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--size-gib", type=float, default=DEFAULT_LOAD_GIB,
                    help="load size in GiB (the heap is half of it); "
                         "reduce only for a rehearsal")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded load and mesh scans")
    ap.add_argument("--data-dir", default=os.path.join(REPO, ".smoke_data"),
                    help="where the run makes (and then removes) its own "
                         "subdirectory for the load file and the heap")
    args = ap.parse_args(argv)
    try:
        dev = run(args)
    except Exception as e:   # noqa: BLE001 - any failure: report, exit 1
        import traceback
        traceback.print_exc()
        print(f"FAIL: {type(e).__name__}: {e}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
