#!/usr/bin/env python
"""bench_matrix.py — run every BASELINE.json config; write BENCH_MATRIX.json.

The five configs (BASELINE.json "configs"):

1. ssd2ram  : sequential O_DIRECT SSD→pinned host RAM (CPU-only baseline)
2. ssd2tpu  : single-file sequential SSD→TPU HBM (the headline, = bench.py)
3. ssd2tpu32: async multi-queue (32 outstanding requests)
4. raid0    : 4-member striped source → single HBM region
5. scan     : heap SeqScan direct-to-HBM + device filter kernel (pgsql analog)

Each config runs in a fresh subprocess, so this parent never touches JAX
and each child owns the chip.  Prints one human line per config and writes
the JSON matrix to BENCH_MATRIX.json (a run artifact, not committed; no
code reads it).  Device rows need a TPU: nothing here falls back.

Env: BENCH_SIZE_MB (default 512), BENCH_SMOKE=1 (64MB).
"""

import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


def _env(extra=None):
    from nvme_strom_tpu.compile_cache import enable_compile_cache
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    enable_compile_cache(env)   # every child shares one compile cache
    if extra:
        env.update(extra)
    return env


def _run(code: str, extra_env=None):
    """Run a python snippet in a subprocess; it must print GBPS=<float>,
    or SKIP=<reason> for a row whose precondition this runtime lacks
    (returned as None and left out of the matrix — a silently-degraded
    measurement must never masquerade as the real one)."""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=REPO, env=_env(extra_env), timeout=3600)
    if out.returncode != 0:
        sys.stderr.write(out.stdout + out.stderr)
        raise SystemExit("bench config failed")
    m = re.search(r"SKIP=(.+)", out.stdout)
    if m:
        sys.stderr.write(f"row skipped: {m.group(1).strip()}\n")
        return None
    m = re.search(r"GBPS=([0-9.]+)", out.stdout)
    if not m:
        sys.stderr.write(out.stdout + out.stderr)
        raise SystemExit("no GBPS in output")
    return float(m.group(1))


_COMMON = """
import os, time, numpy as np
from nvme_strom_tpu.testing import make_test_file
from nvme_strom_tpu.tools.common import drop_page_cache
size = {size}
"""

_SSD2RAM = _COMMON + """
from nvme_strom_tpu import open_source, Session
path = {path!r}
make_test_file(path, size) if not (os.path.exists(path) and os.path.getsize(path) == size) else None
# best-of-3: this shared host's disk throughput swings ~2x run to run,
# and a single cold sample under-reports the engine by that factor
best = 0.0
for _ in range(3):
    drop_page_cache(path)
    with open_source(path) as src, Session() as s:
        h, buf = s.alloc_dma_buffer(size)
        t0 = time.monotonic()
        res = s.memcpy_ssd2ram(src, h, list(range(size >> 20)), 1 << 20)
        s.memcpy_wait(res.dma_task_id)
        best = max(best, size / (time.monotonic() - t0))
        s.unmap_buffer(h); buf.close()
print(f"GBPS={{best/(1<<30):.3f}}")
"""

_SSD2TPU = _COMMON + """
import subprocess, sys, re
path = {path!r}
make_test_file(path, size) if not (os.path.exists(path) and os.path.getsize(path) == size) else None
out = subprocess.run([sys.executable, "-m", "nvme_strom_tpu.tools.ssd2tpu_test",
                      path, "-n", "{segs}", "-s", "16m"],
                     capture_output=True, text=True, timeout=1800)
if out.returncode != 0:
    sys.stderr.write(out.stdout + out.stderr); raise SystemExit(1)
m = re.search(r"=> ([0-9.]+) GB/s", out.stdout)
print(f"GBPS={{float(m.group(1)):.3f}}")
"""

_RAID0 = _COMMON + """
from nvme_strom_tpu.engine import StripedSource, Session
members = []
per = size // 4
for i in range(4):
    p = {path!r} + f".m{{i}}"
    if not (os.path.exists(p) and os.path.getsize(p) == per):
        make_test_file(p, per, seed=i)
    drop_page_cache(p)
    members.append(p)
best = 0.0
for _ in range(3):   # best-of-3 (shared-host disk noise)
    for p in members:
        drop_page_cache(p)
    src = StripedSource(members, stripe_chunk_size=512 << 10)
    with Session() as s:
        h, buf = s.alloc_dma_buffer(size)
        t0 = time.monotonic()
        res = s.memcpy_ssd2ram(src, h, list(range(size >> 20)), 1 << 20)
        s.memcpy_wait(res.dma_task_id)
        best = max(best, size / (time.monotonic() - t0))
        s.unmap_buffer(h); buf.close()
    src.close()
print(f"GBPS={{best/(1<<30):.3f}}")
"""

_AUTOTUNE_AB = _COMMON + """
# online-autotuner A/B (ISSUE 18): deliberately bad statics
# (submit_window=2, 256K request cap) vs the controller tuning the same
# workload live, on the latency-injected 2-member striped fake — the
# row is latency-bound by construction, so it is deterministic on any
# disk and independent of BENCH_SIZE_MB.  Journals one JSON line per
# run to AUTOTUNE_AB.jsonl; GBPS reports the CONVERGED tuned rate.
import json, statistics, tempfile
from nvme_strom_tpu import Session, config
from nvme_strom_tpu.testing import FakeStripedNvmeSource, FaultPlan
from nvme_strom_tpu.testing import make_test_file as _mk
CH = 64 << 10
n = 64
snap = config.snapshot()
with tempfile.TemporaryDirectory(prefix="strom_autotune_ab_") as d:
    paths = []
    for i in range(2):
        p = os.path.join(d, f"m{{i}}.bin")
        _mk(p, n // 2 * CH)
        paths.append(p)
    for k, v in (("io_backend", "python"), ("submit_window", 2),
                 ("member_queue_depth", 2), ("dma_max_size", 256 << 10),
                 ("cache_bytes", 0), ("cache_arbitration", False),
                 ("hedge_policy", "off"), ("autotune", False)):
        config.set(k, v)
    def passes(sess, src, rounds, tuner=None):
        h, buf = sess.alloc_dma_buffer(n * CH)
        out = []
        try:
            for _ in range(rounds):
                t0 = time.monotonic()
                r = sess.memcpy_ssd2ram(src, h, list(range(n)), CH)
                sess.memcpy_wait(r.dma_task_id, timeout=120)
                out.append(time.monotonic() - t0)
                if tuner is not None:
                    tuner.step_epoch()
        finally:
            sess.unmap_buffer(h)
        return out
    src = FakeStripedNvmeSource(paths, CH,
                                fault_plan=FaultPlan(latency_s=0.02),
                                force_cached_fraction=0.0)
    try:
        with Session() as sess:
            static = statistics.median(passes(sess, src, 4))
        config.set("autotune", True)
        with Session() as sess:
            sess._tuner.stop()     # drive epochs synchronously
            epochs = passes(sess, src, 20, tuner=sess._tuner)
        conv = statistics.median(epochs[-5:])
    finally:
        src.close()
        config.restore(snap)
row = {{"row": "autotune_convergence", "static_s": round(static, 4),
        "converged_s": round(conv, 4),
        "speedup": round(static / conv, 2), "epochs": len(epochs),
        "bytes": n * CH,
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}}
with open(os.path.join({repo!r}, "AUTOTUNE_AB.jsonl"), "a") as f:
    f.write(json.dumps(row) + "\\n")
print("autotune A/B:", row["speedup"], "x static")
print(f"GBPS={{n * CH / conv / (1<<30):.3f}}")
"""

_PASSTHRU_AB = _COMMON + """
# raw-passthrough submit overhead A/B (ISSUE 19): per-request cost of
# the resolved-SLBA raw command lane vs the O_DIRECT lane over the same
# extents, on the deterministic URING_CMD emulator — measures the
# submit-path machinery the raw rung deletes (per-request fd/alignment
# bounce, VFS dispatch), so it is disk-independent and runs on hosts
# with no NVMe char device.  Journals one JSON line per run to
# PASSTHRU_AB.jsonl (the same row `make passthru-gate` asserts on);
# GBPS reports the passthrough lane's per-request service rate.
import tempfile
from nvme_strom_tpu.testing.passthru_gate import ab_submit_overhead
with tempfile.TemporaryDirectory(prefix="strom_passthru_ab_") as d:
    row = ab_submit_overhead(d)
print("passthru A/B:", row["reduction"], "x O_DIRECT per-request cost")
print(f"GBPS={{row['req_bytes'] / row['passthru_ns_per_req'] * 1e9 / (1<<30):.3f}}")
"""

_MULTIHOST = _COMMON + """
# multi-host sharded load (ISSUE 17): per-host engine sessions read the
# ownership-split chunk grid concurrently and the landed shards
# redistribute over the mesh ring — the row is END-TO-END aggregate
# GB/s including the on-fabric move, the number the multichip gate
# holds scaling ratios on
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
from nvme_strom_tpu.engine import PlainSource
from nvme_strom_tpu.parallel.mesh import make_scan_mesh
from nvme_strom_tpu.parallel.shardload import load_pages_multihost
from nvme_strom_tpu.scan.heap import PAGE_SIZE
path = {path!r}
make_test_file(path, size) if not (os.path.exists(path) and os.path.getsize(path) == size) else None
mesh = make_scan_mesh(sp=1)
n_dev = mesh.shape["dp"]
hosts = {hosts}
if n_dev % hosts or (size // PAGE_SIZE) % n_dev:
    print(f"SKIP={{n_dev}} devices cannot host-shard {{hosts}} ways")
    raise SystemExit(0)
best = 0.0
for _ in range(3):   # round 1 also absorbs the redistribute compile
    drop_page_cache(path)
    with PlainSource(path) as src:
        t0 = time.monotonic()
        out = load_pages_multihost(src, mesh, hosts=hosts)
        out.block_until_ready()
        best = max(best, size / (time.monotonic() - t0))
print(f"GBPS={{best/(1<<30):.3f}}")
"""

_SCAN = _COMMON + """
import jax
from nvme_strom_tpu.scan.heap import HeapSchema, build_heap_file, PAGE_SIZE
from nvme_strom_tpu.scan.executor import TableScanner
from nvme_strom_tpu.ops.filter_pallas import scan_filter_step_pallas
path = {path!r} + ".heap"
schema = HeapSchema(n_cols=2, visibility=True)
t = schema.tuples_per_page
n_pages = size // PAGE_SIZE
if not (os.path.exists(path) and os.path.getsize(path) == n_pages * PAGE_SIZE):
    rng = np.random.default_rng(0)
    n = t * n_pages
    build_heap_file(path, [rng.integers(-1000, 1000, n).astype(np.int32),
                           rng.integers(0, 100, n).astype(np.int32)], schema)
drop_page_cache(path)
th = jax.device_put(np.int32(100))
fn = lambda pages: scan_filter_step_pallas(pages, th)
# warm the kernel with one batch-shaped input outside the timed region —
# COMMITTED to the device scan_filter uses: an uncommitted warm compiles a
# different (unplaced) specialization, and the first real batch pays a
# second ~0.8s compile inside the timed region
warm = np.zeros((min(2048, n_pages), PAGE_SIZE), np.uint8)
warm_dev = jax.device_put(warm, jax.devices()[0])
jax.block_until_ready(fn(warm_dev))
# warm the K-wide coalesced dispatch too (one traced call folds K
# batches — the streamed scan's steady-state shape); compiling it
# inside the timed region would understate the row
from nvme_strom_tpu.config import config as _cfg
from nvme_strom_tpu.scan.executor import CoalescedFold
fold = CoalescedFold(fn, int(_cfg.get("scan_dispatch_batch")))
if fold.k > 1:
    jax.block_until_ready(fold(*([warm_dev] * fold.k)))
with TableScanner(path, schema, numa_bind=False) as sc:
    t0 = time.monotonic()
    out = sc.scan_filter(fn, dispatch_coalesce=fold)
    dt = time.monotonic() - t0
nbytes = n_pages * PAGE_SIZE
print("result:", {{k: int(v) for k, v in out.items()}})
print(f"GBPS={{nbytes/dt/(1<<30):.3f}}")
"""


_FILTER_CHIP = _COMMON + """
# on-chip filter kernel microbench (VERDICT r1 #6 proof-of-worth): pallas
# and XLA consume the identical HBM-resident page batch; ITERS iterations
# run inside ONE dispatch (fori_loop) so per-call dispatch latency cannot
# pollute the on-chip number.  Threshold varies per iteration so the
# compiler cannot hoist the loop body.
import jax, jax.numpy as jnp
from jax import lax
from nvme_strom_tpu.scan.heap import HeapSchema, build_pages, PAGE_SIZE
schema = HeapSchema(n_cols=2, visibility=True)
batch_bytes = min(size, 32 << 20)
n_pages = batch_bytes // PAGE_SIZE
rng = np.random.default_rng(0)
n = schema.tuples_per_page * n_pages
pages = build_pages([rng.integers(-1000, 1000, n).astype(np.int32),
                     rng.integers(0, 100, n).astype(np.int32)], schema)
if {use_pallas}:
    from nvme_strom_tpu.ops.filter_pallas import scan_filter_step_pallas as fn
else:
    from nvme_strom_tpu.ops.filter_xla import scan_filter_step as fn
# Each iteration filters a different page window (sliding dynamic_slice):
# with an invariant input XLA hoists the whole decode out of the loop.
# ITERS iterations run inside ONE dispatch (fori_loop) and the best of 3
# dispatches is kept.
ITERS = 16
pad = np.zeros((ITERS, PAGE_SIZE), np.uint8)
big = np.concatenate([pages, pad], 0)
@jax.jit
def loop(bp):
    def body(i, acc):
        p = lax.dynamic_slice(bp, (i, 0), (n_pages, PAGE_SIZE))
        out = fn(p, i.astype(jnp.int32))
        return acc + out["count"]
    return lax.fori_loop(0, ITERS, body, jnp.int32(0))
dp = jax.device_put(big)
jax.block_until_ready(dp)
jax.block_until_ready(loop(dp))  # compile + warm
dt = None
# min-of-9: single-dispatch samples occasionally eat a multi-10us queue
# stall (observed as a 2.8x outlier row); more samples make the min a
# stable estimator of the unstalled dispatch
for _ in range(9):
    t0 = time.monotonic()
    jax.block_until_ready(loop(dp))
    d = time.monotonic() - t0
    dt = d if dt is None else min(dt, d)
print(f"GBPS={{n_pages * PAGE_SIZE * ITERS / dt / (1<<30):.3f}}")
"""

_GROUPBY_CHIP = _COMMON + """
# on-chip GROUP BY microbench, FLOAT aggregation column (VERDICT r2 #5):
# pallas single-pass SMEM kernel vs the XLA segment-sum path on the
# identical HBM-resident batch.  Same single-dispatch fori_loop discipline
# as the filter chip rows (ratio is the metric, not absolute GB/s).
import jax, jax.numpy as jnp
from jax import lax
from nvme_strom_tpu.scan.heap import HeapSchema, build_pages, PAGE_SIZE
schema = HeapSchema(n_cols=2, visibility=True,
                    dtypes=("float32", "int32"))
batch_bytes = min(size, 32 << 20)
n_pages = batch_bytes // PAGE_SIZE
rng = np.random.default_rng(0)
n = schema.tuples_per_page * n_pages
G = 16
pages = build_pages(
    [(rng.standard_normal(n) * 50 + 100).astype(np.float32),
     rng.integers(0, G, n).astype(np.int32)], schema)
key = lambda cols, th: cols[1]
pred = lambda cols, th: cols[0] > th.astype(jnp.float32)
if {use_pallas}:
    from nvme_strom_tpu.ops.groupby_pallas import make_groupby_fn_pallas
    fn = make_groupby_fn_pallas(schema, key, G, agg_cols=[0],
                                predicate=pred)
else:
    from nvme_strom_tpu.ops.groupby import make_groupby_fn
    fn = make_groupby_fn(schema, key, G, agg_cols=[0], predicate=pred)
ITERS = 16
pad = np.zeros((ITERS, PAGE_SIZE), np.uint8)
big = np.concatenate([pages, pad], 0)
@jax.jit
def loop(bp):
    def body(i, acc):
        p = lax.dynamic_slice(bp, (i, 0), (n_pages, PAGE_SIZE))
        out = fn(p, i)
        return acc + out["sums"][0, 0]
    return lax.fori_loop(0, ITERS, body, jnp.float32(0))
dp = jax.device_put(big)
jax.block_until_ready(dp)
jax.block_until_ready(loop(dp))  # compile + warm
dt = None
# min-of-9: single-dispatch samples occasionally eat a multi-10us queue
# stall (observed as a 2.8x outlier row); more samples make the min a
# stable estimator of the unstalled dispatch
for _ in range(9):
    t0 = time.monotonic()
    jax.block_until_ready(loop(dp))
    d = time.monotonic() - t0
    dt = d if dt is None else min(dt, d)
print(f"GBPS={{n_pages * PAGE_SIZE * ITERS / dt / (1<<30):.3f}}")
"""

_RAW = _COMMON + """
# fio-style raw denominator: sequential O_DIRECT pread, no framework at
# all — the "raw NVMe bandwidth" every BASELINE target is a percentage of
path = {path!r}
make_test_file(path, size) if not (os.path.exists(path) and os.path.getsize(path) == size) else None
drop_page_cache(path)
import mmap
blk = 4 << 20
buf = mmap.mmap(-1, blk)
best = 0.0
for _ in range(3):   # best-of-3, same policy as the engine rows
    drop_page_cache(path)
    try:
        fd = os.open(path, os.O_RDONLY | os.O_DIRECT)
    except OSError:  # tmpfs etc. reject O_DIRECT; measure buffered-cold
        fd = os.open(path, os.O_RDONLY)
    t0 = time.monotonic()
    off = 0
    while off < size:
        n = os.preadv(fd, [buf], off)
        assert n > 0
        off += n
    best = max(best, size / (time.monotonic() - t0))
    os.close(fd)
print(f"GBPS={{best/(1<<30):.3f}}")
"""

_RAW_WRITE = _COMMON + """
# raw write denominator: sequential O_DIRECT pwrite, no framework — the
# number ram2ssd_seq is a percentage of (a read denominator would be
# wrong-in-kind for the write leg)
import mmap
path = {path!r} + ".rawwr"
blk = 4 << 20
buf = mmap.mmap(-1, blk)
buf[:] = os.urandom(blk)
best = 0.0
try:
    for _ in range(3):
        try:
            fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_DIRECT, 0o644)
        except OSError:  # tmpfs etc. reject O_DIRECT; buffered+fsync instead
            fd = os.open(path, os.O_WRONLY | os.O_CREAT, 0o644)
        os.ftruncate(fd, size)
        t0 = time.monotonic()
        off = 0
        while off < size:
            n = os.pwritev(fd, [buf], off)
            assert n > 0
            off += n
        os.fsync(fd)
        best = max(best, size / (time.monotonic() - t0))
        os.close(fd)
finally:
    if os.path.exists(path):
        os.unlink(path)
print(f"GBPS={{best/(1<<30):.3f}}")
"""

_RAM2SSD = _COMMON + """
from nvme_strom_tpu import Session
from nvme_strom_tpu.engine import open_source
path = {path!r} + ".wr"
with open(path, "wb") as f:
    f.truncate(size)
payload = np.random.default_rng(3).integers(0, 255, size, dtype=np.uint8).tobytes()
best = 0.0
for _ in range(3):   # best-of-3 (shared-host disk noise)
    with open_source(path, writable=True) as sink, Session() as s:
        h, buf = s.alloc_dma_buffer(size)
        buf.view()[:] = payload
        t0 = time.monotonic()
        res = s.memcpy_ram2ssd(sink, h, list(range(size >> 20)), 1 << 20)
        s.memcpy_wait(res.dma_task_id)
        sink.sync()
        best = max(best, size / (time.monotonic() - t0))
        s.unmap_buffer(h); buf.close()
os.unlink(path)
print(f"GBPS={{best/(1<<30):.3f}}")
"""

_H2D = _COMMON + """
import jax
# transport ceiling: pinned-host->HBM device_put alone, no SSD at all.
# ssd2tpu_* rows approaching this number mean the SSD DMA leg is fully
# hidden behind the host->device hop (the overlap goal, SURVEY SS5.8b);
# the ceiling itself is a host property, not framework overhead.
a = np.random.randint(0, 255, size, dtype=np.uint8)
jax.device_put(a[: 1 << 20]).block_until_ready()
t0 = time.monotonic()
step = 16 << 20
for off in range(0, size, step):
    jax.device_put(a[off:off + step]).block_until_ready()
dt = time.monotonic() - t0
print(f"GBPS={{size/dt/(1<<30):.3f}}")
"""

_H2D_PINNED = _COMMON + """
# A/B against h2d_peak (VERDICT r2 #2): the same transfer volume through
# the two-stage pinned_host path — device_put into the PJRT pinned_host
# memory space, jitted pinned->device DMA — sourced from the engine's own
# page-aligned pinned staging buffer, i.e. exactly what the staging
# pipeline moves.  h2d_pinned_peak ~ h2d_peak means plain device_put
# already consumes the pinned buffer without an extra staging copy on
# this runtime (PJRT zero-copy case); h2d_pinned_peak > h2d_peak means
# the pinned_host space earns its keep and config h2d_path=pinned_host
# should be the deployed default.
import jax
from nvme_strom_tpu import Session, config
from nvme_strom_tpu import StromError
from nvme_strom_tpu.hbm.staging import h2d_transfer, _pinned_shardings
config.set("h2d_path", "pinned_host")
dev = jax.devices()[0]
try:
    _pinned_shardings(dev)
except StromError as e:
    print("SKIP=", e)
    raise SystemExit(0)
step = 16 << 20
with Session() as s:
    h, buf = s.alloc_dma_buffer(step)
    host = np.frombuffer(buf.view(), np.uint8)
    host[:] = np.random.randint(0, 255, step, dtype=np.uint8)
    d0, f0 = h2d_transfer(host[: 1 << 20], dev)
    jax.block_until_ready(d0)
    t0 = time.monotonic()
    done = 0
    while done < size:
        d, f = h2d_transfer(host, dev)
        jax.block_until_ready(d)
        done += step
    dt = time.monotonic() - t0
    s.unmap_buffer(h); buf.close()
print(f"GBPS={{size/dt/(1<<30):.3f}}")
"""

_CKPT = _COMMON + """
import jax
from nvme_strom_tpu.data import save_checkpoint, restore_checkpoint
path = {path!r} + ".strom"
n = size // 4 // 1024
ok = False
if os.path.exists(path):
    try:
        from nvme_strom_tpu.data.checkpoint import checkpoint_info
        meta = checkpoint_info(path)
        e = meta["leaves"][0]
        ok = (e["nbytes"] == n * 4096 and os.path.getsize(path)
              >= meta["data_offset"] + e["offset"] + e["nbytes"])
    except Exception:
        ok = False
if not ok:
    rng = np.random.default_rng(0)
    save_checkpoint(path, {{"w": rng.standard_normal((n, 1024)).astype(np.float32)}})
drop_page_cache(path)
# warm the device path (first H2D pays backend init) outside the timed region
jax.device_put(np.zeros(1 << 20, np.uint8)).block_until_ready()
t0 = time.monotonic()
out = restore_checkpoint(path)
jax.block_until_ready(list(out.values()))
dt = time.monotonic() - t0
nbytes = n * 1024 * 4
print(f"GBPS={{nbytes/dt/(1<<30):.3f}}")
"""


_SCAN_CPU = _COMMON + """
# the SAME heap scan + filter with the compute on the HOST CPU backend.
# Divided by ssd2ram_seq (same SSD leg, no compute) in the derived
# block: cpu_pipeline_efficiency isolates the pipeline's overlap quality
# from the device transport.
import jax
jax.config.update("jax_platforms", "cpu")
from nvme_strom_tpu.scan.heap import HeapSchema, build_heap_file, PAGE_SIZE
from nvme_strom_tpu.scan.executor import TableScanner
from nvme_strom_tpu.ops.filter_xla import scan_filter_step
path = {path!r} + ".heap"
schema = HeapSchema(n_cols=2, visibility=True)
t = schema.tuples_per_page
n_pages = size // PAGE_SIZE
if not (os.path.exists(path) and os.path.getsize(path) == n_pages * PAGE_SIZE):
    rng = np.random.default_rng(0)
    n = t * n_pages
    build_heap_file(path, [rng.integers(-1000, 1000, n).astype(np.int32),
                           rng.integers(0, 100, n).astype(np.int32)], schema)
th = np.int32(100)
fn = lambda pages: scan_filter_step(pages, th)
from nvme_strom_tpu.config import config as _cfg
warm = np.zeros(((int(_cfg.get("chunk_size")) // PAGE_SIZE), PAGE_SIZE),
                np.uint8)
jax.block_until_ready(fn(jax.device_put(warm)))
best = 0.0
for _ in range(3):   # best-of-3 (shared-host disk noise)
    drop_page_cache(path)
    with TableScanner(path, schema, numa_bind=False) as sc:
        t0 = time.monotonic()
        out = sc.scan_filter(fn)
        best = max(best, n_pages * PAGE_SIZE / (time.monotonic() - t0))
print(f"GBPS={{best/(1<<30):.3f}}")
"""

_CTAS_WRITE = _COMMON + """
# CREATE TABLE AS materialization (VERDICT r4 weak #6: the write path
# benched) — scan + filter + re-encode + write a derived table; bytes
# WRITTEN per second, anchored to raw_seq_write in the derived block.
import jax
jax.config.update("jax_platforms", "cpu")
from nvme_strom_tpu.scan.heap import HeapSchema, build_heap_file, PAGE_SIZE
from nvme_strom_tpu.scan.sql import create_table_as
path = {path!r} + ".heap"
dest = {path!r} + ".ctas.heap"
schema = HeapSchema(n_cols=2, visibility=True)
t = schema.tuples_per_page
n_pages = size // PAGE_SIZE
if not (os.path.exists(path) and os.path.getsize(path) == n_pages * PAGE_SIZE):
    rng = np.random.default_rng(0)
    n = t * n_pages
    build_heap_file(path, [rng.integers(-1000, 1000, n).astype(np.int32),
                           rng.integers(0, 100, n).astype(np.int32)], schema)
best = 0.0
try:
    for _ in range(3):
        drop_page_cache(path)
        t0 = time.monotonic()
        create_table_as(dest, "SELECT c0, c1 FROM t", path, schema,
                        overwrite=True)
        dt = time.monotonic() - t0
        best = max(best, os.path.getsize(dest) / dt)
finally:
    if os.path.exists(dest):
        os.unlink(dest)
print(f"GBPS={{best/(1<<30):.3f}}")
"""

_CKPT_SAVE = _COMMON + """
# checkpoint SAVE through the engine's async O_DIRECT write queue
# (data/checkpoint._save_leaves_direct) — the write twin of
# ckpt_restore, anchored to raw_seq_write in the derived block.
from nvme_strom_tpu.data import save_checkpoint
path = {path!r} + ".cksave.strom"
rng = np.random.default_rng(1)
arr = rng.standard_normal(size // 4).astype(np.float32)
best = 0.0
try:
    for _ in range(3):
        t0 = time.monotonic()
        save_checkpoint(path, {{"w": arr}}, direct=True)
        best = max(best, size / (time.monotonic() - t0))
finally:
    if os.path.exists(path):
        os.unlink(path)
print(f"GBPS={{best/(1<<30):.3f}}")
"""

_HEAVY_SCAN = _COMMON + """
# CPU-bound filter (60-leaf OR tree) at {workers} worker processes
# (0 = serial, jit warmed outside the timed window; workers pay their
# real spawn + jit cost INSIDE it — the honest end-to-end comparison
# the parallel_speedup ratio divides).
import jax
jax.config.update("jax_platforms", "cpu")
from nvme_strom_tpu.scan.heap import HeapSchema, build_heap_file, PAGE_SIZE
from nvme_strom_tpu.scan.sql import sql_query
path = {path!r} + ".hv.heap"
schema = HeapSchema(n_cols=2)
t = schema.tuples_per_page
n_pages = size // PAGE_SIZE
if not (os.path.exists(path) and os.path.getsize(path) == n_pages * PAGE_SIZE):
    rng = np.random.default_rng(0)
    n = t * n_pages
    build_heap_file(path, [rng.integers(0, 1_000_000, n).astype(np.int32),
                           rng.integers(0, 100, n).astype(np.int32)],
                    schema)
stmt = ("SELECT COUNT(*) AS n FROM t WHERE " +
        " OR ".join(f"(c0 > {{k * 16000}} AND c0 < {{k * 16000 + 900}})"
                    for k in range(60)))
w = {workers}
if not w:
    sql_query(stmt, path, schema)        # warm the serial jit
drop_page_cache(path)
t0 = time.monotonic()
r = sql_query(stmt, path, schema, **({{"workers": w}} if w else {{}}))
dt = time.monotonic() - t0
print("rows:", r["n"])
print(f"GBPS={{n_pages * PAGE_SIZE / dt / (1<<30):.3f}}")
"""


def main() -> int:
    from bench import hold_bench_lock
    _lock = hold_bench_lock("bench_matrix.py")   # released on exit
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    size_mb = 64 if smoke else int(os.environ.get("BENCH_SIZE_MB", "512"))
    size = size_mb << 20
    base = f"/tmp/strom_matrix_{size_mb}"

    configs = [
        ("raw_seq_read", "raw O_DIRECT pread (no framework; denominator)",
         _RAW.format(size=size, path=base + ".bin"), None),
        ("h2d_peak", "host->HBM device_put (transport ceiling)",
         _H2D.format(size=size), None),
        ("h2d_pinned_peak", "host->HBM via pinned_host space (A/B)",
         _H2D_PINNED.format(size=size), None),
        ("ssd2ram_seq", "SSD->pinned RAM, O_DIRECT seq",
         _SSD2RAM.format(size=size, path=base + ".bin"), None),
        ("raw_seq_write", "raw O_DIRECT pwrite (write denominator)",
         _RAW_WRITE.format(size=size, path=base), None),
        ("ram2ssd_seq", "pinned RAM->SSD write (native write queue)",
         _RAM2SSD.format(size=size, path=base), None),
        # seq vs mq32 isolates async depth: the engine queue is capped at 4
        # outstanding NVMe requests for the "seq" row and opened to the
        # 32-deep multi-queue default for the mq32 row (BASELINE.json config 3)
        ("ssd2tpu_seq", "SSD->TPU HBM, single file",
         _SSD2TPU.format(size=size, path=base + ".bin", segs=6),
         {"STROM_TPU_QUEUE_DEPTH": "4"}),
        ("ssd2tpu_mq32", "SSD->TPU HBM, 32 outstanding",
         _SSD2TPU.format(size=size, path=base + ".bin", segs=8),
         {"STROM_TPU_QUEUE_DEPTH": "32"}),
        ("raid0_4x", "4-member RAID-0 -> pinned RAM",
         _RAID0.format(size=size, path=base), None),
        ("multihost_2x", "2-host sharded load + on-fabric redistribute",
         _MULTIHOST.format(size=size, path=base + ".bin", hosts=2), None),
        ("autotune_convergence", "online autotuner vs bad statics (A/B)",
         _AUTOTUNE_AB.format(size=size, repo=REPO), None),
        ("passthru_submit_overhead", "raw NVMe cmd vs O_DIRECT submit (A/B)",
         _PASSTHRU_AB.format(size=size), None),
        ("scan_filter", "heap scan -> HBM + pallas filter",
         _SCAN.format(size=size, path=base), None),
        ("filter_pallas_chip", "on-chip pallas filter kernel",
         _FILTER_CHIP.format(size=size, use_pallas=1), None),
        ("filter_xla_chip", "on-chip XLA filter (same batch)",
         _FILTER_CHIP.format(size=size, use_pallas=0), None),
        ("groupbyf_pallas_chip", "on-chip pallas float GROUP BY",
         _GROUPBY_CHIP.format(size=size, use_pallas=1), None),
        ("groupbyf_xla_chip", "on-chip XLA float GROUP BY (same batch)",
         _GROUPBY_CHIP.format(size=size, use_pallas=0), None),
        ("ckpt_restore", "checkpoint -> HBM direct restore",
         _CKPT.format(size=size, path=base), None),
        ("scan_filter_cpu", "heap scan + CPU-backend filter",
         _SCAN_CPU.format(size=size, path=base), None),
        ("ctas_write", "CREATE TABLE AS materialization (write leg)",
         _CTAS_WRITE.format(size=size, path=base), None),
        ("ckpt_save", "checkpoint save via O_DIRECT write queue",
         _CKPT_SAVE.format(size=size, path=base), None),
        ("scan_heavy_serial", "60-leaf OR filter, serial",
         _HEAVY_SCAN.format(size=size, path=base, workers=0), None),
        ("scan_heavy_workers4", "60-leaf OR filter, 4 worker processes",
         _HEAVY_SCAN.format(size=size, path=base, workers=4), None),
    ]
    results = {}
    captured_at = {}
    for key, desc, code, env in configs:
        gbps = _run(code, env)
        if gbps is None:
            continue
        results[key] = gbps
        captured_at[key] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime())
        print(f"{key:<14} {desc:<34} {gbps:7.3f} GB/s")
    path = _write_matrix(size_mb, results, captured_at)
    print(f"wrote {path}")
    return 0


def _write_matrix(size_mb: int, results: dict, captured_at: dict) -> str:
    """Atomically write BENCH_MATRIX.json with the derived blocks."""
    # derived ratios (VERDICT r1 #2): every BASELINE ">=90% of raw" target
    # becomes checkable from this one JSON
    raw = results.get("raw_seq_read", 0.0)
    h2d = results.get("h2d_peak", 0.0)
    # *_chip rows are on-chip compute, not storage rows — a chip/raw-SSD
    # ratio would be meaningless in the ">=90% of raw" checkable block
    raww = results.get("raw_seq_write", 0.0)
    pct_of_raw = {k: round(v / raw, 3) for k, v in results.items()
                  if raw and k not in ("raw_seq_read", "raw_seq_write",
                                       "ram2ssd_seq", "ctas_write",
                                       "ckpt_save", "scan_heavy_serial",
                                       "scan_heavy_workers4",
                                       # per-request latency A/B on the
                                       # emulator, not a throughput row
                                       "passthru_submit_overhead")
                  and not k.endswith("_chip")}
    if raww and "ram2ssd_seq" in results:
        # the write leg's denominator is the raw WRITE bandwidth
        pct_of_raw["ram2ssd_seq"] = round(results["ram2ssd_seq"] / raww, 3)
    ceiling = min(raw, h2d) if raw and h2d else 0.0
    overlap_efficiency = {
        k: round(results[k] / ceiling, 3)
        for k in ("ssd2tpu_seq", "ssd2tpu_mq32", "scan_filter",
                  "ckpt_restore")
        if ceiling and k in results}
    # transport-independent twin (VERDICT r4 weak #2): the CPU-backend
    # scan+filter against the same-host SSD->RAM engine row
    cpu_pipeline_efficiency = (
        round(results["scan_filter_cpu"] / results["ssd2ram_seq"], 3)
        if results.get("ssd2ram_seq") and results.get("scan_filter_cpu")
        else None)
    if raww:
        # write-leg rows anchor to the raw WRITE denominator
        for k in ("ctas_write", "ckpt_save"):
            if k in results:
                pct_of_raw[k] = round(results[k] / raww, 3)
    # the Gather analog's end-to-end wall-clock win (spawn + jit costs
    # included on the worker side)
    parallel_speedup = (
        round(results["scan_heavy_workers4"] /
              results["scan_heavy_serial"], 3)
        if results.get("scan_heavy_serial")
        and results.get("scan_heavy_workers4") else None)
    # the pallas kernel's justification: on-chip GB/s vs the XLA twin on
    # the identical batch (>1.0 = the hand kernel earns its keep)
    pallas_vs_xla = (round(results["filter_pallas_chip"] /
                           results["filter_xla_chip"], 3)
                     if results.get("filter_xla_chip")
                     and results.get("filter_pallas_chip") else None)
    pallas_vs_xla_groupby = (round(results["groupbyf_pallas_chip"] /
                                   results["groupbyf_xla_chip"], 3)
                             if results.get("groupbyf_xla_chip")
                             and results.get("groupbyf_pallas_chip")
                             else None)
    path = os.path.join(REPO, "BENCH_MATRIX.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"size_mb": size_mb, "unit": "GB/s",
                   "note": "h2d_peak is the host->HBM transport ceiling; "
                           "TPU-destination rows are bounded by it, "
                           "CPU-destination rows (ssd2ram/raid0) show the "
                           "engine's own throughput. pct_of_raw anchors "
                           "read rows to raw_seq_read and ram2ssd_seq to "
                           "raw_seq_write (like-for-like); "
                           "overlap_efficiency = achieved / min(raw ssd, "
                           "h2d ceiling) isolates pipeline overlap quality "
                           "from transport limits",
                   "results": results,
                   "row_captured_at": captured_at,
                   "pct_of_raw": pct_of_raw,
                   "overlap_efficiency": overlap_efficiency,
                   "cpu_pipeline_efficiency": cpu_pipeline_efficiency,
                   "parallel_speedup": parallel_speedup,
                   "pallas_vs_xla": pallas_vs_xla,
                   "pallas_vs_xla_groupby": pallas_vs_xla_groupby}, f,
                  indent=2)
        f.write("\n")
    os.replace(tmp, path)
    return path


if __name__ == "__main__":
    sys.exit(main())
