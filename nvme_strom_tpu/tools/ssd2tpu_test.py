"""ssd2tpu_test — SSD→TPU-HBM throughput benchmark (the north-star path).

Capability mirror of the reference's `utils/ssd2gpu_test.c`: a device
destination buffer registered once, segment-pipelined transfers, optional
byte-exact corruption check against the VFS (`-c`, `:342-372` with the
`memdump_on_corruption` hexdump, `:169-225`), a conventional-path baseline
mode (`-f`, pread + host→device copy, `:377-429`), and a mapped-region dump
(`-p`, `:432-513`).  Reports GB/s and average DMA request size.

Usage: ssd2tpu_test [-c] [-f [IOSIZE]] [-p] [-n SEGS] [-s SEG_SZ] [-d DEV]
                    FILE [FILE ...]        (several FILEs = RAID-0 stripe set)
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from ..config import config
from ..engine import Session, check_file, open_source
from ..stats import stats
from .common import drop_page_cache, parse_size


def _measure_raw(paths, nbytes: int) -> float:
    """Sequential O_DIRECT pread over the run's files, no framework."""
    import mmap
    import os
    blk = 4 << 20
    buf = mmap.mmap(-1, blk)
    total = 0
    t0 = time.monotonic()
    for p in paths:
        try:
            fd = os.open(p, os.O_RDONLY | os.O_DIRECT)
        except OSError:
            fd = os.open(p, os.O_RDONLY)
        try:
            want = min(os.fstat(fd).st_size, nbytes - total)
            off = 0
            while off < want:
                n = os.preadv(fd, [buf], off)
                if n <= 0:
                    break
                off += n
            total += off
        finally:
            os.close(fd)
        if total >= nbytes:
            break
    dt = time.monotonic() - t0
    buf.close()
    return total / dt / (1 << 30) if dt > 0 else 0.0


def _measure_h2d(dev, nbytes: int) -> float:
    """Pinned host->HBM device_put burst ceiling."""
    import jax
    a = np.random.randint(0, 255, nbytes, dtype=np.uint8)
    jax.device_put(a[:1 << 20], dev).block_until_ready()  # warm
    t0 = time.monotonic()
    step = 16 << 20
    for off in range(0, nbytes, step):
        jax.device_put(a[off:off + step], dev).block_until_ready()
    dt = time.monotonic() - t0
    return nbytes / dt / (1 << 30) if dt > 0 else 0.0


def memdump_on_corruption(got: np.ndarray, want: bytes, base: int) -> None:
    """Unified-diff-style hexdump around the first corrupt byte
    (reference memdump_on_corruption, utils/ssd2gpu_test.c:169-225)."""
    wa = np.frombuffer(want, dtype=np.uint8)
    bad = np.nonzero(got != wa)[0]
    first = int(bad[0])
    lo = max(first - 32, 0) & ~15
    hi = min(first + 48, len(wa))
    print(f"corruption at file offset {base + first:#x} "
          f"({len(bad)} bad bytes in this block)", file=sys.stderr)
    for row in range(lo, hi, 16):
        g = got[row:row + 16].tobytes()
        w = wa[row:row + 16].tobytes()
        mark = "!" if g != w else " "
        print(f"{mark} {base + row:#010x}  dma: {g.hex(' ')}", file=sys.stderr)
        if g != w:
            print(f"              vfs: {w.hex(' ')}", file=sys.stderr)


def _pick_device(index):
    from ..hbm.staging import default_device
    return default_device(index)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="ssd2tpu_test", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("file", nargs="+",
                    help="source file; several files form a RAID-0-style "
                         "striped set (see --stripe-chunk)")
    ap.add_argument("--stripe-chunk", type=parse_size, default=512 << 10,
                    help="stripe chunk size for multi-file sources "
                         "(default 512KB, the md-raid0 shape)")
    ap.add_argument("-d", "--device", type=int, default=0)
    ap.add_argument("-n", "--segments", type=int, default=6,
                    help="pipeline depth (reference default: 6 worker segments)")
    ap.add_argument("-s", "--segment-size", type=parse_size, default=16 << 20,
                    help="staging segment size (default 16MB; this host's "
                         "H2D path degrades sharply above ~16MB)")
    ap.add_argument("--chunk", type=parse_size, default=1 << 20)
    ap.add_argument("-c", "--check", action="store_true",
                    help="verify every byte against a VFS read")
    ap.add_argument("-f", "--vfs", nargs="?", const=1 << 20, type=parse_size,
                    default=None, metavar="IOSIZE",
                    help="conventional-path baseline (pread + device_put)")
    ap.add_argument("-p", "--print-memory", action="store_true",
                    help="dump registered device buffers")
    ap.add_argument("--backend", default=None)
    ap.add_argument("--daemon", metavar="SOCK", default=None,
                    help="route the SSD leg through a shared stromd at "
                         "SOCK (DMA lands in shared memory, the H2D hop "
                         "stays client-side)")
    ap.add_argument("--tenant", default=None,
                    help="tenant name for --daemon mode")
    ap.add_argument("--no-drop-cache", action="store_true")
    ap.add_argument("--loops", type=int, default=1,
                    help="repeat the transfer; per-loop GB/s is printed and "
                         "the best loop reported (loop 1 pays jit compile)")
    ap.add_argument("--efficiency", action="store_true",
                    help="also measure the raw O_DIRECT read bandwidth of "
                         "this file and the host->device ceiling, then "
                         "report pct_of_raw and overlap_efficiency = "
                         "achieved / min(raw, h2d)")
    args = ap.parse_args(argv)
    if args.loops < 1:
        ap.error("--loops must be >= 1")

    from .common import tool_startup
    tool_startup()
    import jax
    import jax.numpy as jnp
    from ..hbm import StagingPipeline, registry

    paths = args.file
    striped = len(paths) > 1
    infos = [check_file(p) for p in paths]
    for p, i in zip(paths, infos):
        if not i.supported:
            print(f"{p}: not supported for direct load", file=sys.stderr)
            return 1
    info = infos[0]
    # O_DIRECT alignment must honor the largest member block size, exactly
    # as the single-file path does via check_file
    block = max(i.logical_block_size for i in infos)

    def _open():
        if striped:
            return open_source(paths, stripe_chunk_size=args.stripe_chunk,
                               block_size=block)
        return open_source(paths[0], block_size=block)

    def _drop():
        if not args.no_drop_cache:
            for p in paths:
                drop_page_cache(p)

    with _open() as sized:
        total_size = sized.size
    dev = _pick_device(args.device)
    label = paths[0] if not striped else \
        f"{len(paths)}-way stripe ({args.stripe_chunk >> 10}KB chunks)"
    print(f"file: {label} ({total_size / (1 << 20):.1f} MB)  "
          f"device: {dev}  numa: {info.numa_node_id}")
    # the device the numbers below belong to, in one parseable line
    print(f"platform: {dev.platform} kind: {dev.device_kind} "
          f"count: {len(jax.devices())}")
    if args.backend:
        config.set("io_backend", args.backend)
    _drop()

    chunk = args.chunk
    n_chunks = total_size // chunk
    if n_chunks == 0:
        print("file smaller than one chunk", file=sys.stderr)
        return 1
    nbytes = n_chunks * chunk

    stats.start_export()
    best = None
    t0 = time.monotonic()
    if args.vfs is not None:
        # conventional path: buffered pread -> device_put -> land into the
        # same preallocated registered destination the direct path uses, so
        # the comparison isolates the read path (utils/ssd2gpu_test.c:377-429)
        from ..hbm.staging import _land
        handle = registry.map_device_memory(nbytes, device=dev)
        registry.get(handle).array.block_until_ready()
        hbm = registry.acquire(handle)
        try:
            # warmup: compile the landing kernels + first-touch the H2D path
            # with the run's real shapes, outside the timed region
            warm = jax.device_put(np.zeros(min(args.vfs, nbytes), np.uint8), dev)
            _land(hbm, warm, 0)
            registry.get(handle).array.block_until_ready()
            for loop in range(args.loops):
                _drop()
                tl = time.monotonic()
                with _open() as src:
                    off = 0
                    while off < nbytes:
                        n = min(args.vfs, nbytes - off)
                        # fresh buffer per piece: device_put is async and
                        # must never read a buffer we are about to refill
                        data = bytearray(n)
                        src.read_buffered(off, memoryview(data))
                        part = jax.device_put(
                            np.frombuffer(data, dtype=np.uint8), dev)
                        _land(hbm, part, off)
                        off += n
                registry.get(handle).array.block_until_ready()
                dt = time.monotonic() - tl
                if args.loops > 1:
                    print(f"  loop {loop + 1}: "
                          f"{nbytes / dt / (1 << 30):.2f} GB/s")
                best = dt if best is None else min(best, dt)
        finally:
            registry.release(hbm)
        arr = registry.get(handle).array
        arr.block_until_ready()
        mode = f"vfs baseline (iosize {args.vfs >> 10}KB)"
    elif args.daemon:
        # shared-daemon path: stromd QoS-schedules each segment's DMA into
        # a memfd both processes map, then this client lands the bytes in
        # HBM — SSD arbitration is the daemon's, the H2D hop ours
        from types import SimpleNamespace
        from ..daemon import DaemonSession
        from ..hbm.staging import _land
        seg = args.segment_size
        per_seg = max(seg // chunk, 1)
        n_segs = (n_chunks + per_seg - 1) // per_seg
        handle = registry.map_device_memory(nbytes, device=dev)
        hbm = registry.acquire(handle)
        order: list = []
        wbc = [0]
        try:
            with DaemonSession(args.daemon, tenant=args.tenant) as dsess:
                spec = paths if striped else paths[0]
                dsrc = dsess.open_source(
                    spec, stripe_chunk_size=args.stripe_chunk
                    if striped else None)
                depth = max(1, min(args.segments, 4))
                dbufs = [dsess.alloc_dma_buffer(seg) for _ in range(depth)]
                inflight: list = []   # (task_id, ring_idx, dest_off, nbytes)

                def retire():
                    tid, ridx, off, nb = inflight.pop(0)
                    r = dsess.memcpy_wait(tid)
                    order.extend(r.chunk_ids)
                    wbc[0] += r.nr_ram2dev
                    # copy out before the ring slot is reused: device_put
                    # is async and must never watch a refilling buffer
                    host = np.frombuffer(
                        dbufs[ridx][1].view()[:nb], dtype=np.uint8).copy()
                    _land(hbm, jax.device_put(host, dev), off)

                # warmup compiles the landing kernels with the run's shapes
                warm = jax.device_put(np.zeros(min(seg, nbytes), np.uint8),
                                      dev)
                _land(hbm, warm, 0)
                registry.get(handle).array.block_until_ready()
                for loop in range(args.loops):
                    _drop()
                    order.clear()
                    wbc[0] = 0
                    tl = time.monotonic()
                    for s in range(n_segs):
                        if len(inflight) >= depth:
                            retire()
                        ids = list(range(s * per_seg,
                                         min((s + 1) * per_seg, n_chunks)))
                        ridx = s % depth
                        r = dsess.memcpy_ssd2ram(dsrc, dbufs[ridx][0], ids,
                                                 chunk)
                        inflight.append((r.dma_task_id, ridx,
                                         s * per_seg * chunk,
                                         len(ids) * chunk))
                    while inflight:
                        retire()
                    registry.get(handle).array.block_until_ready()
                    dt = time.monotonic() - tl
                    if args.loops > 1:
                        print(f"  loop {loop + 1}: "
                              f"{nbytes / dt / (1 << 30):.2f} GB/s")
                    best = dt if best is None else min(best, dt)
                snap = dsess.stat_info(debug=True)
                dsrc.close()
        finally:
            registry.release(hbm)
        arr = registry.get(handle).array
        arr.block_until_ready()
        res = SimpleNamespace(chunk_ids=order, nr_ram2dev=wbc[0],
                              nr_chunks=n_chunks)
        mode = (f"daemon ({args.daemon}, {args.segments} x "
                f"{seg >> 20}MB segments)")
    else:
        with _open() as src, Session() as sess:
            handle = registry.map_device_memory(nbytes, device=dev)
            with StagingPipeline(sess, n_buffers=args.segments,
                                 staging_bytes=args.segment_size) as pipe:
                # warmup: one full staged batch compiles the landing kernels
                # and first-touches the H2D path with the run's real shapes,
                # outside the timed region
                per_batch = args.segment_size // chunk
                warm_chunks = min(per_batch, n_chunks)
                pipe.memcpy_ssd2dev(src, handle, list(range(warm_chunks)), chunk)
                rem = n_chunks % per_batch
                if rem and rem != warm_chunks:
                    # the run's final partial batch lands with its own shape
                    pipe.memcpy_ssd2dev(src, handle, list(range(rem)), chunk)
                registry.get(handle).array.block_until_ready()
                _drop()
                for loop in range(args.loops):
                    if loop:
                        _drop()
                    tl = time.monotonic()
                    res = pipe.memcpy_ssd2dev(src, handle,
                                              list(range(n_chunks)), chunk)
                    registry.get(handle).array.block_until_ready()
                    dt = time.monotonic() - tl
                    if args.loops > 1:
                        print(f"  loop {loop + 1}: "
                              f"{nbytes / dt / (1 << 30):.2f} GB/s")
                    best = dt if best is None else min(best, dt)
            arr = registry.get(handle).array
            arr.block_until_ready()
            mode = (f"direct ({sess.backend_name}, {args.segments} x "
                    f"{args.segment_size >> 20}MB segments)")
            snap = sess.stat_info(debug=True)
    elapsed = best if best is not None else time.monotonic() - t0

    if args.vfs is not None:
        snap = stats.snapshot(debug=True)
    c = snap.counters
    nsub = max(c.get("nr_submit_dma", 0), 1)
    print(f"mode: {mode}")
    print(f"transferred: {nbytes / (1 << 30):.2f} GB in {elapsed:.2f}s  "
          f"=> {nbytes / elapsed / (1 << 30):.2f} GB/s")
    if args.vfs is None:
        print(f"avg dma size: {c.get('total_dma_length', 0) / nsub / 1024:.0f}KB  "
              f"requests: {c.get('nr_submit_dma', 0)}  "
              f"wb chunks: {res.nr_ram2dev}/{res.nr_chunks}")

    if args.efficiency:
        # denominators measured in-run on the same file/device (VERDICT r1
        # #2): raw = fio-style sequential O_DIRECT pread, h2d = pinned
        # host->HBM device_put burst.  overlap_efficiency isolates pipeline
        # quality: 1.0 means the slower leg fully hides the other.
        achieved = nbytes / elapsed / (1 << 30)
        _drop()
        raw_bw = _measure_raw(paths, nbytes)
        h2d_bw = _measure_h2d(dev, min(nbytes, 64 << 20))
        print(f"raw O_DIRECT read: {raw_bw:.2f} GB/s   "
              f"h2d ceiling: {h2d_bw:.2f} GB/s")
        if raw_bw:
            print(f"pct_of_raw: {achieved / raw_bw:.1%}")
        ceiling = min(raw_bw, h2d_bw)
        if ceiling:
            print(f"overlap_efficiency: {achieved / ceiling:.1%} "
                  f"(achieved / min(raw, h2d))")

    rc = 0
    if args.check:
        host = np.asarray(arr)
        wantbuf = bytearray(nbytes)
        with _open() as src:
            src.read_buffered(0, memoryview(wantbuf))
        want = bytes(wantbuf)
        if args.vfs is None:
            # undo the chunk reordering: slot i holds chunk res.chunk_ids[i]
            order = res.chunk_ids
        else:
            order = list(range(n_chunks))
        bad_blocks = 0
        for slot, cid in enumerate(order):
            got = host[slot * chunk:(slot + 1) * chunk]
            exp = want[cid * chunk:(cid + 1) * chunk]
            if got.tobytes() != exp:
                if bad_blocks == 0:
                    memdump_on_corruption(got, exp, cid * chunk)
                bad_blocks += 1
        if bad_blocks:
            print(f"CORRUPTION: {bad_blocks}/{n_chunks} blocks differ",
                  file=sys.stderr)
            rc = 1
        else:
            print(f"corruption check: all {n_chunks} blocks OK")

    if args.print_memory:
        # LIST/INFO dump (utils/ssd2gpu_test.c:432-513)
        for h in registry.list():
            i = registry.info(h)
            print(f"  handle {i.handle}: {i.length} bytes on {i.device}  "
                  f"pages {i.n_pages} x {i.page_size}  refs {i.refcount}  "
                  f"uid {i.owner_uid}")
    registry.unmap(handle)
    stats.stop_export()
    return rc


def cli() -> int:
    from ..api import StromError
    try:
        return main()
    except (StromError, OSError) as e:
        print(f"{e.__class__.__name__.lower().replace('stromerror', 'error')}: "
              f"{e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(cli())
