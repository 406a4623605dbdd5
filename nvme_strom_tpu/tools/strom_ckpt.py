"""strom_ckpt — inspect / verify / benchmark strom checkpoint files.

The checkpoint tier's CLI face, in the mold of the reference's utilities
(observability + built-in oracles, SURVEY.md SS4): ``info`` dumps the leaf
table, ``verify`` restores and compares bytes against a buffered read
(the ``-c`` corruption-oracle pattern of `utils/ssd2gpu_test.c:342-372`),
``bench`` times a direct-to-device restore.

Usage:
  strom_ckpt info FILE
  strom_ckpt verify FILE
  strom_ckpt bench FILE [--loops N]
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from ..data.checkpoint import checkpoint_info, restore_checkpoint
from .common import drop_page_cache


def _info(path: str) -> int:
    meta = checkpoint_info(path)
    total = 0
    print(f"{path}: strom checkpoint v{meta['version']}, "
          f"{len(meta['leaves'])} leaves, data at {meta['data_offset']:#x}")
    for e in meta["leaves"]:
        shape = "x".join(map(str, e["shape"])) or "scalar"
        print(f"  {e['key']:<40} {e['dtype']:<6} {shape:<16} "
              f"{e['nbytes']:>12} B @ {meta['data_offset'] + e['offset']:#x}")
        total += e["nbytes"]
    print(f"  total tensor bytes: {total}")
    return 0


def _verify(path: str) -> int:
    from ..scan.heap import crc32c
    meta = checkpoint_info(path)
    out = restore_checkpoint(path)
    bad = 0
    with_crc = 0
    with open(path, "rb") as f:
        for e in meta["leaves"]:
            f.seek(meta["data_offset"] + e["offset"])
            raw = f.read(e["nbytes"])
            want = np.frombuffer(raw, np.dtype(e["dtype"]))
            got = np.asarray(out[e["key"]]).ravel().view(np.dtype(e["dtype"]))
            if not np.array_equal(
                    got.view(np.uint8), want.view(np.uint8)):
                print(f"  CORRUPT: {e['key']} (direct != buffered)",
                      file=sys.stderr)
                bad += 1
                continue
            # crash-consistency oracle (ISSUE 11): the header's per-leaf
            # crc32c pins the bytes the SAVER intended — a torn write
            # that both read paths agree on still fails here
            if "crc32c" in e:
                with_crc += 1
                if crc32c(raw) != e["crc32c"]:
                    print(f"  CORRUPT: {e['key']} (crc32c mismatch, "
                          f"header {e['crc32c']:#010x})", file=sys.stderr)
                    bad += 1
    if bad:
        print(f"verify: {bad}/{len(meta['leaves'])} leaves corrupt",
              file=sys.stderr)
        return 1
    crc_note = f", {with_crc} crc32c-checked" if with_crc else ""
    print(f"verify: all {len(meta['leaves'])} leaves OK "
          f"(direct restore == buffered read{crc_note})")
    return 0


def _bench(path: str, loops: int) -> int:
    import jax
    meta = checkpoint_info(path)
    nbytes = sum(e["nbytes"] for e in meta["leaves"])
    # first-touch the device path outside the timed region
    jax.device_put(np.zeros(1 << 20, np.uint8)).block_until_ready()
    best = None
    for loop in range(loops):
        drop_page_cache(path)
        t0 = time.monotonic()
        out = restore_checkpoint(path)
        jax.block_until_ready(list(out.values()))
        dt = time.monotonic() - t0
        if loops > 1:
            print(f"  loop {loop + 1}: {nbytes / dt / (1 << 30):.2f} GB/s")
        best = dt if best is None else min(best, dt)
    print(f"restored {len(meta['leaves'])} leaves, "
          f"{nbytes / (1 << 20):.1f} MB in {best:.2f}s  "
          f"=> {nbytes / best / (1 << 30):.2f} GB/s")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="strom_ckpt", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name in ("info", "verify", "bench"):
        p = sub.add_parser(name)
        p.add_argument("file")
        if name == "bench":
            p.add_argument("--loops", type=int, default=1)
    args = ap.parse_args(argv)
    from .common import tool_startup
    tool_startup()
    if args.cmd == "info":
        return _info(args.file)
    if args.cmd == "verify":
        return _verify(args.file)
    return _bench(args.file, max(args.loops, 1))


def cli() -> int:
    from ..api import StromError
    try:
        return main()
    except (StromError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(cli())
