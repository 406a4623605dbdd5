"""strom_check — environment doctor for the direct-load stack.

Capability analog of the reference's ops tooling: where
`utils/rhel7-kernel-check.sh` diffs vendored kernel headers against the
running kernel and the `/proc/nvme-strom` read exposes the module's build
signature (`kmod/nvme_strom.c:2111-2136`), this tool probes every runtime
capability the TPU framework depends on and reports drift with fix advice
(the sysctl/limits provisioning in `deploy/` mirrors
`kmod/sysctl-nvmestrom.conf` and `kmod/limits-nvmestrom.conf`).

Checks: kernel + io_uring availability, O_DIRECT on a target path, hugepage
provisioning, memlock limits, NUMA topology, JAX backend/devices, native
engine build signature.

Usage: strom_check [-v] [--path DIR] [--jax]
Exit code: 0 all required checks pass, 1 otherwise.
"""

from __future__ import annotations

import argparse
import os
import platform
import resource
import sys
import tempfile

OK, WARN, FAIL = "ok", "warn", "FAIL"


def _report(name: str, status: str, detail: str, advice: str = "") -> bool:
    mark = {OK: " ok ", WARN: "warn", FAIL: "FAIL"}[status]
    print(f"[{mark}] {name:<22} {detail}")
    if advice and status != OK:
        print(f"       -> {advice}")
    return status != FAIL


def check_kernel() -> bool:
    rel = platform.release()
    try:
        major, minor = (int(x) for x in rel.split(".")[:2])
        has_uring = (major, minor) >= (5, 1)
    except ValueError:
        has_uring = False
    return _report("kernel", OK if has_uring else WARN, rel,
                   "io_uring needs Linux >= 5.1; the threadpool backend "
                   "will be used instead")


def check_io_uring() -> bool:
    from .. import _native
    if not _native.native_available():
        return _report("native engine", FAIL, "libstrom_tpu.so not loadable",
                       "build it: make -C csrc (needs g++)")
    try:
        eng = _native.NativeEngine("io_uring", 8)
    except Exception as e:
        return _report("io_uring", WARN, f"unavailable ({e})",
                       "check /proc/sys/kernel/io_uring_disabled; the "
                       "threadpool backend will be used instead")
    # io_uring itself is proven at this point: a probe-only failure must
    # degrade to "no fixed buffers", never misreport io_uring as absent
    probe = None
    try:
        import ctypes
        import mmap
        probe = mmap.mmap(-1, 4096)
        addr = ctypes.addressof(ctypes.c_char.from_buffer(probe))
        slot = eng.buf_register(addr, 4096)
        if slot is not None:
            eng.buf_unregister(slot)
            fixed = "registered (fixed) buffers supported"
        else:
            fixed = "no fixed-buffer support (pre-5.13 kernel?): " \
                    "requests use plain opcodes"
    except Exception as e:
        fixed = f"fixed-buffer probe failed ({e}): plain opcodes"
    finally:
        eng.close()
        if probe is not None:
            try:
                probe.close()
            except BufferError:
                pass   # from_buffer export still alive; dropped with it
    return _report("io_uring", OK, f"available; {fixed}")


def check_odirect(path: str) -> bool:
    try:
        fd, tmp = tempfile.mkstemp(dir=path)
        os.write(fd, b"\0" * 4096)
        os.close(fd)
        try:
            d = os.open(tmp, os.O_RDONLY | os.O_DIRECT)
            os.close(d)
            return _report("O_DIRECT", OK, path)
        finally:
            os.unlink(tmp)
    except OSError as e:
        return _report("O_DIRECT", FAIL, f"{path}: {e}",
                       "direct loads need an O_DIRECT-capable filesystem "
                       "(ext4/xfs; tmpfs does not qualify)")


def check_hugepages() -> bool:
    total = free = size_kb = 0
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("HugePages_Total"):
                    total = int(line.split()[1])
                elif line.startswith("HugePages_Free"):
                    free = int(line.split()[1])
                elif line.startswith("Hugepagesize"):
                    size_kb = int(line.split()[1])
    except OSError:
        pass
    if total:
        return _report("hugepages", OK,
                       f"{free}/{total} free x {size_kb >> 10}MB")
    return _report("hugepages", WARN, "none provisioned",
                   "sysctl vm.nr_hugepages=2048 (see deploy/sysctl-strom-"
                   "tpu.conf); pinned buffers fall back to 4KB pages")


def check_memlock() -> bool:
    soft, hard = resource.getrlimit(resource.RLIMIT_MEMLOCK)
    inf = resource.RLIM_INFINITY

    def fmt(v):
        return "unlimited" if v == inf else f"{v >> 20}MB"
    need = 4 << 30
    status = OK if (soft == inf or soft >= need) else WARN
    return _report("memlock rlimit", status, f"soft {fmt(soft)} hard {fmt(hard)}",
                   "raise to >= 4GB (see deploy/limits-strom-tpu.conf); "
                   "mlock of staging buffers will silently degrade")


def check_numa() -> bool:
    from ..numa import nodes_with_memory
    nodes = nodes_with_memory()
    return _report("numa", OK, f"nodes with memory: {nodes}")


def check_native_signature() -> bool:
    from .. import __version__, _native
    sig = _native.native_signature()
    if sig is None:
        return _report("signature", WARN, f"python {__version__}, no native .so",
                       "make -C csrc")
    return _report("signature", OK, f"python {__version__}; {sig}")


def check_abi() -> bool:
    """Native ABI drift — stromlint's ``abi.drift`` rule at startup
    (satellite of the stromlint PR): cross-check the ctypes bindings
    against ``csrc/strom_tpu.h`` and the loaded .so's reported API
    version, so a stale build is diagnosed HERE instead of surfacing as
    a corrupted submit at first I/O."""
    from .. import _native
    from ..analysis.abi import check_bindings_source, parse_header
    from ..analysis.core import SourceFile
    hdr_path = os.path.join(_native._CSRC, "strom_tpu.h")
    if not os.path.exists(hdr_path):
        return _report("native abi", WARN,
                       "csrc/strom_tpu.h not present (installed without "
                       "sources): drift check skipped")
    with open(hdr_path, "r", encoding="utf-8") as fh:
        abi = parse_header(fh.read())
    with open(_native.__file__, "r", encoding="utf-8") as fh:
        src = SourceFile("nvme_strom_tpu/_native/__init__.py", fh.read())
    findings = check_bindings_source(src, abi)
    if findings:
        for f in findings[:5]:
            print(f"       {f.path}:{f.line} {f.message}")
        return _report("native abi", FAIL,
                       f"{len(findings)} ctypes/header drift(s)",
                       "bindings no longer match csrc/strom_tpu.h — run "
                       "strom_lint --rule abi and fix before trusting I/O")
    want = abi.defines.get("NSTPU_API_VERSION")
    got = _native.native_api_version()
    if got is not None and want is not None and got != want:
        return _report("native abi", FAIL,
                       f"loaded .so reports api v{got}, header is "
                       f"v{want}: stale build",
                       "rebuild it: make -C csrc")
    so = f", .so api v{got}" if got is not None else ", no .so loaded"
    return _report("native abi", OK,
                   f"bindings match strom_tpu.h (api v{want}){so}")


def check_jax(timeout_s: float = 45.0) -> bool:
    """Device probe in a KILLABLE subprocess: a hung accelerator driver
    blocks backend init indefinitely, and the doctor must diagnose that
    state, not inherit it.  The child sees the same ``JAX_PLATFORMS`` as
    every other entry point."""
    import subprocess
    import sys
    code = ("import jax\n"
            "d = jax.devices()\n"
            "print('PROBE', jax.__version__, len(d),"
            " sorted({x.platform for x in d}))\n")
    # Popen + bounded communicate, NOT subprocess.run: run()'s timeout
    # handler kills then WAITS UNBOUNDED for the reap — a child wedged in
    # uninterruptible (D-state) driver sleep never reaps, and the doctor
    # would inherit the very hang it is diagnosing
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        try:
            proc.wait(timeout=2.0)
        except subprocess.TimeoutExpired:
            pass   # D-state child: report without reaping
        return _report("jax", FAIL,
                       f"accelerator backend unresponsive (device query "
                       f"hung > {timeout_s:.0f}s)",
                       "accelerator driver hung: check the device and "
                       "its runtime; CPU-path tools keep working with "
                       "JAX_PLATFORMS=cpu")
    for line in stdout.splitlines():
        if line.startswith("PROBE "):
            _, ver, n, kinds = line.split(" ", 3)
            status = OK if "cpu" != kinds.strip("[]'\"") else WARN
            return _report("jax", status, f"{ver}, {n} device(s) {kinds}",
                           "no accelerator visible; HBM loads will "
                           "target CPU buffers")
    return _report("jax", FAIL,
                   f"device probe failed: {stderr.strip()[-200:]}")


def check_backend_latch() -> bool:
    """In-process backend-loss latch (VERDICT r3 #5): reports whether
    this process has declared the device backend LOST (bounded fence
    timeout / PJRT error) and revoked its registered HBM buffers — the
    state every subsequent staging call fails fast from (ENODEV)."""
    from ..hbm.backend import monitor
    from ..hbm.registry import registry
    why = monitor.lost()
    if why is None:
        return _report("backend", OK,
                       f"no loss latched; {len(registry.list())} HBM "
                       f"buffer(s) registered")
    return _report("backend", FAIL,
                   f"LOST: {why}",
                   "device fences now fail with ENODEV; re-register "
                   "destinations after transport recovery (the latch "
                   "clears via BackendMonitor.reset / a new process)")


def check_backing(path: str) -> bool:
    """Backing-device eligibility (kmod/nvme_strom.c:229-438 analog):
    reports whether *path* sits on raw NVMe / md-RAID0-of-NVMe, with the
    classifier's reason when not — informational unless config
    ``require_nvme_backing`` is on, in which case drift here disables the
    direct path outright."""
    from ..config import config
    from ..eligibility import probe_backing
    b = probe_backing(path)
    strict = config.get("require_nvme_backing")
    detail = f"kind={b.kind or '?'} name={b.name or '?'}"
    if b.supported:
        extra = (f" members={','.join(b.members)}" if b.members else "")
        return _report("backing", OK,
                       f"{detail}{extra} numa={b.numa_node_id} "
                       f"dma64={b.support_dma64} "
                       f"dma_max={b.dma_max_size or 'n/a'}")
    status = FAIL if strict else WARN
    return _report("backing", status, f"{detail}: {b.reason}",
                   advice="direct-load perf model assumes NVMe; set "
                          "require_nvme_backing=off (default) to run "
                          "anyway on this backing" if strict else
                          "numbers on this backing are not NVMe-class; "
                          "set require_nvme_backing=on to hard-gate")


def check_blockmap(path: str) -> bool:
    """Passthrough readiness (PR 19): the two ingredients of the raw
    NVMe rung — a capability-probed char device, and FIEMAP file->LBA
    maps on *path* with their fragmentation (extents/GB) and the share
    of bytes raw-command eligible.  Informational: a host missing either
    simply rides the io_uring/threadpool rungs, with the refusal reason
    counted at engine create."""
    from .. import blockmap
    from .._native import PASSTHRU_REASONS, passthru_probe
    from ..engine import _resolve_passthru_dev
    dev = _resolve_passthru_dev()
    probe = passthru_probe(dev) if dev else None
    if dev is None:
        devmsg = "no char device (passthru_dev_glob)"
    elif probe is None:
        devmsg = f"{dev}: native lib predates passthru"
    elif probe >= 9:
        devmsg = f"{dev}: lba=2^{probe}"
    else:
        devmsg = f"{dev}: refused ({PASSTHRU_REASONS.get(probe, probe)})"
    frag = None
    try:
        fd, tmp = tempfile.mkstemp(dir=path)
        try:
            os.write(fd, b"\0" * (1 << 20))
            os.fsync(fd)
            os.close(fd)
            frag = blockmap.fragmentation(tmp)
        finally:
            os.unlink(tmp)
    except OSError:
        pass
    if frag is None:
        return _report("blockmap", WARN, f"FIEMAP unsupported on {path}; "
                       f"{devmsg}",
                       "passthrough needs file->LBA maps; extents here "
                       "ride O_DIRECT (note: some filesystems lie in "
                       "FIEMAP — see deploy checklist item 23)")
    next_, total, eligible = frag
    per_gb = next_ / max(total / 2**30, 1e-9)
    pct = 100.0 * eligible / total if total else 0.0
    return _report("blockmap", OK,
                   f"FIEMAP ok on {path}: {next_} extent(s) "
                   f"({per_gb:.0f}/GB), {pct:.0f}% bytes eligible; "
                   f"{devmsg}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="strom_check", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--path", default=".",
                    help="directory to probe for O_DIRECT (default: cwd)")
    ap.add_argument("--jax", action="store_true",
                    help="also probe the JAX backend (initializes a device)")
    args = ap.parse_args(argv)

    ok = True
    for fn in (check_kernel, check_io_uring,
               lambda: check_odirect(args.path),
               lambda: check_backing(args.path),
               lambda: check_blockmap(args.path),
               check_hugepages, check_memlock, check_numa,
               check_native_signature, check_abi, check_backend_latch):
        ok = fn() and ok
    if args.jax:
        ok = check_jax() and ok
    print("all required checks passed" if ok else "REQUIRED CHECKS FAILED",
          file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
