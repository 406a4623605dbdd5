"""ctypes bindings for the native async I/O engine (csrc/strom_engine.cc).

Loads ``libstrom_tpu.so`` (building it via ``make -C csrc`` on first use when
a toolchain is present).  The native engine is the performance path: io_uring
submission/completion entirely outside the GIL, with the same task-table
semantics as the Python fallback in :mod:`nvme_strom_tpu.engine`.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Dict, List, Optional, Sequence, Tuple

from ..api import StromError

__all__ = ["NativeEngine", "native_available"]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "libstrom_tpu.so")
_CSRC = os.path.join(os.path.dirname(os.path.dirname(_HERE)), "csrc")

BACKEND_AUTO, BACKEND_IO_URING, BACKEND_THREADPOOL = 0, 1, 2
BACKEND_NVME_PASSTHRU = 3
_BACKEND_NAMES = {BACKEND_AUTO: "auto",
                  BACKEND_IO_URING: "io_uring",
                  BACKEND_THREADPOOL: "threadpool",
                  BACKEND_NVME_PASSTHRU: "nvme_passthru"}

#: nstpu_passthru_probe() / nstpu_engine_passthru_reason() refusal
#: reasons (negative), keyed by the counter suffix Session uses to count
#: why the ladder fell (NSTPU_PASSTHRU_* in csrc/strom_tpu.h)
PASSTHRU_REASONS = {-1: "disabled", -2: "nodev", -3: "nouring",
                    -4: "nouringcmd", -5: "lbafmt"}

#: NSTPU_API_VERSION — the header contract these bindings mirror.  A
#: loaded .so reporting a different nstpu_engine_version() is a stale
#: build (strom_check diagnoses this at startup; stromlint's abi.drift
#: rule keeps the constant itself honest against csrc/strom_tpu.h).
API_VERSION = 4

# counter order must match enum NSTPU_CTR_* in csrc/strom_tpu.h
NATIVE_COUNTERS = (
    "nr_submit_dma", "clk_submit_dma",
    "nr_ssd2dev", "clk_ssd2dev",
    "nr_wait_dtask", "clk_wait_dtask",
    "nr_wrong_wakeup",
    "total_dma_length",
    "cur_dma_count",
    "max_dma_count",
    "nr_resubmit",
    "nr_sq_full",
    "nr_write_dma",
    "total_write_length",
    "nr_fixed_dma",
    "nr_enter_dma",
    # appended in API v1 (PR 4): queue-occupancy integral.  Older .so
    # builds return fewer entries from nstpu_engine_stats; stats() simply
    # omits the missing tail, so the binding stays compatible both ways.
    "occ_integral_ns",
    "occ_busy_ns",
    # appended in API v4 (PR 19): requests submitted as raw NVMe READ
    # commands over the io_uring passthrough rung
    "nr_passthru_dma",
)

#: log2-ns latency histogram depth — must match kNstpuLatBuckets in
#: csrc/strom_engine.cc and stats.LAT_HIST_BUCKETS
LAT_HIST_BUCKETS = 64

REQ_WRITE = 0x1        # NSTPU_REQ_WRITE
REQ_PASSTHRU = 0x2     # NSTPU_REQ_PASSTHRU: file_off is a DEVICE byte offset
REQ_MEMBER_SHIFT = 8   # NSTPU_REQ_MEMBER_SHIFT
MAX_MEMBERS = 64       # NSTPU_MAX_MEMBERS


class _Req(ctypes.Structure):
    _fields_ = [("fd", ctypes.c_int32), ("flags", ctypes.c_int32),
                ("file_off", ctypes.c_uint64), ("len", ctypes.c_uint64),
                ("dest_off", ctypes.c_uint64)]


class _TraceEvent(ctypes.Structure):
    # must match nstpu_trace_event in csrc/strom_tpu.h (API v3)
    _fields_ = [("submit_ns", ctypes.c_uint64),
                ("complete_ns", ctypes.c_uint64),
                ("file_off", ctypes.c_uint64), ("len", ctypes.c_uint64),
                ("member", ctypes.c_uint32), ("lane", ctypes.c_uint32),
                ("result", ctypes.c_int32), ("seq", ctypes.c_uint32)]


#: drain batch size — matches NSTPU_TRACE_RING_EVENTS so one call can
#: empty a full lane ring
TRACE_RING_EVENTS = 4096


_lib = None
_lib_lock = threading.Lock()
_load_failed = False

#: the files the .so is built from: a source newer than the .so means a
#: stale build, which is rebuilt before loading
_SOURCES = ("strom_engine.cc", "strom_tpu.h", "Makefile")


def _stale() -> bool:
    if not os.path.exists(_SO):
        return True
    built = os.path.getmtime(_SO)
    return any(os.path.getmtime(os.path.join(_CSRC, f)) > built
               for f in _SOURCES if os.path.exists(os.path.join(_CSRC, f)))


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    with _lib_lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            if _stale():
                subprocess.run(["make", "-C", _CSRC], check=True,
                               capture_output=True, timeout=300)
            lib = ctypes.CDLL(_SO)
        except (OSError, subprocess.SubprocessError) as e:
            # no toolchain or no loadable build: I/O runs on the Python
            # pool, and the reason is said once instead of hidden
            from ..log import pr_warn
            pr_warn("native engine unavailable, using the Python I/O "
                    "pool: %s", e)
            _load_failed = True
            return None
        lib.nstpu_engine_create.restype = ctypes.c_uint64
        lib.nstpu_engine_create.argtypes = [ctypes.c_int, ctypes.c_int]
        lib.nstpu_engine_create2.restype = ctypes.c_uint64
        lib.nstpu_engine_create2.argtypes = [ctypes.c_int, ctypes.c_int,
                                             ctypes.c_int]
        lib.nstpu_engine_destroy.argtypes = [ctypes.c_uint64]
        lib.nstpu_engine_backend.argtypes = [ctypes.c_uint64]
        lib.nstpu_submit.restype = ctypes.c_int64
        lib.nstpu_submit.argtypes = [ctypes.c_uint64, ctypes.c_void_p,
                                     ctypes.POINTER(_Req), ctypes.c_int32]
        lib.nstpu_wait.argtypes = [ctypes.c_uint64, ctypes.c_int64, ctypes.c_int64]
        lib.nstpu_pending.argtypes = [ctypes.c_uint64,
                                      ctypes.POINTER(ctypes.c_int64), ctypes.c_int32]
        lib.nstpu_engine_reap.argtypes = [ctypes.c_uint64,
                                          ctypes.POINTER(ctypes.c_int64),
                                          ctypes.c_int32, ctypes.c_int64]
        lib.nstpu_engine_stats.argtypes = [ctypes.c_uint64,
                                           ctypes.POINTER(ctypes.c_uint64),
                                           ctypes.c_int32]
        lib.nstpu_engine_member_stats.argtypes = [
            ctypes.c_uint64, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.nstpu_signature.restype = ctypes.c_char_p
        lib.nstpu_buf_register.argtypes = [ctypes.c_uint64,
                                           ctypes.c_void_p,
                                           ctypes.c_uint64]
        lib.nstpu_buf_unregister.argtypes = [ctypes.c_uint64,
                                             ctypes.c_int32]
        lib.nstpu_engine_lat_hist.argtypes = [
            ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int32]
        lib.nstpu_engine_nlanes.argtypes = [ctypes.c_uint64]
        lib.nstpu_engine_lane_pin.argtypes = [
            ctypes.c_uint64, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32]
        lib.nstpu_engine_member_lat_hist.argtypes = [
            ctypes.c_uint64, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int32]
        lib.nstpu_engine_member_occ.argtypes = [
            ctypes.c_uint64, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.nstpu_engine_trace.argtypes = [ctypes.c_uint64, ctypes.c_int]
        lib.nstpu_engine_trace_drain.argtypes = [
            ctypes.c_uint64, ctypes.POINTER(_TraceEvent), ctypes.c_int32]
        lib.nstpu_engine_create3.restype = ctypes.c_uint64
        lib.nstpu_engine_create3.argtypes = [ctypes.c_int, ctypes.c_int,
                                             ctypes.c_int,
                                             ctypes.c_char_p]
        lib.nstpu_passthru_probe.argtypes = [ctypes.c_char_p]
        lib.nstpu_engine_passthru_reason.argtypes = [ctypes.c_uint64]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


def native_api_version() -> Optional[int]:
    """ABI version the loaded .so reports, or None when unavailable.
    Compared against :data:`API_VERSION` by strom_check's abi probe."""
    lib = _load()
    return None if lib is None else int(lib.nstpu_engine_version())


def passthru_probe(dev_path: Optional[str]) -> Optional[int]:
    """Capability-probe one NVMe char device for the passthrough rung.

    Returns the device's LBA shift (>= 9) when every rung of the probe
    passes, a negative ``NSTPU_PASSTHRU_*`` refusal reason when it does
    not (see :data:`PASSTHRU_REASONS`), or None when the .so is missing."""
    lib = _load()
    if lib is None:
        return None
    dev = dev_path.encode() if dev_path else None
    return int(lib.nstpu_passthru_probe(dev))


def native_signature() -> Optional[str]:
    """Build signature of the loaded .so (the /proc/nvme-strom
    version-read analog), or None when the native engine is unavailable."""
    lib = _load()
    return None if lib is None else lib.nstpu_signature().decode()


class NativeEngine:
    """One native engine instance (the 'loaded kernel module' analog)."""

    def __init__(self, backend: str = "auto", queue_depth: int = 32,
                 rings: int = 0, passthru_dev: Optional[str] = None):
        lib = _load()
        if lib is None:
            raise StromError(38, "native engine unavailable (libstrom_tpu.so)")  # ENOSYS
        want = {"auto": BACKEND_AUTO, "io_uring": BACKEND_IO_URING,
                "threadpool": BACKEND_THREADPOOL,
                "nvme_passthru": BACKEND_NVME_PASSTHRU}[backend]
        self._lib = lib
        if (passthru_dev or want in (BACKEND_AUTO,
                                         BACKEND_NVME_PASSTHRU)):
            self._h = lib.nstpu_engine_create3(
                want, queue_depth, rings,
                passthru_dev.encode() if passthru_dev else None)
        elif rings > 0:
            self._h = lib.nstpu_engine_create2(want, queue_depth, rings)
        else:
            self._h = lib.nstpu_engine_create(want, queue_depth)
        if not self._h:
            raise StromError(5, f"native engine init failed (backend={backend})")
        self.backend_name = _BACKEND_NAMES.get(
            lib.nstpu_engine_backend(self._h), "unknown")
        self._prev_stats: Dict[str, int] = {}
        self._prev_members: Dict[int, Tuple[int, int, int]] = {}
        self._prev_hist: List[int] = [0] * LAT_HIST_BUCKETS
        self._prev_member_hist: Dict[int, List[int]] = {}
        self._prev_member_occ: Dict[int, Tuple[int, int]] = {}
        self._stats_lock = threading.Lock()

    def submit(self, dest_addr: int,
               reqs: Sequence[Tuple[int, int, int, int]], *,
               write: bool = False,
               members: Optional[Sequence[int]] = None,
               passthru: Optional[Sequence[bool]] = None) -> int:
        """Submit one task of (fd, file_off, len, dest_off) requests.

        ``write=True`` reverses direction for the whole task: the buffer
        span at dest_off is WRITTEN to the fd (the GIL-free RAM2SSD leg
        the read-only reference lacked).  ``members[i]`` attributes request
        *i* to a stripe member for per-member accounting.  ``passthru[i]``
        marks request *i* as a raw NVMe READ: its file_off is a DEVICE
        byte offset (blockmap-resolved) and its fd is ignored — only valid
        on the nvme_passthru backend, refused whole-submit otherwise."""
        arr = (_Req * len(reqs))()
        base_flags = REQ_WRITE if write else 0
        for i, (fd, off, ln, doff) in enumerate(reqs):
            arr[i].fd = fd
            m = members[i] if members is not None else 0
            pt = REQ_PASSTHRU if (passthru is not None and passthru[i]) else 0
            arr[i].flags = base_flags | pt | (min(max(m, 0), MAX_MEMBERS - 1)
                                              << REQ_MEMBER_SHIFT)
            arr[i].file_off = off
            arr[i].len = ln
            arr[i].dest_off = doff
        tid = self._lib.nstpu_submit(self._h, ctypes.c_void_p(dest_addr),
                                     arr, len(reqs))
        if tid < 0:
            raise StromError(-tid, f"native submit failed ({-tid})")
        return tid

    def buf_register(self, addr: int, length: int) -> Optional[int]:
        """Register a pinned region as an io_uring fixed buffer (the
        PRP-list-pool analog, kmod/nvme_strom.c:912-936).  Returns the
        slot, or None when unsupported/full — callers just lose the fast
        path, never correctness.  The region must stay mapped until
        :meth:`buf_unregister` (or engine close)."""
        slot = self._lib.nstpu_buf_register(self._h, ctypes.c_void_p(addr),
                                            ctypes.c_uint64(length))
        return slot if slot >= 0 else None

    def buf_unregister(self, slot: int) -> None:
        if self._h:
            self._lib.nstpu_buf_unregister(self._h, slot)

    def passthru_reason(self) -> Optional[int]:
        """Why the passthrough rung is (in)active: 0 when nvme_passthru IS
        the backend, a negative ``NSTPU_PASSTHRU_*`` refusal reason when
        the ladder fell past it."""
        return int(self._lib.nstpu_engine_passthru_reason(self._h))

    def nlanes(self) -> int:
        """Lane (queue-pair) count of this engine."""
        n = self._lib.nstpu_engine_nlanes(self._h)
        return n if n > 0 else 1

    def lane_pin(self, lane: int, cpus: Sequence[int]) -> bool:
        """Pin one lane's reaper/worker threads to the given CPUs (the
        NUMA-locality lever).  Returns True on success; False covers a
        bad lane or a kernel that refuses the affinity — callers lose
        only locality, never correctness."""
        if not cpus:
            return False
        arr = (ctypes.c_int32 * len(cpus))(*cpus)
        return self._lib.nstpu_engine_lane_pin(self._h, lane, arr,
                                               len(cpus)) == 0

    def member_stats(self, member: int) -> Tuple[int, int, int]:
        """(completed requests, bytes, busy ns) for one stripe member."""
        out = (ctypes.c_uint64 * 3)()
        rc = self._lib.nstpu_engine_member_stats(self._h, member, out)
        if rc < 0:
            raise StromError(-rc, f"member_stats({member}) failed")
        return out[0], out[1], out[2]

    def wait(self, task_id: int, timeout_ms: int = -1) -> None:
        rc = self._lib.nstpu_wait(self._h, task_id, timeout_ms)
        if rc < 0:
            raise StromError(-rc, f"native task {task_id} failed ({-rc})")

    def pending(self, cap: int = 4096) -> List[int]:
        out = (ctypes.c_int64 * cap)()
        n = self._lib.nstpu_pending(self._h, out, cap)
        if n < 0:
            raise StromError(-n, "native pending failed")
        return list(out[:min(n, cap)])

    def reap(self, timeout_ms: int = 30000, cap: int = 4096) -> List[int]:
        out = (ctypes.c_int64 * cap)()
        n = self._lib.nstpu_engine_reap(self._h, out, cap, timeout_ms)
        if n < 0:
            raise StromError(-n, "native reap failed")
        return list(out[:min(n, cap)])

    def stats(self) -> Dict[str, int]:
        out = (ctypes.c_uint64 * len(NATIVE_COUNTERS))()
        n = self._lib.nstpu_engine_stats(self._h, out, len(NATIVE_COUNTERS))
        return {NATIVE_COUNTERS[i]: out[i] for i in range(max(n, 0))}

    def stats_delta(self) -> Dict[str, int]:
        """Counters since the previous call (gauges passed through).
        Serialized: concurrent callers must not double-count a delta."""
        with self._stats_lock:
            cur = self.stats()
            prev, self._prev_stats = self._prev_stats, dict(cur)
            out = {}
            for k, v in cur.items():
                if k in ("cur_dma_count", "max_dma_count"):
                    out[k] = v
                else:
                    out[k] = v - prev.get(k, 0)
            return out

    def lat_hist(self) -> Optional[List[int]]:
        """Absolute per-request service-latency histogram (log2-ns
        buckets), or None when the engine refuses the export."""
        out = (ctypes.c_uint64 * LAT_HIST_BUCKETS)()
        n = self._lib.nstpu_engine_lat_hist(self._h, out, LAT_HIST_BUCKETS)
        if n < 0:
            return None
        return list(out[:min(n, LAT_HIST_BUCKETS)])

    def lat_hist_delta(self) -> Optional[List[int]]:
        """Histogram bucket deltas since the previous call (serialized
        like stats_delta so concurrent folders never double-count)."""
        with self._stats_lock:
            cur = self.lat_hist()
            if cur is None:
                return None
            cur += [0] * (LAT_HIST_BUCKETS - len(cur))
            prev, self._prev_hist = self._prev_hist, list(cur)
            return [c - p for c, p in zip(cur, prev)]

    def member_lat_hist(self, member: int) -> Optional[List[int]]:
        """Absolute per-member latency histogram, or None (bad member)."""
        out = (ctypes.c_uint64 * LAT_HIST_BUCKETS)()
        n = self._lib.nstpu_engine_member_lat_hist(self._h, member, out,
                                                   LAT_HIST_BUCKETS)
        if n < 0:
            return None
        return list(out[:min(n, LAT_HIST_BUCKETS)])

    def member_lat_hist_delta(self, members: Sequence[int]
                              ) -> Dict[int, List[int]]:
        """Per-member histogram bucket deltas since the previous call
        (serialized like stats_delta).  Members with no new completions
        are omitted."""
        with self._stats_lock:
            out: Dict[int, List[int]] = {}
            for m in sorted({min(max(m, 0), MAX_MEMBERS - 1)
                             for m in members}):
                cur = self.member_lat_hist(m)
                if cur is None:
                    continue
                cur += [0] * (LAT_HIST_BUCKETS - len(cur))
                prev = self._prev_member_hist.get(m, [0] * LAT_HIST_BUCKETS)
                delta = [c - p for c, p in zip(cur, prev)]
                if any(delta):
                    out[m] = delta
                    self._prev_member_hist[m] = cur
            return out

    def member_occ(self, member: int) -> Optional[Tuple[int, int]]:
        """Monotonic (occ_integral_ns, occ_busy_ns) for one member, or
        None for a bad member."""
        out = (ctypes.c_uint64 * 2)()
        if self._lib.nstpu_engine_member_occ(self._h, member, out) < 0:
            return None
        return out[0], out[1]

    def member_occ_delta(self, members: Sequence[int]
                         ) -> Dict[int, Tuple[int, int]]:
        """Per-member (occ_integral_ns, occ_busy_ns) deltas since the
        previous call (serialized like stats_delta)."""
        with self._stats_lock:
            out: Dict[int, Tuple[int, int]] = {}
            for m in sorted({min(max(m, 0), MAX_MEMBERS - 1)
                             for m in members}):
                cur = self.member_occ(m)
                if cur is None:
                    continue
                prev = self._prev_member_occ.get(m, (0, 0))
                if cur != prev:
                    out[m] = (cur[0] - prev[0], cur[1] - prev[1])
                    self._prev_member_occ[m] = cur
            return out

    def trace_enable(self, on: bool = True) -> bool:
        """Turn the native flight-recorder ring on/off.  Returns the
        PREVIOUS state."""
        return self._lib.nstpu_engine_trace(self._h, 1 if on else 0) > 0

    def trace_drain(self, cap: int = TRACE_RING_EVENTS) -> List[Dict[str, int]]:
        """Drain recorded device events (oldest first per lane).  Each dict carries the measured submit->complete window
        in CLOCK_MONOTONIC ns — the same domain as time.monotonic_ns()."""
        if not self._h:
            return []
        out = (_TraceEvent * cap)()
        n = self._lib.nstpu_engine_trace_drain(self._h, out, cap)
        if n <= 0:
            return []
        return [{"submit_ns": e.submit_ns, "complete_ns": e.complete_ns,
                 "file_off": e.file_off, "len": e.len, "member": e.member,
                 "lane": e.lane, "result": e.result, "seq": e.seq}
                for e in out[:min(n, cap)]]

    def member_stats_delta(self, members: Sequence[int]) -> Dict[int, Tuple[int, int, int]]:
        """Per-member (nreq, bytes, ns) deltas since the previous call,
        for the given member indices.  Serialized like stats_delta.
        Indices clamp to the engine's member table the same way submit()
        clamps them, so callers may pass raw source indices."""
        with self._stats_lock:
            out: Dict[int, Tuple[int, int, int]] = {}
            for m in sorted({min(max(m, 0), MAX_MEMBERS - 1)
                             for m in members}):
                cur = self.member_stats(m)
                prev = self._prev_members.get(m, (0, 0, 0))
                if cur != prev:
                    out[m] = tuple(c - p for c, p in zip(cur, prev))
                    self._prev_members[m] = cur
            return out

    def close(self) -> None:
        # swap the handle out under the lock so two racing closers (user
        # close vs __del__ on another thread) cannot double-destroy
        with self._stats_lock:
            h, self._h = self._h, 0
        if h:
            self._lib.nstpu_engine_destroy(h)

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass
