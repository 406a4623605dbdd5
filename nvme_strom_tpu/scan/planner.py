"""Scan path planning: eligibility cache, size threshold, cost model.

Capability analog of the pgsql extension's planner integration
(`pgsql/nvme_strom.c:217-633`):

* **capability cache** — per-directory CHECK_FILE *capability* probes
  (can this filesystem do direct load, which NUMA node, DMA64) cached with
  a TTL and an explicit ``invalidate()`` (the reference caches per
  tablespace with a syscache callback + 1-entry MRU, `:217-348`).
  Per-file facts (size) are always read fresh.
* **size threshold** — the direct path only pays off when the table cannot
  live in the host page cache; the reference gates on
  ``(RAM − shared_buffers)·⅔ + shared_buffers`` (`:1544-1559`), overridable
  by ``debug_no_threshold``.  Here RAM comes from /proc MemTotal and the
  "shared_buffers" analog is the configured staging pool size.
* **cost model** — per-page cost with the reduced ``seq_page_cost`` GUC
  (default ¼ of the conventional cost, `:1614-1625`) and a parallel divisor
  capped at 4 for the disk component (`:491-517`) so I/O cost does not
  shrink linearly with workers.
"""

from __future__ import annotations

import os
import threading
import time
import dataclasses
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

from ..api import FileInfo
from ..config import config
from ..engine import check_file

__all__ = ["CapabilityCache", "capability_cache", "direct_scan_threshold",
           "should_use_direct_scan", "ScanCost", "cost_direct_scan",
           "cost_vfs_scan", "PushdownDecision", "decide_pushdown",
           "transport_rates"]

# conventional-path reference cost per 8KB page (PG's seq_page_cost = 1.0)
VFS_PAGE_COST = 1.0
CPU_TUPLE_COST = 0.01
_MAX_PARALLEL_DISK_DIVISOR = 4.0   # reference caps at 4 (:491-517)


class CapabilityCache:
    """Directory-level capability cache (TTL + explicit invalidation).

    Caches only directory-scoped facts — fs capability, DMA64 support, NUMA
    node, request cap.  File size is stat'ed fresh on every probe so one
    file's geometry is never attributed to another in the same directory."""

    def __init__(self, ttl_s: float = 60.0):
        self._lock = threading.Lock()
        self._cache: Dict[str, Tuple[FileInfo, float]] = {}
        self._mru: Optional[Tuple[str, FileInfo, float]] = None  # 1-entry MRU (:233)
        self.ttl_s = ttl_s

    def _fresh(self, path: str, cap: FileInfo) -> FileInfo:
        size = os.stat(path).st_size
        kind = cap.fs_kind if size >= 4096 else type(cap.fs_kind)(0)
        # replace(), not a field-by-field copy: a FileInfo field added
        # later must flow through the cache unchanged, not silently
        # reset to its default
        return dataclasses.replace(cap, path=path, file_size=size,
                                   fs_kind=kind)

    def probe(self, path: str) -> FileInfo:
        d = os.path.dirname(os.path.abspath(path)) or "/"
        now = time.monotonic()
        with self._lock:
            if self._mru is not None and self._mru[0] == d                     and now - self._mru[2] < self.ttl_s:
                return self._fresh(path, self._mru[1])
            hit = self._cache.get(d)
            if hit is not None and now - hit[1] < self.ttl_s:
                self._mru = (d, hit[0], hit[1])
                return self._fresh(path, hit[0])
        # honest facts only (strict=False): policy is applied live by
        # should_use_direct_scan, so toggling require_nvme_backing takes
        # effect immediately instead of after cache TTL
        cap = check_file(path, strict=False)
        with self._lock:
            self._cache[d] = (cap, now)
            self._mru = (d, cap, now)
        return self._fresh(path, cap)

    def invalidate(self, directory: Optional[str] = None) -> None:
        """Syscache-callback analog (`pgsql/nvme_strom.c:340-348`)."""
        with self._lock:
            if directory is None:
                self._cache.clear()
            else:
                self._cache.pop(os.path.abspath(directory), None)
            self._mru = None


capability_cache = CapabilityCache()


def _mem_total_bytes() -> int:
    """Physical RAM (the reference's threshold uses total RAM,
    pgsql/nvme_strom.c:1544-1559)."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) << 10
    except OSError:
        pass
    return 8 << 30


def direct_scan_threshold() -> int:
    """Table size above which the direct path is planned
    (reference `(RAM − shared_buffers)·⅔ + shared_buffers`, :1544-1559)."""
    ram = _mem_total_bytes()
    shared = config.get("buffer_size")
    return int((max(ram - shared, 0) * 2) // 3 + shared)


def should_use_direct_scan(path: str, *, table_size: Optional[int] = None) -> bool:
    """The add-path gate (`nvmestrom_add_scan_path`, :555-596)."""
    if not config.get("enabled"):
        return False
    info = capability_cache.probe(path)
    if not info.supported:
        return False
    # DMA64 was the reference's hard requirement for P2P BAR addressing
    # (pgsql/nvme_strom.c:313-318); on the pinned-host path the host
    # kernel owns addressing, so the shared strict predicate only gates
    # when backing verification is authoritative (live policy read —
    # cache holds honest facts)
    if config.get("require_nvme_backing") and not info.strict_eligible:
        return False
    size = table_size if table_size is not None else info.file_size
    if config.get("debug_no_threshold"):
        return True
    return size >= direct_scan_threshold()


@dataclass(frozen=True)
class ScanCost:
    startup: float
    total: float
    pages: int
    workers: int


def _parallel_divisor(workers: int) -> float:
    """PG's parallel divisor incl. leader contribution."""
    d = float(max(workers, 1))
    if workers >= 1:
        d += 0.3 * min(workers, 4) / 4  # leader does some work too
    return d


def cost_direct_scan(n_pages: int, n_tuples: int, *, workers: int = 0) -> ScanCost:
    """`cost_nvmestrom_scan` analog (:451-520): reduced per-page cost, disk
    component divided by at most 4 regardless of worker count."""
    page_cost = config.get("seq_page_cost") * VFS_PAGE_COST
    disk_div = min(_parallel_divisor(workers), _MAX_PARALLEL_DISK_DIVISOR)
    cpu_div = _parallel_divisor(workers)
    disk = n_pages * page_cost / disk_div
    cpu = n_tuples * CPU_TUPLE_COST / cpu_div
    return ScanCost(startup=0.0, total=disk + cpu, pages=n_pages, workers=workers)


def cost_vfs_scan(n_pages: int, n_tuples: int, *, workers: int = 0) -> ScanCost:
    disk = n_pages * VFS_PAGE_COST / min(_parallel_divisor(workers),
                                         _MAX_PARALLEL_DISK_DIVISOR)
    cpu = n_tuples * CPU_TUPLE_COST / _parallel_divisor(workers)
    return ScanCost(startup=0.0, total=disk + cpu, pages=n_pages, workers=workers)


# -- compute pushdown: where does each column expand? (ISSUE 14) -----------
#
# The AXI4MLIR question (PAPERS.md, arXiv:2402.19184): for each column,
# does decompression happen on the host, on the chip, or not at all (ship
# raw)?  The inputs are the OBSERVED codec ratio (exact, recorded by the
# encoder per column) and the live transport picture: when h2d is the
# ceiling (the measured reality here: h2d_peak 1.06 vs raw_seq_read 3.36
# GB/s), packed bytes must stay packed across the link and expand in
# VMEM; when the SSD is the ceiling instead, host expansion already
# captures the win and keeps the decode off the accelerator.


def _live_ssd_gbps() -> Optional[float]:
    """The host disk's rate as the engine saw it: bytes of direct reads
    over the time the device queue was busy (None before any direct
    read in this process)."""
    from ..stats import stats
    nbytes = stats._c.get("total_dma_length", 0)
    busy_ns = stats._c.get("occ_busy_ns", 0)
    if nbytes <= 0 or busy_ns <= 0:
        return None
    return nbytes / busy_ns * (1e9 / (1 << 30))


def transport_rates() -> Tuple[float, Optional[float]]:
    """(h2d_gbps, ssd_gbps) the pushdown decision runs on.

    h2d: config override > live H2D rate meter (fed by transfer-bound
    scan fences) > this device kind's figure (``device_figures``; an
    unknown kind raises).  ssd: config override > live meter of the
    engine's direct reads, else None (unknown)."""
    h2d = float(config.get("pushdown_h2d_gbps"))
    ssd = float(config.get("pushdown_ssd_gbps")) or _live_ssd_gbps()
    if not h2d:
        from ..device_figures import device_figures
        from ..hbm.staging import h2d_meter
        h2d = h2d_meter.observed_gbps() or device_figures().h2d_gbps
    return h2d, ssd


@dataclass(frozen=True)
class PushdownDecision:
    """Where a packed scan expands, and the wire-byte prediction EXPLAIN
    reports."""

    mode: str                    # "chip" | "host" | "raw"
    wire_bytes: int              # predicted bytes crossing host->device
    logical_bytes: int           # bytes the query logically consumes
    per_column: Tuple[tuple, ...]   # (col, codec, ratio, "chip"|"host"|"raw")
    reason: str

    def explain(self) -> str:
        cols = ", ".join(
            f"col{c}={where}({codec}" +
            (f" {ratio:.1f}x)" if codec != "raw" else ")")
            for c, codec, ratio, where in self.per_column)
        codecs = "+".join(sorted({codec for _c, codec, _r, _w
                                  in self.per_column})) or "none"
        return (f"pushdown {self.mode}: predicted wire bytes: "
                f"{self.wire_bytes} ({self.logical_bytes} logical, "
                f"codec={codecs}); {cols}; {self.reason}")


def decide_pushdown(meta, need_cols=None) -> PushdownDecision:
    """Per-column host/chip/raw expansion decision for a packed sidecar.

    *meta* is a ``scan/colpack.py`` PackedMeta; *need_cols* restricts the
    decision to the columns the query touches (projection pushdown).
    ``pushdown=on`` forces chip; ``auto`` keys on the observed codec
    ratio vs ``pushdown_chip_ratio`` and on which transport is the
    ceiling."""
    mode_cfg = config.get("pushdown")
    h2d, ssd = transport_rates()
    thresh = float(config.get("pushdown_chip_ratio"))
    need = set(range(len(meta.cols))) if need_cols is None \
        else set(need_cols)
    # an unknown SSD rate counts as h2d-bound: shipping packed bytes is
    # never more wire than shipping expanded ones
    h2d_bound = ssd is None or ssd > h2d
    per_col, wire = [], 0
    for c, cm in enumerate(meta.cols):
        if c not in need:
            continue
        ratio = cm.ratio
        if mode_cfg == "on" or (ratio >= thresh and h2d_bound):
            where = "chip"         # packed across the link, expand in VMEM
        elif ratio >= thresh:
            where = "host"         # SSD-bound: packed off disk only
        else:
            where = "raw"          # codec never paid for itself
        per_col.append((c, cm.codec, round(ratio, 3), where))
        wire += cm.packed_bytes
    logical = 4 * meta.n_rows * len(per_col)
    # the file is ONE representation: per-page headers + unselected-column
    # regions ride along, so the honest wire prediction is whole packed
    # pages, scaled to nothing only when the scan goes raw
    wire_pages = meta.packed_bytes
    scan_ratio = logical / wire_pages if wire_pages else 1.0
    if mode_cfg == "off":
        mode, why = "raw", "pushdown=off"
    elif mode_cfg == "on":
        mode, why = "chip", "pushdown=on (forced)"
    elif not per_col:
        mode, why = "raw", "no packable columns in the projection"
    elif scan_ratio < thresh:
        mode, why = "raw", (f"whole-scan codec ratio {scan_ratio:.2f}x "
                            f"below chip threshold {thresh:.2f}x")
    elif h2d_bound:
        vs = f"{ssd:.2f} GB/s" if ssd is not None else "unknown"
        mode, why = "chip", (f"h2d is the ceiling ({h2d:.2f} GB/s vs SSD "
                             f"{vs}): packed bytes cross the link, "
                             f"expand in VMEM")
    else:
        mode, why = "host", (f"SSD is the ceiling ({ssd:.2f} vs h2d "
                             f"{h2d:.2f} GB/s): packed off disk, "
                             f"expanded on host")
    return PushdownDecision(
        mode=mode,
        wire_bytes=int(wire_pages if mode != "raw"
                       else 4 * meta.n_rows * len(meta.cols)),
        logical_bytes=int(logical),
        per_column=tuple(per_col), reason=why)
