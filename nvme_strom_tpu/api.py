"""UAPI-equivalent command types.

Capability mirror of the reference's ioctl ABI (`kmod/nvme_strom.h:17-171`):
ten commands, each an argument struct with in/out fields.  On TPU there is no
kernel module — the "driver" is an in-process native engine — but the command
vocabulary, field semantics and error model are preserved so every capability
in SURVEY.md SS2 has a testable contract:

==========================  ==========================================
reference ioctl             here
==========================  ==========================================
STROM_IOCTL__CHECK_FILE     CheckFileCmd / FileInfo
..__MAP_GPU_MEMORY          MapDeviceMemoryCmd (HBM handle, hbm.registry)
..__UNMAP_GPU_MEMORY        UnmapDeviceMemoryCmd
..__LIST_GPU_MEMORY         ListDeviceMemoryCmd
..__INFO_GPU_MEMORY         InfoDeviceMemoryCmd
..__MEMCPY_SSD2GPU          MemCopySsdToDeviceCmd  (SSD -> HBM)
..__MEMCPY_SSD2RAM          MemCopySsdToRamCmd     (SSD -> pinned host)
..__MEMCPY_WAIT             MemCopyWaitCmd
..__ALLOC_DMA_BUFFER        AllocDmaBufferCmd (implemented, not vestigial)
..__STAT_INFO               StatInfoCmd / StatInfo
==========================  ==========================================

Chunk-reordering contract (reference `kmod/nvme_strom.h:99-101`,
`kmod/nvme_strom.c:1647-1663`): on return from a memcpy command the caller's
``chunk_ids`` array is permuted — the first ``nr_ssd2dev`` entries were read
by direct I/O into the destination, the trailing ``nr_ram2dev`` entries were
found (mostly) resident in the host page cache and took the write-back path.
"""

from __future__ import annotations

import enum
import errno as _errno
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

__all__ = [
    "StromError", "ErrorClass", "FsKind", "FileInfo", "BufferInfo",
    "DmaTaskState", "MemCopyResult", "StatInfo", "STAT_FIELDS",
]


class ErrorClass(enum.Enum):
    """Fault taxonomy for the I/O runtime.

    The reference latches a single raw errno per task (kmod/nvme_strom.c
    first-error retention); here each error additionally carries a class
    that drives the recovery policy: TRANSIENT errors are retried (and may
    degrade to the buffered path), CORRUPTION triggers re-read then a
    latched EBADMSG, TIMEOUT is latched by the task watchdog, PERSISTENT
    fails fast with no retry.
    """

    TRANSIENT = "transient"
    PERSISTENT = "persistent"
    CORRUPTION = "corruption"
    TIMEOUT = "timeout"


# default errno -> class mapping; explicit error_class wins
_TRANSIENT_ERRNOS = frozenset((
    _errno.EIO, _errno.EAGAIN, _errno.EBUSY, _errno.EINTR, _errno.ENOMEM,
))
_CORRUPTION_ERRNOS = frozenset((_errno.EBADMSG, _errno.EILSEQ))


def _classify_errno(errno_: int) -> ErrorClass:
    if errno_ == _errno.ETIMEDOUT:
        return ErrorClass.TIMEOUT
    if errno_ in _CORRUPTION_ERRNOS:
        return ErrorClass.CORRUPTION
    if errno_ in _TRANSIENT_ERRNOS:
        return ErrorClass.TRANSIENT
    return ErrorClass.PERSISTENT


class StromError(OSError):
    """Engine error carrying an errno-style code (reference returns -errno)
    plus a recovery class (:class:`ErrorClass`).  The class defaults from
    the errno (EIO/EAGAIN/EBUSY/EINTR/ENOMEM transient, EBADMSG/EILSEQ
    corruption, ETIMEDOUT timeout, everything else persistent) and can be
    pinned explicitly by the raiser."""

    def __init__(self, errno_: int, msg: str,
                 error_class: Optional[ErrorClass] = None):
        super().__init__(errno_, msg)
        self.error_class = error_class or _classify_errno(errno_)

    @property
    def transient(self) -> bool:
        return self.error_class is ErrorClass.TRANSIENT


class FsKind(enum.IntEnum):
    """Filesystem classification from the eligibility check.

    The reference accepts only ext4/xfs (magic + module identity check,
    kmod/nvme_strom.c:477-486).  The TPU engine's O_DIRECT path works on any
    filesystem that honours O_DIRECT; we still classify so policy can gate.
    """

    UNSUPPORTED = 0
    EXT4 = 1
    XFS = 2
    OTHER_DIRECT = 3   # O_DIRECT probe succeeded on some other fs
    FAKE = 4           # testing.fake loopback device


@dataclass(frozen=True)
class FileInfo:
    """Result of CHECK_FILE (reference StromCmd__CheckFile, kmod/nvme_strom.h:34-46
    filled by ioctl_check_file, kmod/nvme_strom.c:188-583)."""

    path: str
    file_size: int
    fs_kind: FsKind
    logical_block_size: int      # HW sector size analog (kmod/nvme_strom.c:274-295)
    dma_max_size: int            # clamped merged-request cap (:297-314)
    numa_node_id: int            # (:316-328)
    support_dma64: bool          # probed from the device chain (:330-336)
    n_members: int = 1           # RAID-0 member count (1 = plain file)
    stripe_chunk_size: int = 0   # RAID-0 chunk in bytes (0 = plain)
    backing_kind: str = ""       # "nvme" | "md-raid0" | "md" (failed RAID-0
                                 # validation) | "other" | "none"
    backing_supported: bool = False  # raw-NVMe-or-RAID0 verified (:229-438)
    backing_reason: str = ""     # why-not, for strom_check / planner logs
    policy_rejected: bool = False    # strict eligibility said no (policy,
                                     # distinct from the fs_kind fact)

    @property
    def supported(self) -> bool:
        return self.fs_kind != FsKind.UNSUPPORTED and not self.policy_rejected

    @property
    def strict_eligible(self) -> bool:
        """THE strict-eligibility predicate (verified NVMe backing + 64-bit
        DMA, the reference's hard gate kmod/nvme_strom.c:229-438 + pgsql
        :313-318).  check_file's policy_rejected and the planner's live
        gate both derive from this so they can never disagree."""
        return self.backing_supported and self.support_dma64


@dataclass(frozen=True)
class BufferInfo:
    """INFO_GPU_MEMORY analog (reference StromCmd__InfoGpuMemory,
    kmod/nvme_strom.h:66-82): geometry of one registered destination buffer."""

    handle: int
    length: int
    page_size: int
    n_pages: int
    owner_uid: int
    refcount: int
    kind: str            # 'hbm' | 'pinned_host' | 'user'
    device: Optional[str] = None


class DmaTaskState(enum.IntEnum):
    RUNNING = 0
    DONE = 1
    FAILED = 2           # latched first error, retained until reaped
    REAPED = 3


@dataclass
class MemCopyResult:
    """Out-fields of MEMCPY_SSD2GPU/RAM (reference kmod/nvme_strom.h:85-117).

    ``chunk_ids`` is the caller's array *after* the engine's reordering:
    ``chunk_ids[:nr_ssd2dev]`` went through direct I/O, the tail
    ``chunk_ids[nr_chunks-nr_ram2dev:]`` took the page-cache write-back path.
    """

    dma_task_id: int
    nr_chunks: int
    nr_ssd2dev: int
    nr_ram2dev: int
    chunk_ids: List[int]
    # landing path this command took ("direct" zero-copy into the owned
    # destination buffer, "staged" through the pinned ring); empty for
    # raw engine commands where the question does not arise
    landing: str = ""

    def __post_init__(self) -> None:
        # conservation invariant the reference asserts (kmod/nvme_strom.c:1708)
        assert self.nr_ssd2dev + self.nr_ram2dev == self.nr_chunks, \
            f"chunk conservation violated: {self.nr_ssd2dev}+{self.nr_ram2dev}!={self.nr_chunks}"


# The statistics contract: count+clock pairs per stage plus gauges, mirroring
# the reference's 26 atomic64 counters (kmod/nvme_strom.c:83-106) and the
# STAT_INFO snapshot (:2059-2103).  Clocks are monotonic nanoseconds here
# (the reference used rdtsc and shipped tsc_hz for conversion).
STAT_FIELDS: Tuple[str, ...] = (
    "nr_ioctl_memcpy_submit", "clk_ioctl_memcpy_submit",
    "nr_ioctl_memcpy_wait",   "clk_ioctl_memcpy_wait",
    "nr_ssd2dev",             "clk_ssd2dev",
    "nr_setup_prps",          "clk_setup_prps",      # request-build stage
    "nr_submit_dma",          "clk_submit_dma",
    "nr_wait_dtask",          "clk_wait_dtask",
    "nr_wrong_wakeup",
    "total_dma_length",
    "cur_dma_count",
    "max_dma_count",
    # beyond the reference's 26: batched-submission syscall count (one
    # io_uring_enter covers a whole task's SQE batch per ring, so
    # nr_enter_dma / nr_submit_dma ~ 1/batch)
    "nr_enter_dma",
    # deepest ADAPTIVE H2D pipeline reached by a scan (gauge; grows only
    # when the consumer observed itself blocking on transfer readiness)
    "h2d_depth_reached",
    # jitted kernel-call dispatches issued by streamed scan compute and
    # checkpoint-restore landings: with dispatch coalescing (config
    # scan_dispatch_batch = K) this moves once per K batches/spans, so
    # nr_kernel_dispatch / batches ~ 1/K on coalesced paths
    "nr_kernel_dispatch",
    # value-keyed GROUP BY's one-pass key set: steps that added keys the
    # scan met after the first batch's, and batches whose kernel ran
    # again over only the keys they missed
    "nr_group_key_grow",
    "nr_group_rerun",
    # fault-tolerance layer (PR 1): retry/degradation accounting.  The
    # reference has no retry tier (EIO fails the task outright); these
    # count each recovery action so operators can see a degrading device
    # before it turns into latched errors.
    "nr_io_retry",            # direct-read attempts repeated after a
    #                           transient error (per-chunk, per-attempt)
    "nr_io_fallback",         # extents degraded to the buffered path
    #                           after retries were exhausted
    "nr_backend_fallback",    # native engine setup/submit failures that
    #                           fell back to the threadpool/python path
    "nr_task_timeout",        # DMA tasks latched ETIMEDOUT by the watchdog
    "nr_chunk_cancelled",     # chunks skipped because their task already
    #                           failed (watchdog/first-error cancellation)
    "nr_csum_fail",           # page checksum mismatches observed
    "nr_csum_reread",         # re-reads issued to heal a checksum mismatch
    "nr_member_quarantine",   # member quarantine transitions (entries)
    # member-health state machine + hedging + mirroring (PR 6)
    "nr_member_failed",       # members driven to FAILED (persistent error)
    "nr_member_rejoin",       # REJOINING -> HEALTHY warmup completions
    "nr_canary_probe",        # background canary probes issued
    "nr_hedge_issued",        # hedge legs actually launched (latch expired)
    "nr_hedge_won",           # hedge legs that delivered the bytes first
    "nr_hedge_cancelled",     # hedge legs discarded after the primary won
    "nr_mirror_read",         # extents served from a member's mirror at
    #                           direct speed (degraded-mode striping)
    # write-amplification surface (PR 7): bytes the pipeline TOUCHED
    # beyond the payload it delivered.  The ROADMAP item 5 gate metric is
    # the derived ratio (payload + these) / payload — "bytes touched per
    # byte delivered", 1.0 = the reference's zero-copy ideal
    # (stats.bytes_touched_ratio; tpu_stat -v and the Prometheus render
    # both surface it).
    "bytes_staging_copy",     # staged bytes copied pinned-host -> device
    #                           (the hop GPUDirect avoided; every staged
    #                           payload byte crosses it once today)
    "bytes_verify_reread",    # bytes re-read healing checksum mismatches
    "bytes_hedge_dup",        # duplicate bytes a hedge race read twice
    #                           (the losing leg's extent length)
    # zero-copy landing (ISSUE 8): plan-time routing of each pipeline
    # command between direct-to-destination and the staged ring
    "nr_landing_direct",      # commands landed straight in an owned
    #                           LandingBuffer the device array aliases
    #                           (no staging hop: ratio floor ~1.0)
    "nr_landing_staged",      # commands routed through the staging ring
    #                           (chosen or fallen back)
    "nr_landing_fallback",    # commands that wanted direct but fell back
    "nr_landing_fallback_alignment",  # ...dest_offset/total does not
    #                                   cover the destination exactly
    "nr_landing_fallback_dtype",      # ...chunk/tail geometry or array
    #                                   shape not dtype-compatible
    "nr_landing_fallback_backend",    # ...backend cannot alias host
    #                                   memory (no zero-copy device_put)
    # queue-occupancy integral (PR 4 saturation work): occ_integral_ns
    # accumulates sum(in_flight * dt) and occ_busy_ns the elapsed ns with
    # in_flight > 0, so mean queue occupancy over an interval is
    # d(occ_integral_ns) / d(occ_busy_ns) — the observable proof that the
    # submission window held the device queue full across chunk
    # boundaries instead of draining at each wait.
    "occ_integral_ns",
    "occ_busy_ns",
    # cross-query residency tier (ISSUE 9): the owned pinned-RAM extent
    # cache in cache.py — hits are chunks served straight from slabs
    # (no submission, no mincore probe), fills are miss extents
    # installed at wait time after the fault ladder healed them
    "nr_cache_hit",           # chunks served from resident slabs
    "nr_cache_miss",          # chunks that went to the engine instead
    "nr_cache_fill",          # extents installed into slabs
    "nr_cache_evict",         # extents ARC-evicted to make room
    "nr_cache_invalidate",    # extents dropped by write-back/checkpoint
    #                           coherency
    "bytes_cache_hit",        # payload bytes served from the tier
    "cache_resident_bytes",   # gauge: bytes currently resident
    # mirror-coherent write ladder (ISSUE 11): the RAM->SSD leg fans out
    # to paired mirrors, degrades to mirror-only with a dirty-extent
    # resync journal, and (optionally) read-back-verifies at wait time
    "nr_mirror_write",        # mirror-partner write legs landed
    "nr_write_retry",         # write attempts re-driven (transient retry
    #                           or native-completion failover to the pool)
    "nr_resync_extent",       # journal extents replayed onto a rejoiner
    "nr_write_verify_fail",   # write_verify read-back crc32c mismatches
    "resync_pending_bytes",   # gauge: dirty-extent bytes awaiting resync
    # shared serving daemon (ISSUE 12): stromd arbitrates N clients over
    # one engine the way the reference's /proc/nvme-strom entry
    # arbitrates N processes in the kernel — session lifecycle, admission
    # control, and the QoS scheduler each account here
    "nr_session_attach",      # client sessions attached
    "nr_session_detach",      # sessions released by clean detach
    "nr_session_reap",        # orphans reaped after client disconnect
    #                           (crash/SIGKILL) without detach
    "nr_admission_reject",    # submits bounced with EAGAIN by per-tenant
    #                           in-flight quota (backpressure, not queueing)
    "nr_qos_wait", "clk_qos_wait",  # per-dispatch queue wait (enqueue ->
    #                                 scheduler pick) count+clock pair
    "nr_qos_throttle",        # tenants token-bucket-gated at the head of
    #                           their class ring (edge, not per-poll)
    "daemon_sessions",        # gauge: sessions currently attached
    "qos_queue_depth",        # gauge: items queued ahead of dispatch
    # compute pushdown (ISSUE 14): packed-extent scans that expand the
    # codec on chip (fused decode->filter->project kernel) or on the
    # host (SSD-bound: packed crosses the disk link only)
    "nr_pushdown_decode_chip",   # packed batches expanded in VMEM by
    #                              the fused decode kernel
    "nr_pushdown_decode_host",   # packed batches expanded host-side
    "bytes_wire_saved",          # logical-minus-packed bytes that never
    #                              crossed the bottleneck transport
    # LLM serving stack (ISSUE 15): the device-side HBM residency tier
    # above the host ARC tier, checkpoint weight streaming, and the
    # SSD-backed KV-cache block pool
    "nr_hbm_hit",             # chunks served from HBM-resident extents
    #                           (outranks host hits; one device->dest copy)
    "nr_hbm_promote",         # extents promoted host tier -> HBM
    #                           (second-touch t1->t2 transition, KV pins)
    "nr_hbm_demote",          # extents demoted HBM -> host tier by
    #                           capacity eviction
    "nr_kv_pagein",           # KV blocks paged SSD -> RAM (+ promotion)
    "nr_kv_pageout",          # KV blocks spilled RAM -> SSD (mirrored
    #                           write ladder)
    "hbm_resident_bytes",     # gauge: bytes currently HBM-resident
    "coldstart_bytes_per_sec",  # gauge: last weight-stream landing rate
    # resident-data integrity domain (ISSUE 16)
    "nr_integrity_verify",    # resident checksums verified (transitions,
    #                           lease reads under integrity=always, scrub)
    "nr_integrity_fail",      # resident checksum mismatches detected
    "nr_scrub_extent",        # extents walked by the background scrubber
    "bytes_scrubbed",         # bytes the scrubber has verified
    "nr_scrub_repair",        # corrupt residents healed (SSD re-fill or
    #                           mirror-leg read-back)
    "nr_scrub_fail",          # corrupt residents that could NOT be healed
    "nr_cache_mlock_fail",    # mlock(2) failures: slab runs unpinned
    "cache_unpinned_bytes",   # gauge: resident slab bytes not mlock-pinned
    "nr_pressure_shed",       # residents shed under memlock/HBM pressure
    "nr_pressure_passthrough",  # fills refused under pressure (reads pass
    #                           through to SSD instead of ENOMEM)
    # multi-host scale-out (ISSUE 17): sharded loading + on-fabric moves
    "nr_shard_load",          # per-host local shard reads completed
    "bytes_shard_load",       # bytes read through per-host shard sessions
    "nr_ici_permute",         # ring-permute rotation steps executed
    "bytes_ici",              # bytes moved device-to-device over the ring
    "nr_shard_wait",          # per-shard completion fan-in waits observed
    "clk_shard_wait",         # total submit->completion wait (straggler
    #                           attribution; per-shard histogram in export)
    "nr_kv_migrate",          # KV chains migrated to a peer host's pool
    "nr_kv_migrate_fail",     # migrations rolled back (peer append failed)
    # self-driving data path (ISSUE 18): autotune controller + readahead
    "nr_autotune_step",       # accepted knob movements (per family step)
    "nr_autotune_revert",     # probes stepped back (no gain / p99 regress)
    "nr_autotune_freeze",     # epochs frozen for the health machine
    "nr_readahead_fill",      # speculative fills completed
    "nr_readahead_hit",       # first demand touch of a speculative slab
    "nr_readahead_skip",      # predictions dropped (budget/alloc pressure)
    "bytes_readahead",        # bytes prefetched into the residency tier
    # raw NVMe passthrough (PR 19): URING_CMD lane + blockmap resolution
    "nr_passthru_dma",        # requests served as raw NVMe READ commands
    "bytes_passthru",         # bytes routed onto the passthrough lane
    "nr_passthru_refused_extent",  # spans refused per-extent (hole,
    #                           ineligible flags, unaligned, no path)
    "nr_passthru_fallback",   # resolved extents served OFF the lane
    #                           (ladder rung, hedge win, create failure)
    "nr_passthru_refusal_disabled",   # rung refused: NSTPU_DISABLE_PASSTHRU
    "nr_passthru_refusal_nodev",      # rung refused: no NVMe char device
    "nr_passthru_refusal_nouring",    # rung refused: io_uring unavailable
    "nr_passthru_refusal_nouringcmd",  # rung refused: no URING_CMD opcode
    "nr_passthru_refusal_lbafmt",     # rung refused: unusable LBA format
    "nr_blockmap_resolve",    # real FIEMAP walks (cache misses)
    "nr_blockmap_invalidate",  # cached file->LBA maps dropped by writes
    # unified extent address space (ISSUE 20): one placement/migration
    # engine across HBM -> pinned RAM -> SSD (tiering.extent_space)
    "nr_tier_hbm_promote",    # extents second-touch promoted RAM -> HBM
    #                           (exclusive migration: RAM copy yielded up)
    "nr_tier_hbm_demote",     # HBM capacity victims demoted into RAM
    "nr_tier_ram_fault",      # demand faults filled SSD -> RAM (cache
    #                           fills + KV block page-ins; speculative
    #                           readahead fills deliberately excluded)
    "nr_tier_ram_demote",     # RAM victims dropped to the SSD-backed
    #                           tier (ARC capacity eviction)
    "nr_tier_ram_shed",       # RAM residents shed under memlock pressure
    # caller-thread stages of the load and scan paths, count+clock pairs
    # timed by stats.stage() (which also puts each entry on a running
    # jax.profiler trace as a strom.<stage> span)
    "nr_pagecache_copy", "clk_pagecache_copy",  # write-back page-cache
    #                           copies run inline on the caller (runs under
    #                           the pool threshold), nested in
    #                           ioctl_memcpy_submit
    "nr_writeback_copy", "clk_writeback_copy",  # write-back runs copied on
    #                           the session's I/O pool, off the caller
    "nr_staging_fence", "clk_staging_fence",    # waits for the device to
    #                           finish reading a staging buffer to reuse
    "nr_staging_fence_blocked",  # of those, fences whose transfer had not
    #                           finished on entry (the ring's reuse order
    #                           failed to hide the h2d behind the next read)
    "nr_staging_retire", "clk_staging_retire",  # staged batch h2d issue
    #                           plus its landing dispatch
    "nr_load_setup", "clk_load_setup",  # load_file_to_device's set-up
    #                           and tear-down (one count per load)
    "nr_vfs_read", "clk_vfs_read",      # the VFS scan's waits for its next
    #                           batch from the read-ahead ring
    "nr_vfs_read_blocked",    # of those, waits whose batch was not read
    #                           yet on entry (the ring failed to hide it)
    "nr_vfs_readahead", "clk_vfs_readahead",  # the ring's buffered reads,
    #                           on its reader thread
    "nr_vfs_readahead_fence_blocked",  # ring refills that waited for the
    #                           transfer out of the slot to finish
    "nr_group_discover", "clk_group_discover",  # value-keyed GROUP BY's
    #                           key finding: the one-pass bootstrap and
    #                           growth steps (nested in staging_retire), or
    #                           a sidecar or discovery scan (whose
    #                           vfs_read/staging_retire nest in it)
    "nr_mesh_submit", "clk_mesh_submit",  # a mesh scan step's engine
    #                           submits for every shard (the engine's own
    #                           submit and page-cache copy nest in it)
    "nr_mesh_wait", "clk_mesh_wait",  # the mesh stream's waits on its
    #                           shards' read tasks (polls and blocking)
    "nr_debug1", "clk_debug1",
    "nr_debug2", "clk_debug2",
    "nr_debug3", "clk_debug3",
    "nr_debug4", "clk_debug4",
)


@dataclass
class StatInfo:
    """STAT_INFO snapshot (reference StromCmd__StatInfo, kmod/nvme_strom.h:141-171)."""

    version: int = 1
    has_debug: bool = False
    timestamp_ns: int = 0
    counters: dict = field(default_factory=dict)

    def __getattr__(self, name: str):
        try:
            return self.__dict__["counters"][name]
        except KeyError:
            raise AttributeError(name) from None

    @staticmethod
    def delta(new: "StatInfo", old: "StatInfo") -> "StatInfo":
        d = {k: new.counters.get(k, 0) - old.counters.get(k, 0) for k in new.counters}
        # gauges are point-in-time, not deltas
        for g in ("cur_dma_count", "max_dma_count", "h2d_depth_reached",
                  "cache_resident_bytes", "resync_pending_bytes",
                  "daemon_sessions", "qos_queue_depth",
                  "hbm_resident_bytes", "coldstart_bytes_per_sec",
                  "cache_unpinned_bytes"):
            if g in new.counters:
                d[g] = new.counters[g]
        return StatInfo(version=new.version, has_debug=new.has_debug,
                        timestamp_ns=new.timestamp_ns - old.timestamp_ns,
                        counters=d)
