"""SSD→pinned-host→HBM staging pipeline.

The reference's headline capability is peer-to-peer DMA: the SSD's engine
writes straight into GPU BAR1, no host staging (`kmod/nvme_strom.c:
1518-1589`).  TPUs expose no third-party-DMA BAR, so the equivalent path is
(SURVEY.md SS5.8): O_DIRECT/io_uring reads into **pinned hugepage-backed host
buffers**, overlapped with pinned→HBM transfers through PJRT, so the extra
hop GPUDirect avoided is hidden behind the SSD DMA time.

The ring holds ``staging_buffers`` (default 3) pinned buffers.  Up to
``staging_buffers - 1`` of them receive SSD DMA (native engine, GIL-free);
a batch is retired (its host→device transfer issued and its landing
dispatched) as soon as that many reads are in flight, so at every submit at
least two buffers are free: the one just handed to the transfer and one
whose transfer was issued a cycle or more earlier.  The submit refills the
free buffer whose transfer was issued EARLIEST (one with no transfer behind
it first), so the next read overlaps the newest transfer instead of waiting
for it.  Before a buffer is reused, the device op reading it is
synchronized through a bounded ``block_until_ready`` — the correctness
fence the reference got from DMA completion IRQs; in the steady state the
oldest transfer has finished and the fence returns at once.

Device writes are functional and XLA-idiomatic: the destination is a
registered :class:`~nvme_strom_tpu.hbm.registry.HbmBuffer` whose array is
advanced by a donated jitted ``dynamic_update_slice`` — in-place on device,
no reallocation.
"""

from __future__ import annotations

import errno as _errno
import time
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..api import MemCopyResult, StromError
from ..config import config
from ..engine import Session, Source
from ..log import pr_warn
from ..stats import stats
from ..trace import recorder as _tr
from .registry import HbmRegistry, LandingBuffer, registry as global_registry

__all__ = ["StagingPipeline", "load_file_to_device", "AdaptiveH2DDepth",
           "plan_landing", "H2DRateMeter", "h2d_meter"]


class AdaptiveH2DDepth:
    """Depth controller for deferred-fence H2D pipelining, shared by the
    scan executor and the checkpoint restore ring (VERDICT r2 #3 + r3 #6).

    Grow by one whenever the consumer actually blocked on a transfer
    fence (more overlap would have helped — the reference's ring deepens
    the same way its 32-deep queue absorbs bursts,
    ``pgsql/nvme_strom.c:862-936``); DECAY by one after ``decay_after``
    consecutive fence-free retirements.  On a token-bucket transport the
    two regimes alternate: a deepened pipeline that never shrinks keeps
    pinned chunks out of the pool long after the burst window closed,
    which is exactly backwards for the sustained regime — decay tracks
    the closing window.

    ``observe(blocked_ns)`` after each fence; read ``depth`` before each
    dispatch."""

    BLOCK_NS = 200_000    # a fence wait above 0.2ms counts as blocking

    def __init__(self, cap: int, *, start: int = 2, floor: int = 2,
                 decay_after: int = 4):
        self.cap = max(1, int(cap))
        self.floor = min(max(1, floor), self.cap)
        self.depth = min(max(1, start), self.cap)
        self.decay_after = max(1, decay_after)
        self._streak = 0

    def observe(self, blocked_ns: int) -> int:
        if blocked_ns > self.BLOCK_NS:
            self._streak = 0
            if self.depth < self.cap:
                self.depth += 1
        else:
            self._streak += 1
            if self._streak >= self.decay_after and self.depth > self.floor:
                self.depth -= 1
                self._streak = 0
        return self.depth


class H2DRateMeter:
    """Live estimate of the host->device link rate, fed by the scan
    pipeline's fence waits (executor.retire_oldest).

    Only transfer-BOUND retirements update it: a fence that returned
    immediately says nothing about the link (the transfer overlapped with
    compute), while a blocking fence's bytes/blocked-time approximates
    the drain rate of a backlogged link.  When no sample has landed yet,
    consumers (the pushdown planner) fall back to the device kind's
    figure (``device_figures``) — the estimate refines under load.
    EWMA so one anomalous burst cannot repoint the planner."""

    _ALPHA = 0.2

    def __init__(self) -> None:
        self.rate_gbps = 0.0
        self.samples = 0

    def note(self, nbytes: int, blocked_ns: int) -> None:
        if nbytes <= 0 or blocked_ns <= AdaptiveH2DDepth.BLOCK_NS:
            return
        gbps = nbytes / blocked_ns * (1e9 / (1 << 30))
        self.rate_gbps = gbps if self.samples == 0 else \
            (1 - self._ALPHA) * self.rate_gbps + self._ALPHA * gbps
        self.samples += 1

    def observed_gbps(self) -> Optional[float]:
        return self.rate_gbps if self.samples else None


h2d_meter = H2DRateMeter()


def bounded_fence(arr, what: str = "h2d"):
    """``block_until_ready`` through the backend monitor: bounded by
    config ``backend_fence_timeout``; a deadline miss or runtime error
    latches backend loss and raises ENODEV (VERDICT r3 #5).  Returns
    *arr*."""
    from .backend import monitor
    return monitor.fence(arr, what=what)


def _pending(arr) -> bool:
    """Whether a fence on *arr* will have to wait.  A runtime error is
    left for the bounded fence to classify (it counts as pending)."""
    try:
        return not arr.is_ready()
    except RuntimeError:
        return True


@partial(jax.jit, donate_argnums=(0,))
def _write_slice(dest: jax.Array, chunk: jax.Array, start: jax.Array) -> jax.Array:
    """Land one staged batch into the destination at a dynamic offset.
    ``dest`` is donated: XLA updates the buffer in place on device.
    Limited to int32-addressable offsets (< 2^31 elements)."""
    return jax.lax.dynamic_update_slice(dest, chunk, (start,))


@partial(jax.jit, donate_argnums=(0,))
def _write_slices(dest: jax.Array, starts: jax.Array,
                  *chunks: jax.Array) -> jax.Array:
    """K staged batches land in ONE dispatch: per-call latency on a
    high-latency backend otherwise costs a round trip per span (the same
    coalescing discipline as the scan executor's CoalescedFold).
    ``starts`` is an int32 (K,) vector of element offsets; the slices
    are disjoint so update order is immaterial.  ``dest`` donated."""
    for i, c in enumerate(chunks):
        dest = jax.lax.dynamic_update_slice(dest, c, (starts[i],))
    return dest


#: lane width of the row view :func:`_write_row` lands through.  On the
#: TPU a 1-D array is tiled in runs of 8x128 elements, which is exactly
#: the tiling of its ``(-1, 128)`` view, so the reshape moves no bytes
#: and the donated update stays in place (tests/test_tpu_compile.py
#: checks this at 8 GiB; a wider row view would make XLA relayout the
#: whole destination into a second buffer of its size).
_LANES = 128


@partial(jax.jit, donate_argnums=(0,))
def _write_row(dest: jax.Array, chunk: jax.Array, row: jax.Array) -> jax.Array:
    """Row-addressed landing: view the destination and the chunk as
    ``(-1, _LANES)`` and update from row *row*.  Row indices stay small,
    so destinations beyond the int32 element ceiling (>2GiB of uint8)
    address correctly.  The landing start and the chunk length must be
    multiples of ``_LANES`` elements."""
    d2 = dest.reshape(-1, _LANES)
    d2 = jax.lax.dynamic_update_slice(d2, chunk.reshape(-1, _LANES), (row, 0))
    return d2.reshape(dest.shape)


_INT32_MAX = (1 << 31) - 1


def owned_if_cpu(host: np.ndarray, devlike) -> np.ndarray:
    """Copy a pinned-buffer view before device_put on the CPU backend.

    CPU-backend device_put zero-copies aligned numpy views, so the "device"
    array would alias pinned memory the next SSD DMA overwrites (and
    close() unmaps).  Accelerator backends always copy host->HBM, so this
    is free where throughput matters."""
    platform = (devlike.platform if hasattr(devlike, "platform")
                else next(iter(devlike.device_set)).platform)
    if platform == "cpu":
        return np.array(host)
    return host


def safe_device_put(host: np.ndarray, devlike) -> jax.Array:
    """device_put that never aliases the source buffer (owned_if_cpu)."""
    return jax.device_put(owned_if_cpu(host, devlike), devlike)


# -- H2D transfer paths (VERDICT r2 #2: kill the second host copy) ---------
#
# The reference's whole point is zero extra copies (PRPs aim at GPU BAR1,
# kmod/nvme_strom.c:1518-1589).  On TPU the SSD leg lands in OUR pinned
# mmap; the question is what the pinned->HBM leg costs:
#
#  * "plain": jax.device_put(numpy_view).  PJRT's BufferFromHostBuffer
#    DMAs straight from the caller's buffer when alignment/layout allow —
#    our staging buffers are page-aligned mmaps, exactly the zero-copy
#    case — but falls back to an internal staging copy when they don't.
#  * "pinned_host": two-stage through the PJRT pinned_host memory space:
#    device_put into page-locked PJRT memory, then a jitted
#    pinned->device copy that is pure DMA.  One explicit host copy, but
#    the DMA leg can overlap with compute under XLA's scheduler, and the
#    staging buffer frees as soon as the FIRST leg completes.
#
# Which wins is a hardware/runtime property, so it is a config knob
# ("h2d_path": auto|plain|pinned_host), not an assumption.  "auto" =
# plain; neither leg has been measured on the chip yet.

_pinned_sharding_cache: dict = {}


def _pinned_shardings(dev):
    """(pinned_host sharding, pinned->device copy) for *dev*.  Raises
    StromError(ENOTSUP) when the runtime has no pinned_host memory space
    or cannot lower the copy (CPU lists the space but cannot): a
    configured ``h2d_path=pinned_host`` never silently becomes plain."""
    got = _pinned_sharding_cache.get(dev)
    if got is not None:
        return got
    if "pinned_host" not in {m.kind for m in dev.addressable_memories()}:
        raise StromError(_errno.ENOTSUP, f"h2d_path=pinned_host: {dev} has "
                                         f"no pinned_host memory space")
    from jax.sharding import SingleDeviceSharding
    s_pin = SingleDeviceSharding(dev, memory_kind="pinned_host")
    s_dev = SingleDeviceSharding(dev, memory_kind="device")
    # one jitted pinned->device copy per device, cached (the DMA leg XLA
    # can overlap with compute).  Probed end to end: capability is what
    # runs, not what enumerates.
    to_dev = jax.jit(lambda x: x, out_shardings=s_dev)
    try:
        probe = jax.device_put(np.zeros(16, np.uint8), s_pin)
        jax.block_until_ready(to_dev(probe))
    except jax.errors.JaxRuntimeError as e:
        raise StromError(_errno.ENOTSUP, f"h2d_path=pinned_host: {dev} "
                                         f"cannot copy pinned_host->device: "
                                         f"{e}") from e
    got = _pinned_sharding_cache[dev] = (s_pin, to_dev)
    return got


def h2d_transfer(host: np.ndarray, dev) -> tuple:
    """Move one staged batch host->device on the configured path.

    Returns ``(dev_chunk, reuse_fence)``: the device array to land, and
    the array whose readiness means the SOURCE buffer is safe to reuse
    (on the pinned_host path that is the first leg, so the staging buffer
    frees before the DMA to HBM even completes)."""
    if config.get("h2d_path") in ("auto", "plain"):
        dev_chunk = safe_device_put(host, dev)
        return dev_chunk, dev_chunk
    s_pin, to_dev = _pinned_shardings(dev)
    pinned = jax.device_put(owned_if_cpu(host, dev), s_pin)
    return to_dev(pinned), pinned


def default_device(index: int = 0) -> jax.Device:
    """Prefer an accelerator, like the reference preferring Tesla/Quadro
    (`utils/ssd2gpu_test.c:632-656`); fall back to CPU.  Only this
    process's own (addressable) devices qualify — under ``jax.distributed``
    a remote default would make every unsharded landing span hosts.  An
    index past the pool is an error, never device 0 in disguise."""
    devs = jax.local_devices()
    pool = [d for d in devs if d.platform != "cpu"] or devs
    if not 0 <= index < len(pool):
        raise StromError(_errno.ENODEV, f"device index {index} out of range "
                                        f"({len(pool)} local devices)")
    return pool[index]


def _land(hbm, dev_chunk, elem_start: int):
    """Pick the addressing mode for one landing and install the result."""
    if (hbm.array.size % _LANES == 0 and elem_start % _LANES == 0
            and dev_chunk.size % _LANES == 0):
        hbm.swap(_write_row(hbm.array, dev_chunk,
                            np.int32(elem_start // _LANES)))
    elif elem_start + dev_chunk.size <= _INT32_MAX:
        hbm.swap(_write_slice(hbm.array, dev_chunk, np.int32(elem_start)))
    else:
        raise StromError(75,  # EOVERFLOW
                        f"landing at element {elem_start} exceeds int32 "
                        f"addressing and is not aligned to {_LANES} "
                        f"elements; size the device buffer and the chunks "
                        f"to multiples of {_LANES} elements")


def plan_landing(hbm, chunk_ids: Sequence[int], chunk_size: int,
                 dest_offset: int, device_dtype, tail_len: int):
    """Plan-time landing routing for one pipeline command (ISSUE 8).

    Returns ``(mode, reason)``: *mode* is ``"direct"`` or ``"staged"``;
    *reason* names the fallback cause (``"alignment"`` | ``"dtype"`` |
    ``"backend"``) when the configuration allowed direct but the command
    is ineligible, else ``None``.

    Direct landing REPLACES the destination array with an alias of the
    landed buffer, so the command must cover the destination exactly
    (offset 0, total == nbytes), the geometry must be expressible in the
    device dtype, and the backend must zero-copy page-aligned host views
    (CPU today).  Accelerators pay a host→HBM copy either way, and the
    staged ring overlaps that copy with in-flight SSD DMA — falling back
    there is the fast path, not a compromise."""
    how = config.get("landing")
    if how == "staged":
        return "staged", None
    arr = hbm.array
    dev = list(arr.devices())[0]
    if dev.platform != "cpu":
        return "staged", "backend"
    itemsize = np.dtype(device_dtype).itemsize
    if (arr.ndim != 1 or arr.dtype != np.dtype(device_dtype)
            or chunk_size % itemsize or tail_len % itemsize):
        return "staged", "dtype"
    total = (len(chunk_ids) - 1) * chunk_size + tail_len
    if dest_offset != 0 or total != arr.nbytes:
        return "staged", "alignment"
    return "direct", None


def _trace_landing(source: Source, chunk_ids: Sequence[int], chunk_size: int,
                   nbytes: int, path: str, t0: int, t1: int,
                   trid: int) -> None:
    """One 'landing' span per member extent of the command's chunks, so
    Perfetto member tracks show direct-vs-staged routing per extent
    (events carrying member >= 0 render on the member track)."""
    left = nbytes
    for cid in chunk_ids:
        length = min(chunk_size, left)
        left -= length
        if length <= 0:
            break
        try:
            extents = source.extents(cid * chunk_size, length)
        except (StromError, NotImplementedError):
            extents = None
        if not extents:
            _tr.span("landing", t0, t1, tid=trid, member=0,
                     offset=cid * chunk_size, length=length,
                     args={"path": path})
            continue
        for e in extents:
            _tr.span("landing", t0, t1, tid=trid, member=e.member,
                     offset=e.file_off, length=e.length,
                     args={"path": path})


class StagingPipeline:
    """Overlapped SSD→HBM chunk mover (MEMCPY_SSD2GPU analog, full path).

    Since ISSUE 8 this is the FALLBACK tier: eligible commands land
    zero-copy in an owned :class:`LandingBuffer` (``_memcpy_direct``)
    and never touch the ring; everything else stages here."""

    def __init__(self, session: Session, *, n_buffers: Optional[int] = None,
                 staging_bytes: Optional[int] = None,
                 hbm_registry: Optional[HbmRegistry] = None):
        self.session = session
        self.n_buffers = n_buffers or config.get("staging_buffers")
        self.staging_bytes = staging_bytes or config.get("chunk_size")
        self.registry = hbm_registry or global_registry
        self._bufs = []          # [(engine_handle, DmaBuffer)]
        self._barriers: List[Optional[jax.Array]] = [None] * self.n_buffers
        # retire order of each buffer's pending barrier: reuse picks the
        # smallest, i.e. the transfer that has had the longest to finish
        self._retired_at: List[int] = [0] * self.n_buffers
        self._n_retired = 0
        for _ in range(self.n_buffers):
            self._bufs.append(session.alloc_dma_buffer(self.staging_bytes))

    # -- core ---------------------------------------------------------------
    def memcpy_ssd2dev(self, source: Source, hbm_handle: int,
                       chunk_ids: Sequence[int], chunk_size: int, *,
                       dest_offset: int = 0,
                       device_dtype=jnp.uint8) -> MemCopyResult:
        """Move ``chunk_ids`` (units of ``chunk_size`` bytes in *source*) into
        the registered device buffer, starting at byte ``dest_offset``.

        Returns an aggregated :class:`MemCopyResult`: ``chunk_ids`` is the
        concatenation of each staged batch's reordered array, so entry *i*
        names the chunk now resident at device bytes
        ``dest_offset + i*chunk_size`` — the same slot contract as one
        reference ioctl, applied per batch (each batch is one engine
        command, as each 32MB segment was in ssd2gpu_test).
        """
        if chunk_size > self.staging_bytes:
            raise StromError(22, f"chunk_size {chunk_size} exceeds staging "
                                 f"buffer {self.staging_bytes}")
        if not chunk_ids:
            raise StromError(22, "no chunks")
        # chunks must be full except a single trailing partial: staging
        # slots are chunk_size-strided, so a partial chunk mid-batch would
        # leave a hole in the device layout (the reference reads uniform
        # BLCKSZ blocks for the same reason).  A non-multiple file TAIL is
        # legal (ISSUE 8): it lands a partial slot — submitted as its own
        # single-chunk command, so cache arbitration can never reorder it
        # off the final device slot
        tail_len = chunk_size
        last = len(chunk_ids) - 1
        for pos, cid in enumerate(chunk_ids):
            if cid * chunk_size >= source.size:
                raise StromError(22, f"chunk {cid} beyond EOF (source size "
                                     f"{source.size})")
            if (cid + 1) * chunk_size > source.size:
                if pos != last:
                    raise StromError(22, f"chunk {cid} is partial (source "
                                         f"size {source.size}) but not last; "
                                         f"only the final slot may be partial")
                tail_len = source.size - cid * chunk_size
        hbm = self.registry.acquire(hbm_handle)
        try:
            itemsize = np.dtype(device_dtype).itemsize
            if dest_offset % itemsize:
                raise StromError(22, "dest_offset not aligned to device dtype")
            if tail_len % itemsize:
                raise StromError(22, f"partial tail ({tail_len} bytes) not a "
                                     f"multiple of device dtype itemsize "
                                     f"{itemsize}")
            # -- plan-time landing decision (ISSUE 8) ----------------------
            mode, why = plan_landing(hbm, chunk_ids, chunk_size, dest_offset,
                                     device_dtype, tail_len)
            if mode == "direct":
                stats.add("nr_landing_direct")
                return self._memcpy_direct(source, hbm, list(chunk_ids),
                                           chunk_size, tail_len, device_dtype)
            stats.add("nr_landing_staged")
            if why is not None:
                stats.add("nr_landing_fallback")
                stats.add(f"nr_landing_fallback_{why}")
                if _tr.active:
                    _tr.instant("landing_fallback", args={"reason": why})
                if config.get("landing") == "direct":
                    pr_warn("landing=direct but command ineligible (%s); "
                            "falling back to the staged ring", why)
            per_batch = self.staging_bytes // chunk_size
            full_ids = (list(chunk_ids) if tail_len == chunk_size
                        else list(chunk_ids[:-1]))
            batches = [full_ids[i:i + per_batch]
                       for i in range(0, len(full_ids), per_batch)]
            if tail_len != chunk_size:
                batches.append([chunk_ids[-1]])

            # (bufidx, engine_task_id, batch, dev_elem_start, nbytes, out_pos)
            inflight = []
            # positional: batches may retire OUT OF ORDER (per-member lane
            # fan-in below), but entry i must still name the chunk at
            # device slot i
            out_ids: List[Optional[int]] = [None] * len(chunk_ids)
            nr_ssd = nr_ram = 0
            elem_cursor = dest_offset // itemsize
            chunk_cursor = 0
            total_bytes_needed = (dest_offset
                                  + (len(chunk_ids) - 1) * chunk_size
                                  + tail_len)
            if total_bytes_needed > hbm.nbytes:
                raise StromError(34, f"device buffer too small: need "
                                     f"{total_bytes_needed} > {hbm.nbytes}")

            def retire(slot, res=None) -> None:
                nonlocal nr_ssd, nr_ram
                bufidx, task_id, batch, elem_start, nbytes, out_pos = slot
                if res is None:
                    res = self.session.memcpy_wait(task_id)
                _, dbuf = self._bufs[bufidx]
                # last line of defense before bytes become device state:
                # the direct tier was already verified by the engine at
                # wait time (on this very retired slot — zero-copy, PR 4),
                # so only the write-back (page-cache) tail still needs a
                # staging-ring pass here
                if config.get("checksum_verify"):
                    self._verify_staged(
                        source, res.chunk_ids[res.nr_ssd2dev:], chunk_size,
                        dbuf.view()[res.nr_ssd2dev * chunk_size:nbytes])
                out_ids[out_pos:out_pos + len(batch)] = res.chunk_ids
                nr_ssd += res.nr_ssd2dev
                nr_ram += res.nr_ram2dev
                # the pinned-host hop re-touches every delivered byte (the
                # cost GPUDirect avoided) — feed the bytes-touched ratio
                stats.add("bytes_staging_copy", nbytes)
                # staged batch -> device (async H2D), landed with an async
                # donated update; nothing here blocks
                trid = _tr.traced_id(task_id) if _tr.active else 0
                with stats.stage("staging_retire", ring="staging_retire",
                                 tid=trid, length=nbytes) as st:
                    dev = list(hbm.array.devices())[0]
                    host = np.frombuffer(dbuf.view()[:nbytes],
                                         dtype=device_dtype)
                    dev_chunk, fence = h2d_transfer(host, dev)
                    _land(hbm, dev_chunk, elem_start)
                    # the staging buffer is reusable once the H2D *read*
                    # of it completes — fence on the transfer's first leg,
                    # not the landing (on the pinned_host path the buffer
                    # frees before the DMA to HBM finishes; on CPU the
                    # chunk is an owned copy, so this stays safe)
                    self._barriers[bufidx] = fence
                    self._n_retired += 1
                    self._retired_at[bufidx] = self._n_retired
                    if trid:
                        st.args = {"batch_chunks": len(batch),
                                   "buffer": bufidx,
                                   "ssd2dev": res.nr_ssd2dev,
                                   "ram2dev": res.nr_ram2dev}
                if _tr.active:
                    if trid:
                        _trace_landing(source, res.chunk_ids, chunk_size,
                                       nbytes, "staged", st.t0, st.t1, trid)
                    _tr.task_end(task_id)

            def retire_one() -> None:
                # fan-in from the member lanes (PR 5): retire the FIRST
                # COMPLETED in-flight batch rather than strictly the
                # oldest — with per-member queue pairs a batch striped
                # onto fast members finishes ahead of an older batch
                # queued behind a slow lane, and its staging buffer and
                # H2D leg must not wait on that lane.  Positional out_ids
                # keep the device-slot contract intact.
                for i, slot in enumerate(inflight):
                    try:
                        res = self.session.memcpy_wait(slot[1], timeout=0.0)
                    except StromError as e:
                        if e.errno == _errno.ETIMEDOUT:
                            continue
                        inflight.pop(i)  # failed: wait already reaped it
                        raise
                    retire(inflight.pop(i), res)
                    return
                # none complete yet: block on the oldest (the classic
                # submit-ahead/wait-behind ring of ssd2ram_test,
                # utils/ssd2ram_test.c:139-226)
                retire(inflight.pop(0))

            # reads in flight before a batch is retired: one buffer beyond
            # the just-retired one stays free, so a submit never has to
            # refill the buffer whose transfer it has only just issued
            max_reads = max(1, self.n_buffers - 1)
            try:
                for bi, batch in enumerate(batches):
                    if len(inflight) >= max_reads:
                        retire_one()
                    reading = {s[0] for s in inflight}
                    bufidx = min((i for i in range(self.n_buffers)
                                  if i not in reading),
                                 key=self._reuse_order)
                    # bounded fence (VERDICT r3 #5): the device op that
                    # last consumed this buffer must be done before the
                    # SSD engine overwrites it — and a dead backend must
                    # fail the command, not hang it
                    barrier = self._barriers[bufidx]
                    if barrier is not None:
                        with stats.stage("staging_fence"):
                            if _pending(barrier):
                                stats.add("nr_staging_fence_blocked")
                            bounded_fence(barrier, "staging-reuse")
                        self._barriers[bufidx] = None
                    handle, _ = self._bufs[bufidx]
                    nbytes = len(batch) * chunk_size
                    if tail_len != chunk_size and bi == len(batches) - 1:
                        nbytes = tail_len     # the partial-tail slot
                    task = self.session.memcpy_ssd2ram(source, handle,
                                                       batch, chunk_size)
                    inflight.append((bufidx, task.dma_task_id, batch,
                                     elem_cursor, nbytes, chunk_cursor))
                    elem_cursor += nbytes // itemsize
                    chunk_cursor += len(batch)
                while inflight:
                    retire_one()
            except BaseException:
                # backend loss (or any mid-command failure): reap the
                # in-flight SSD tasks, bounded, so the task table retains
                # no orphans — then surface the FIRST error (the
                # reference's first-error latch + retention discipline,
                # kmod/nvme_strom.c:770-776)
                for slot in inflight:
                    try:
                        self.session.memcpy_wait(slot[1], timeout=5.0)
                    except StromError:
                        pass
                raise
            return MemCopyResult(dma_task_id=0, nr_chunks=len(out_ids),
                                 nr_ssd2dev=nr_ssd, nr_ram2dev=nr_ram,
                                 chunk_ids=out_ids, landing="staged")
        finally:
            self.registry.release(hbm)

    def _reuse_order(self, bufidx: int) -> int:
        """Sort key of a free buffer for reuse: no pending transfer first
        (never used, or drained), then the transfer issued earliest."""
        if self._barriers[bufidx] is None:
            return 0
        return self._retired_at[bufidx]

    def _memcpy_direct(self, source: Source, hbm, chunk_ids: List[int],
                       chunk_size: int, tail_len: int,
                       device_dtype) -> MemCopyResult:
        """Zero-copy landing (ISSUE 8): the engine's O_DIRECT/io_uring
        reads land straight in an owned :class:`LandingBuffer` and the
        device array becomes an ALIAS of it — no staging hop, every
        delivered byte touched once (``bytes_touched_per_byte_delivered``
        → ~1.0, the reference's BAR1 contract, `kmod/pmemmap.c`).

        The full chunks ride ONE engine command (window-pipelined across
        the member lanes, verified at wait time against the landed buffer
        itself); a partial tail rides its own single-chunk command pinned
        to the final slot.  Write-back (page-cache) chunks get the same
        post-landing verify pass the staging ring applies, because the
        engine's wait-time verify only covers the direct legs."""
        n = len(chunk_ids)
        total = (n - 1) * chunk_size + tail_len
        t0 = time.monotonic_ns()
        landing = LandingBuffer(self.session, total)
        verify = bool(config.get("checksum_verify"))
        adopted = False
        tasks = []                    # (task_id, region_off, region_len)
        unwaited: List[int] = []
        try:
            full = chunk_ids if tail_len == chunk_size else chunk_ids[:-1]
            if full:
                sub = self.session.memcpy_ssd2ram(source, landing.handle,
                                                  full, chunk_size)
                tasks.append((sub.dma_task_id, 0, len(full) * chunk_size))
                unwaited.append(sub.dma_task_id)
            if tail_len != chunk_size:
                sub = self.session.memcpy_ssd2ram(
                    source, landing.handle, [chunk_ids[-1]], chunk_size,
                    dest_offset=(n - 1) * chunk_size)
                tasks.append((sub.dma_task_id, (n - 1) * chunk_size,
                              tail_len))
                unwaited.append(sub.dma_task_id)
            waited = []               # (result, region_off, region_len, id)
            first_err: Optional[BaseException] = None
            for task_id, region, rlen in tasks:
                unwaited.remove(task_id)   # wait reaps, success or failure
                try:
                    res = self.session.memcpy_wait(task_id)
                except StromError as e:
                    if first_err is None:
                        first_err = e
                    continue
                waited.append((res, region, rlen, task_id))
            if first_err is not None:
                raise first_err
            out_ids: List[int] = []
            nr_ssd = nr_ram = 0
            view = landing.view()
            for res, region, rlen, _tid in waited:
                if verify and res.nr_ram2dev:
                    # write-back chunks sit tail-packed in their region
                    # (the per-command positional contract)
                    self._verify_staged(
                        source, res.chunk_ids[res.nr_ssd2dev:], chunk_size,
                        view[region + res.nr_ssd2dev * chunk_size:
                             region + rlen])
                out_ids.extend(res.chunk_ids)
                nr_ssd += res.nr_ssd2dev
                nr_ram += res.nr_ram2dev
            dev = list(hbm.array.devices())[0]
            arr = landing.adopt_array(device_dtype, dev)
            # the adopted alias must be real before it becomes device
            # state: a wedged backend latches loss HERE with ENODEV —
            # the same detection point the staged path gets per H2D fence
            bounded_fence(arr, "landing-adopt")
            hbm.adopt(arr, landing)
            adopted = True
            now = time.monotonic_ns()
            if _tr.active:
                for res, region, rlen, task_id in waited:
                    trid = _tr.traced_id(task_id)
                    if trid:
                        _trace_landing(source, res.chunk_ids, chunk_size,
                                       rlen, "direct", t0, now, trid)
                    _tr.task_end(task_id)
            return MemCopyResult(dma_task_id=0, nr_chunks=n,
                                 nr_ssd2dev=nr_ssd, nr_ram2dev=nr_ram,
                                 chunk_ids=out_ids, landing="direct")
        except BaseException:
            # first-error latch + retention discipline (the staged path's
            # except clause, kmod/nvme_strom.c:770-776): reap what is
            # still in flight, bounded, before surfacing the error
            for task_id in unwaited:
                try:
                    self.session.memcpy_wait(task_id, timeout=5.0)
                except StromError:
                    pass
            raise
        finally:
            if not adopted:
                landing.release()

    def _verify_staged(self, source: Source, chunk_ids: Sequence[int],
                       chunk_size: int, view: memoryview) -> None:
        """Verify heap-page checksums for a landed staging batch.

        ``chunk_ids[i]`` occupies staging bytes ``[i*chunk_size,
        (i+1)*chunk_size)`` (the post-reorder slot contract), which maps a
        bad page straight back to its file offset for the buffered re-read.
        After ``checksum_retries`` failed heals the CORRUPTION error is
        raised — the caller's except path reaps in-flight tasks, so the
        latch discipline matches a direct-read corruption failure."""
        from ..scan.heap import PAGE_SIZE, verify_page_checksums
        if chunk_size % PAGE_SIZE:
            return          # pages straddle chunks: geometry unverifiable
        bad = verify_page_checksums(view)
        rereads = int(config.get("checksum_retries"))
        while bad:
            stats.add("nr_csum_fail", len(bad))
            if rereads <= 0:
                boff = bad[0] * PAGE_SIZE
                foff = (chunk_ids[boff // chunk_size] * chunk_size
                        + boff % chunk_size)
                raise StromError(
                    _errno.EBADMSG,
                    f"page checksum mismatch in staging ring at file offset "
                    f"{foff} ({len(bad)} bad page(s), re-reads exhausted)")
            rereads -= 1
            stats.add("nr_csum_reread", len(bad))
            stats.add("bytes_verify_reread", len(bad) * PAGE_SIZE)
            for p in bad:
                boff = p * PAGE_SIZE
                foff = (chunk_ids[boff // chunk_size] * chunk_size
                        + boff % chunk_size)
                source.read_buffered(foff, view[boff:boff + PAGE_SIZE])
            bad = verify_page_checksums(view)

    def drain(self) -> None:
        """Block until every outstanding device op has completed (bounded
        — a dead backend raises ENODEV instead of hanging)."""
        for i, b in enumerate(self._barriers):
            if b is not None:
                bounded_fence(b, "staging-drain")
                self._barriers[i] = None

    def close(self) -> None:
        for i, b in enumerate(self._barriers):
            if b is not None:
                try:
                    bounded_fence(b, "staging-close")
                except StromError:
                    # per-barrier: an ENOMEM on one array must not skip
                    # the other buffers' drains; a latched loss fails
                    # the rest instantly anyway
                    pass
                self._barriers[i] = None
        for handle, buf in self._bufs:
            try:
                self.session.unmap_buffer(handle)
            except StromError:
                pass
            buf.close()
        self._bufs.clear()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def load_file_to_device(source: Source, *, chunk_size: Optional[int] = None,
                        session: Optional[Session] = None,
                        device: Optional[jax.Device] = None,
                        dtype=jnp.uint8,
                        staging_bytes: Optional[int] = None,
                        hbm_registry: Optional[HbmRegistry] = None) -> jax.Array:
    """One-call SSD→HBM load of an entire source (the ssd2tpu 'happy path').

    Allocates a device buffer of the source's (dtype-truncated) size, streams
    every chunk through the staging pipeline, and returns the device array.
    The load's own set-up and tear-down (destination, staging ring, the
    final fence, unmap) is one ``load_setup`` stage entry in several
    pieces.
    """
    chunk_size = chunk_size or min(config.get("chunk_size"), 1 << 20)
    reg = hbm_registry or global_registry
    itemsize = np.dtype(dtype).itemsize
    if source.size % itemsize:
        raise StromError(22, f"source size {source.size} not a multiple of "
                             f"dtype itemsize {itemsize}")
    n_elems = source.size // itemsize
    own_session = session is None
    sess = session or Session()
    try:
        with stats.stage("load_setup"):
            handle = reg.map_device_memory(n_elems, dtype=dtype,
                                           device=device)
        try:
            n_chunks = (source.size + chunk_size - 1) // chunk_size
            with stats.stage("load_setup", n=0):
                pipe = StagingPipeline(sess, staging_bytes=staging_bytes,
                                       hbm_registry=reg)
            try:
                # a non-multiple file tail rides the pipeline as a partial
                # final chunk (ISSUE 8) — no separate pinned hop
                pipe.memcpy_ssd2dev(source, handle, list(range(n_chunks)),
                                    chunk_size, device_dtype=dtype)
            finally:
                with stats.stage("load_setup", n=0):
                    pipe.close()
            arr = reg.get(handle).array
            with stats.stage("load_setup", n=0):
                arr.block_until_ready()
            return arr
        finally:
            with stats.stage("load_setup", n=0):
                reg.unmap(handle)
    finally:
        if own_session:
            sess.close()
