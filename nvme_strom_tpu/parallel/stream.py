"""Sharded direct loading: stripe a source across the device mesh.

The RAID-0 fan-out analog over the mesh (SURVEY.md SS5.8c): where the
reference stripes one logical stream across NVMe members in-kernel
(`kmod/nvme_strom.c:823-910`), here the *destination* is striped — every
device owns a disjoint page range of the global array, and each process
direct-loads only the ranges of its **addressable** devices, so the loader
is multi-host correct by construction (each host reads its own shard from
its own storage; no cross-host data moves at load time — the collectives
that later consume the array ride ICI/DCN).

The global array is assembled with
``jax.make_array_from_single_device_arrays`` — no host ever materializes
the full table.
"""

from __future__ import annotations

import errno as _errno
import time
from typing import List, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..api import StromError
from ..config import config
from ..engine import Session, Source, reorder_chunks
from ..hbm.staging import safe_device_put
from ..scan.heap import PAGE_SIZE
from ..stats import stats
from ..trace import recorder

__all__ = ["load_pages_sharded", "ShardedBatchStream", "distributed_scan_filter"]


def load_pages_sharded(source: Source, mesh: Mesh, *,
                       session: Optional[Session] = None,
                       axis: str = "dp") -> jax.Array:
    """Direct-load a page-formatted source into a (n_pages, PAGE_SIZE)
    global array sharded over *axis* of *mesh*.

    Each addressable device's row range is read through the engine's
    direct path (page-granular chunks) into a pinned buffer and placed on
    that device; the returned global array is sharded ``P(axis, None)``.
    ``n_pages`` must divide evenly by the axis size.
    """
    if source.size % PAGE_SIZE:
        raise StromError(22, f"source size {source.size} not page-aligned")
    n_pages = source.size // PAGE_SIZE
    n_shards = mesh.shape[axis]
    if n_pages % n_shards:
        raise StromError(22, f"{n_pages} pages not divisible by {n_shards} "
                             f"'{axis}' shards; pad the source")
    sharding = NamedSharding(mesh, P(axis, None))
    global_shape = (n_pages, PAGE_SIZE)
    idx_map = sharding.addressable_devices_indices_map(global_shape)

    own_session = session is None
    sess = session or Session()
    shards = []
    try:
        for dev, idx in idx_map.items():
            rows = idx[0]
            r0 = rows.start or 0
            r1 = rows.stop if rows.stop is not None else n_pages
            nbytes = (r1 - r0) * PAGE_SIZE
            handle, buf = sess.alloc_dma_buffer(nbytes)
            try:
                want = list(range(r0, r1))
                res = sess.memcpy_ssd2ram(source, handle, want, PAGE_SIZE)
                sess.memcpy_wait(res.dma_task_id)
                host = reorder_chunks(
                    np.frombuffer(buf.view()[:nbytes], np.uint8),
                    PAGE_SIZE, res.chunk_ids, want).reshape(r1 - r0,
                                                            PAGE_SIZE)
                shards.append(safe_device_put(host, dev))
            finally:
                sess.unmap_buffer(handle)
                buf.close()
        return jax.make_array_from_single_device_arrays(
            global_shape, sharding, shards)
    finally:
        if own_session:
            sess.close()


class ShardedBatchStream:
    """Stream fixed-size page batches to the mesh with submit-ahead DMA.

    The distributed form of the executor's async ring (`pgsql/nvme_strom.c:
    862-936`): while the consumer's step runs on batch *b*, batch *b+1*'s
    SSD DMAs are already in flight into a second set of pinned buffers
    (one double-buffer pair per addressable device).  Buffer reuse is
    fenced on the previous batch's device arrays being ready — the H2D
    read must complete before the SSD engine overwrites the pinned pages.

    Yields ``(first_page, global_array)`` with the array sharded
    ``P(axis, None)`` over *mesh* — ready for a shard_map'ed step.
    ``batch_pages`` must divide by the axis size; the final partial batch
    is dropped if it cannot fill every shard evenly (callers scan tails
    separately, as with the executor's tail path).
    """

    def __init__(self, source: Source, mesh: Mesh, *, batch_pages: int,
                 session: Optional[Session] = None, axis: str = "dp"):
        n_shards = mesh.shape[axis]
        if batch_pages <= 0 or batch_pages % n_shards:
            raise StromError(22, f"batch_pages {batch_pages} must divide by "
                                 f"{n_shards} '{axis}' shards")
        if source.size % PAGE_SIZE:
            raise StromError(22, "source size not page-aligned")
        self.source = source
        self.mesh = mesh
        self.axis = axis
        self.batch_pages = batch_pages
        self.n_pages = source.size // PAGE_SIZE
        self.n_batches = self.n_pages // batch_pages
        self.sharding = NamedSharding(mesh, P(axis, None))
        self._own_session = session is None
        self.session = session or Session()
        self._shape = (batch_pages, PAGE_SIZE)
        self._idx = list(self.sharding.addressable_devices_indices_map(
            self._shape).items())
        per_shard = batch_pages // n_shards
        # a shard's pages of a step are read as engine chunks of the
        # largest power-of-two page count that divides them, up to the
        # engine's chunk size: one chunk a shard a step by default, so the
        # engine plans and probes a step's 8 MiB once, not page by page
        self._chunk_pages = min(per_shard & -per_shard,
                                max(config.get("chunk_size") // PAGE_SIZE,
                                    1))
        # double buffering: ring of 2 pinned buffers per addressable shard
        self._bufs = [[self.session.alloc_dma_buffer(per_shard * PAGE_SIZE)
                       for _ in range(2)] for _ in self._idx]
        self._fence: List[Optional[jax.Array]] = [None, None]

    def _submit(self, b: int):
        from ..hbm.staging import bounded_fence
        ring = b % 2
        if self._fence[ring] is not None:
            # bounded: a dead backend fails the stream with ENODEV
            # instead of hanging the double-buffer rotation
            with stats.stage("staging_fence"):
                bounded_fence(self._fence[ring], "mesh-h2d")
            self._fence[ring] = None
        tasks = []
        base = b * self.batch_pages
        with stats.stage("mesh_submit"):
            for k, (dev, idx) in enumerate(self._idx):
                rows = idx[0]
                r0 = base + (rows.start or 0)
                r1 = base + (rows.stop if rows.stop is not None
                             else self.batch_pages)
                handle, _buf = self._bufs[k][ring]
                cp = self._chunk_pages
                res = self.session.memcpy_ssd2ram(
                    self.source, handle, list(range(r0 // cp, r1 // cp)),
                    cp * PAGE_SIZE)
                # submit timestamp rides with the task: the fan-in loop
                # below turns it into the per-shard wait distribution
                tasks.append((dev, res, time.monotonic_ns()))
        return ring, tasks

    def _collect(self, ring, tasks) -> jax.Array:
        shards: List[Optional[jax.Array]] = [None] * len(tasks)

        def account(k) -> None:
            # straggler attribution (ISSUE 17): the batch is gated on its
            # SLOWEST shard, so record each shard's submit->completion
            # wait where the aggregate histogram can't smear it — one
            # log2-ns histogram per mesh shard plus a flight-recorder span
            t1 = time.monotonic_ns()
            stats.shard_wait(k, t1 - tasks[k][2])
            if recorder.active:
                recorder.span("shard_wait", tasks[k][2], t1,
                              args={"shard": k})

        def wait(k, timeout):
            """Shard *k*'s finished task, or None if a poll times out."""
            with stats.stage("mesh_wait"):
                try:
                    return self.session.memcpy_wait(
                        tasks[k][1].dma_task_id, timeout=timeout)
                except StromError as e:
                    if e.errno == _errno.ETIMEDOUT:
                        return None
                    raise

        def place(k, done) -> None:
            account(k)
            _handle, buf = self._bufs[k][ring]
            # one staging_retire entry a step (counted at the assembly);
            # the shards' placements add their time to it
            with stats.stage("staging_retire", n=0):
                # slot i holds chunk chunk_ids[i]: with a partially cached
                # source the engine fronts direct-I/O chunks and tails
                # write-back chunks, so restore file order before placement
                host = reorder_chunks(np.frombuffer(buf.view(), np.uint8),
                                      self._chunk_pages * PAGE_SIZE,
                                      done.chunk_ids,
                                      sorted(done.chunk_ids)).reshape(
                                          -1, PAGE_SIZE)
                shards[k] = safe_device_put(host, tasks[k][0])

        # completion fan-in (PR 5): with per-member engine lanes the
        # shards' SSD DMAs finish independently, so start each device's
        # H2D as soon as ITS shard lands instead of serializing the whole
        # batch behind shard 0's lane
        remaining = list(range(len(tasks)))
        while remaining:
            progressed = False
            for k in list(remaining):
                done = wait(k, 0.0)
                if done is None:
                    continue
                place(k, done)
                remaining.remove(k)
                progressed = True
            if remaining and not progressed:
                k = remaining.pop(0)
                place(k, wait(k, None))
        with stats.stage("staging_retire"):
            arr = jax.make_array_from_single_device_arrays(
                self._shape, self.sharding, shards)
        self._fence[ring] = arr
        return arr

    def __iter__(self):
        if self.n_batches == 0:
            return
        pending = self._submit(0)
        for b in range(self.n_batches):
            nxt = self._submit(b + 1) if b + 1 < self.n_batches else None
            arr = self._collect(*pending)
            yield b * self.batch_pages, arr
            pending = nxt

    def close(self) -> None:
        for ring in self._bufs:
            for handle, buf in ring:
                try:
                    self.session.unmap_buffer(handle)
                except StromError:
                    pass
                buf.close()
        self._bufs = []
        if self._own_session:
            self.session.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def distributed_scan_filter(source: Source, mesh: Mesh, step, *,
                            batch_pages: int,
                            session: Optional[Session] = None,
                            combine=None) -> dict:
    """Fold a shard_map'ed *step* over the source, streamed batch-wise.

    ``step(global_pages, ...)``-style callables from
    :func:`..parallel.dscan.make_distributed_scan_step` take the threshold
    positionally; here *step* is ``step(global_pages) -> dict`` (bind any
    parameters with a lambda).  Results are summed per key (or folded with
    *combine*).  This is the pgsql parallel SeqScan shape at mesh scale:
    bounded memory (2 pinned buffers per shard + 1 resident batch per
    device), SSD DMA / H2D / device compute all overlapped.
    """
    from ..scan.executor import fold_results

    acc = None
    with ShardedBatchStream(source, mesh, batch_pages=batch_pages,
                            session=session) as stream:
        for _first, arr in stream:
            # the step's kernel call and fold end its staging_retire
            with stats.stage("staging_retire", n=0):
                acc = fold_results(acc, step(arr), combine)
            stats.add("nr_kernel_dispatch")
    # per-leaf: heterogeneous list leaves keep their acc dtypes
    return {} if acc is None else jax.tree.map(np.asarray, acc)
