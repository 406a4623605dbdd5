"""Multi-host sharded loading: per-host local reads + on-fabric shard moves.

The scale-out story (ISSUE 17, ROADMAP item 3): the reference saturates
one host's PCIe by giving every SSD's DMA engine a direct lane into
device memory; the TPU analog of "add another SSD" is "add another
host".  Here the file's chunk grid is split by a host→member ownership
map derived from the stripe config (:func:`..engine.plan_shard_ownership`
over :func:`..stripe.host_of` — the userspace mirror of the reference's
md-RAID-0 member math, ``kmod/nvme_strom.c:823-910``), each host's
engine session reads ONLY the extent shards its local NVMe set holds,
lands them in per-host device memory via the existing zero-copy landing
path, and the shards then move **device-to-device over ICI** with the
generalized ring permute (:func:`..parallel.ring.ring_permute_step`:
Pallas ``make_async_remote_copy`` on TPU, ``ppermute`` elsewhere) —
aggregate GB/s divides the file across per-host NVMe queues, and the
redistribution never bounces through host exchange.

Emulation note: a "host" here is a planning unit — on a real multi-host
mesh it is one process (``jax.process_index()``) with its own NVMe set;
on the virtual single-process mesh the loader runs one reader thread +
engine session per virtual host (per-host submission windows are the
bound on a latency-bound source, so wall time divides by host count).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..api import StromError
from ..config import config
from ..engine import Session, Source, plan_shard_ownership, reorder_chunks
from ..hbm.staging import safe_device_put
from ..scan.heap import PAGE_SIZE
from ..stats import stats
from ..trace import recorder as _trace
from jax import shard_map
from .ring import _mark_varying, permute_backend, ring_all_gather, \
    ring_permute_step

__all__ = ["load_pages_multihost", "shard_ownership"]


def shard_ownership(source: Source, n_hosts: int,
                    *, chunk_size: int = PAGE_SIZE) -> Dict[int, List[int]]:
    """Host → owned chunk ids for the whole of *source* (planner entry
    the tests assert partition correctness against): disjoint,
    exhaustive, member-aligned on striped sources, contiguous-range on
    single-member ones."""
    n_chunks = source.size // chunk_size
    return plan_shard_ownership(source, range(n_chunks), chunk_size, n_hosts)


#: engine tasks a host keeps in flight while reading its shard
_TASK_WINDOW = 4


def _read_host_shard(host: int, ids: List[int], source: Source,
                     session: Optional[Session]) -> np.ndarray:
    """One host's local read: submit the owned chunk grid through this
    host's OWN engine session, wait, restore caller order.  Returns an
    owned (len(ids), PAGE_SIZE) array (copied out before the pinned
    buffer unmaps).

    The grid goes out as one engine task per ``config chunk_size`` of
    pages, ``_TASK_WINDOW`` in flight: the task deadline
    (``task_deadline_s``) bounds one task, and a single task for a whole
    GiB shard of 8 KiB pages overran it on a page-cached heap (PR 21)."""
    if not ids:
        return np.empty((0, PAGE_SIZE), np.uint8)
    own = session is None
    sess = session or Session()
    ts = time.monotonic_ns()
    per_task = max(1, int(config.get("chunk_size")) // PAGE_SIZE)
    host_rows = np.empty((len(ids), PAGE_SIZE), np.uint8)
    try:
        nbytes = len(ids) * PAGE_SIZE
        handle, buf = sess.alloc_dma_buffer(nbytes)
        view = np.frombuffer(buf.view()[:nbytes], np.uint8)
        inflight: deque = deque()

        def retire() -> None:
            lo, part, res = inflight.popleft()
            sess.memcpy_wait(res.dma_task_id)
            raw = view[lo * PAGE_SIZE:(lo + len(part)) * PAGE_SIZE]
            host_rows[lo:lo + len(part)] = reorder_chunks(
                raw, PAGE_SIZE, res.chunk_ids, part).reshape(-1, PAGE_SIZE)

        try:
            for lo in range(0, len(ids), per_task):
                part = ids[lo:lo + per_task]
                inflight.append((lo, part, sess.memcpy_ssd2ram(
                    source, handle, part, PAGE_SIZE,
                    dest_offset=lo * PAGE_SIZE)))
                if len(inflight) >= _TASK_WINDOW:
                    retire()
            while inflight:
                retire()
        finally:
            del view
            sess.unmap_buffer(handle)
            buf.close()
    finally:
        if own:
            sess.close()
    stats.add("nr_shard_load")
    stats.add("bytes_shard_load", len(ids) * PAGE_SIZE)
    if _trace.active:
        _trace.span("shard_load", ts, time.monotonic_ns(),
                    length=len(ids) * PAGE_SIZE,
                    args={"host": host, "chunks": len(ids)})
    return host_rows


#: compiled redistribution programs keyed by (mesh, axis, rows_max,
#: rows_per_dev, transport) — a fresh jit closure per load would retrace
#: the ring scan every batch, and the retrace would dwarf the I/O of a
#: small batch.  Meshes hash by value.
_redistribute_cache: dict = {}


def _make_redistribute(mesh: Mesh, axis: str, rows_max: int,
                       rows_per_dev: int, backend: Optional[str]):
    """Jit the ring redistribution: each device starts with one padded
    (data, idx) block of its host's locally-read pages, rotates it all
    the way around the *axis* ring, and scatters the rows whose file
    position lands in its own output range — after ``ring`` steps every
    page has visited its destination, so the output is the row-sharded
    file-order array, byte-identical to a single-host load."""
    ring = mesh.shape[axis]
    backend = permute_backend(backend)
    key = (mesh, axis, rows_max, rows_per_dev, backend)
    cached = _redistribute_cache.get(key)
    if cached is not None:
        return cached

    def _local(data, idx):
        me = jax.lax.axis_index(axis)
        # +1 dummy row: rows owned by other devices (and -1 padding)
        # scatter there and are dropped, so the write stays dense
        out = jnp.zeros((rows_per_dev + 1, PAGE_SIZE), jnp.uint8)

        def body(carry, _):
            data, idx, out = carry
            dest = idx - me * rows_per_dev
            ok = (idx >= 0) & (dest >= 0) & (dest < rows_per_dev)
            slot = jnp.where(ok, dest, rows_per_dev)
            out = out.at[slot].set(data)
            data = ring_permute_step(data, axis=axis, ring=ring,
                                     backend=backend)
            idx = ring_permute_step(idx, axis=axis, ring=ring,
                                    backend=backend)
            return (data, idx, out), None

        (_d, _i, out), _ = jax.lax.scan(
            body, (data, idx, _mark_varying(out, axis)), None, length=ring)
        return out[:rows_per_dev]

    fn = jax.jit(shard_map(
        _local, mesh=mesh,
        in_specs=(P(axis, None), P(axis)),
        out_specs=P(axis, None), check_vma=False))
    _redistribute_cache[key] = fn
    return fn


def load_pages_multihost(source: Source, mesh: Mesh, *,
                         hosts: Optional[int] = None,
                         axis: str = "dp",
                         session: Optional[Session] = None,
                         source_factory: Optional[Callable[[int], Source]]
                         = None,
                         backend: Optional[str] = None,
                         gather: bool = False) -> jax.Array:
    """Load a page-formatted source through *hosts* sharded engine
    sessions and redistribute over the fabric.

    Phase 1 (per-host NVMe): the chunk grid is split by the
    host-ownership map; each host's reader thread submits only its own
    chunks through its own session (``source_factory(h)`` opens that
    host's local view of the source — default: share *source*, which is
    the single-filesystem emulation).  Phase 2 (ICI): the landed shards
    rotate around the mesh ring (``config ici_permute`` transport) and
    every device keeps the rows of its final file-order range.

    Returns the ``(n_pages, PAGE_SIZE)`` global array sharded
    ``P(axis, None)`` — byte-identical to
    :func:`..parallel.stream.load_pages_sharded` of the same source —
    or, with ``gather=True``, the fully-replicated gathered array (the
    cold-start all-gather shape).
    """
    if source.size % PAGE_SIZE:
        raise StromError(22, f"source size {source.size} not page-aligned")
    n_pages = source.size // PAGE_SIZE
    n_dev = mesh.shape[axis]
    if n_pages % n_dev:
        raise StromError(22, f"{n_pages} pages not divisible by {n_dev} "
                             f"'{axis}' shards; pad the source")
    hosts = int(hosts or config.get("shard_hosts") or 1)
    if hosts < 1 or n_dev % hosts:
        raise StromError(22, f"host count {hosts} must divide the {n_dev}"
                             f"-device '{axis}' axis")
    rows_per_dev = n_pages // n_dev
    dev_per_host = n_dev // hosts

    owned = shard_ownership(source, hosts)

    # -- phase 1: per-host local reads, one engine session each --------
    host_rows: List[Optional[np.ndarray]] = [None] * hosts
    errors: List[BaseException] = []

    def _run(h: int) -> None:
        src = source_factory(h) if source_factory else source
        try:
            host_rows[h] = _read_host_shard(
                h, owned[h], src,
                session if (session is not None and hosts == 1) else None)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)
        finally:
            if source_factory:
                src.close()

    if hosts == 1:
        _run(0)
    else:
        threads = [threading.Thread(target=_run, args=(h,),
                                    name=f"strom-shardload-{h}")
                   for h in range(hosts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    if errors:
        raise errors[0]

    # -- split each host's rows across its device group ----------------
    per_dev: List[tuple] = []      # axis position -> (pages, ids)
    for h in range(hosts):
        rows, ids = host_rows[h], owned[h]
        q, r = divmod(len(ids), dev_per_host)
        pos = 0
        for k in range(dev_per_host):
            take = q + (1 if k < r else 0)
            per_dev.append((rows[pos:pos + take], ids[pos:pos + take]))
            pos += take
    rows_max = max(1, max(len(ids) for _, ids in per_dev))

    data_shape = (n_dev * rows_max, PAGE_SIZE)
    idx_shape = (n_dev * rows_max,)
    data_sharding = NamedSharding(mesh, P(axis, None))
    idx_sharding = NamedSharding(mesh, P(axis))
    data_map = data_sharding.addressable_devices_indices_map(data_shape)
    idx_map = idx_sharding.addressable_devices_indices_map(idx_shape)

    data_shards = []
    idx_shards = {}
    for dev, sl in data_map.items():
        p = (sl[0].start or 0) // rows_max
        pages, ids = per_dev[p]
        block = np.zeros((rows_max, PAGE_SIZE), np.uint8)
        block[:len(ids)] = pages
        index = np.full((rows_max,), -1, np.int32)
        index[:len(ids)] = ids
        data_shards.append(safe_device_put(block, dev))
        idx_shards[dev] = safe_device_put(index, dev)
    data_g = jax.make_array_from_single_device_arrays(
        data_shape, data_sharding, data_shards)
    idx_g = jax.make_array_from_single_device_arrays(
        idx_shape, idx_sharding,
        [idx_shards[dev] for dev in idx_map])

    # -- phase 2: on-fabric redistribution ------------------------------
    step = _make_redistribute(mesh, axis, rows_max, rows_per_dev, backend)
    ts = time.monotonic_ns()
    out = step(data_g, idx_g)
    out.block_until_ready()
    n_addr = len(data_map)
    moved = n_dev * n_addr * rows_max * (PAGE_SIZE + 4)
    stats.add("nr_ici_permute", n_dev)
    stats.add("bytes_ici", moved)
    if _trace.active:
        _trace.span("ici_permute", ts, time.monotonic_ns(), length=moved,
                    args={"steps": n_dev, "ring": n_dev,
                          "backend": permute_backend(backend),
                          "hosts": hosts})
    if gather:
        ts = time.monotonic_ns()
        gathered = ring_all_gather(out, mesh, axis=axis, backend=backend)
        gathered.block_until_ready()
        moved = n_dev * n_addr * rows_per_dev * PAGE_SIZE
        stats.add("nr_ici_permute", n_dev)
        stats.add("bytes_ici", moved)
        if _trace.active:
            _trace.span("ici_permute", ts, time.monotonic_ns(),
                        length=moved,
                        args={"steps": n_dev, "ring": n_dev,
                              "backend": permute_backend(backend),
                              "hosts": hosts, "gather": True})
        return gathered
    return out
