"""Ring-streaming scan: rotate page blocks around the mesh with ppermute.

The long-sequence scaling substrate (SURVEY.md SS5.7 maps the reference's
chunked/bounded-depth streaming onto the TPU).  For a *single* commutative
aggregate, sharding + psum (:mod:`.dscan`) is optimal.  The ring earns its
keep when every device needs to see the **whole** stream but no device can
hold it — the same access pattern as ring attention (each query block
visits every KV block): here, N *different* scan queries each need the
full table, and each device holds only 1/N of the pages.

Topology: each device starts with its local page shard and its own query
(threshold).  At every step it aggregates its query over the resident
block, then forwards the block to its ring neighbour with
``jax.lax.ppermute`` — the collective rides ICI, communication overlaps
the next block's compute (XLA schedules the ppermute DMA concurrently),
and after ``dp`` steps every query has seen every page with per-device
memory = one shard + one in-flight block.

Peak per-device memory stays O(B/dp) regardless of table size, which is
exactly the property ring attention buys for sequence length.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from ..config import config
from ..ops.filter_xla import DEFAULT_SCHEMA, decode_pages
from ..scan.heap import HeapSchema
from .mesh import make_scan_mesh

__all__ = ["make_ring_multi_query_scan", "ring_scan_source",
           "permute_backend", "ring_permute_step", "ring_all_gather"]


def _mark_varying(x, axis: str):
    """Mark *x* as axis-varying so scan carries type-match a rotating
    (varying) block."""
    return jax.lax.pcast(x, axis, to="varying")


# ---------------------------------------------------------------------------
# Generalized ring permute (ISSUE 17): one rotation step usable inside any
# shard_map'ed body.  Two transports behind one call:
#
# * ``pallas`` — a Pallas kernel built on ``pltpu.make_async_remote_copy``
#   (SNIPPETS.md [2] shape): src/dst refs live in ``pl.ANY`` (HBM —
#   the landing buffers the sharded loader adopts are HBM-resident), a
#   paired send/recv DMA-semaphore pledge fences the device-to-device copy,
#   and the neighbour is addressed by LOGICAL device id computed from the
#   mesh axis index — the transfer rides ICI without bouncing through the
#   host exchange path.
# * ``xla`` — ``jax.lax.ppermute``, the collective XLA lowers to the same
#   ICI rotation on TPU and to a mesh copy on the CPU virtual mesh; it is
#   the correctness oracle the pallas path must match and the only
#   transport a non-TPU backend can run.
#
# ``config ici_permute`` picks: ``auto`` (pallas on a TPU backend, xla
# elsewhere), or pin either for A/B and tests.
# ---------------------------------------------------------------------------

def permute_backend(backend: Optional[str] = None) -> str:
    """Resolve the ring-permute transport: explicit *backend* wins, else
    ``config ici_permute`` (``auto`` = pallas iff running on TPU)."""
    b = backend or str(config.get("ici_permute"))
    if b == "auto":
        b = "pallas" if jax.default_backend() == "tpu" else "xla"
    if b not in ("pallas", "xla"):
        raise ValueError(f"ici_permute backend {b!r} (want pallas|xla|auto)")
    return b


def _pallas_permute_step(block, axis: str, ring: int):
    """One +1 ring rotation as semaphore-paired async remote DMA."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(src_ref, dst_ref, send_sem, recv_sem):
        # neighbour by LOGICAL id from this device's own axis position:
        # the kernel is mesh-shape generic, nothing is baked in
        me = jax.lax.axis_index(axis)
        copy = pltpu.make_async_remote_copy(
            src_ref=src_ref, dst_ref=dst_ref,
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=jax.lax.rem(me + 1, ring),
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        copy.start()
        copy.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
    )
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(block.shape, block.dtype),
        grid_spec=grid_spec)(block)


def ring_permute_step(block, *, axis: str, ring: int,
                      backend: Optional[str] = None):
    """Rotate *block* one step (+1) around the *axis* ring; call from
    INSIDE a shard_map'ed body.  The two transports are byte-equivalent;
    only the lane differs (Pallas remote DMA vs the XLA collective)."""
    if permute_backend(backend) == "pallas":
        return _pallas_permute_step(block, axis, ring)
    perm = [(i, (i + 1) % ring) for i in range(ring)]
    return jax.lax.ppermute(block, axis, perm)


#: compiled ring programs keyed by (mesh, axis, shape, dtype, transport).
#: The sharded loader and the cold-start handshake call per batch; a
#: fresh closure per call would defeat jax's jit cache and pay a full
#: retrace each time — for a small batch the retrace would cost more
#: than its I/O.  Meshes hash by value, so
#: same-shape calls across Mesh instances share one program.
_ring_jit_cache: dict = {}


def ring_all_gather(arr, mesh: Mesh, *, axis: str = "dp",
                    backend: Optional[str] = None):
    """All-gather an ``P(axis, ...)``-sharded global array by ring
    rotation: after ``ring-1`` permute steps every device has placed
    every shard, so the result is fully replicated.  This is the
    on-fabric gather lane the sharded cold-start ends with — shards
    move device-to-device over ICI (pallas) or the ppermute collective
    (xla), never through host exchange.  Returns the gathered array
    (leading axis = ring * shard_rows), replicated over *axis*."""
    ring = mesh.shape[axis]
    backend = permute_backend(backend)
    key = ("gather", mesh, axis, tuple(arr.shape), str(arr.dtype), backend)
    cached = _ring_jit_cache.get(key)
    if cached is not None:
        return cached(arr)

    def _local(x):
        rows = x.shape[0]
        me = jax.lax.axis_index(axis)
        out = jnp.zeros((ring * rows,) + x.shape[1:], x.dtype)

        def body(carry, step):
            block, out = carry
            # after s rotations the resident block originated at
            # (me - s) mod ring — place it at that shard's row range
            src = jax.lax.rem(me - step + ring, ring)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, block, src * rows, axis=0)
            block = ring_permute_step(block, axis=axis, ring=ring,
                                      backend=backend)
            return (block, out), None

        (block, out), _ = jax.lax.scan(
            body, (x, _mark_varying(out, axis)),
            jnp.arange(ring, dtype=jnp.int32))
        return out

    n_spec = (None,) * (arr.ndim - 1)
    fn = jax.jit(shard_map(
        _local, mesh=mesh,
        in_specs=P(axis, *n_spec),
        out_specs=P(*((None,) + n_spec)),
        check_vma=False))
    _ring_jit_cache[key] = fn
    return fn(arr)


def make_ring_multi_query_scan(devices: Optional[Sequence[jax.Device]] = None,
                               *, schema: HeapSchema = DEFAULT_SCHEMA,
                               predicate=None):
    """Build the jitted ring scan over a 1-D dp mesh.

    Returns ``(run, mesh)``.  ``run(pages_np, thresholds_np)`` takes a page
    batch (leading axis divisible by the ring size) and one threshold per
    device; result ``{"count": (dp,), "sums": (dp, n_cols)}`` holds, for
    each query *q*, the aggregate over the ENTIRE page batch.

    *predicate* as in :func:`..parallel.dscan.make_distributed_scan_step`.
    """
    mesh = make_scan_mesh(devices, sp=1)
    ring = mesh.shape["dp"]
    pred = predicate or (lambda cols, th: cols[0] > th)
    n_cols = schema.n_cols
    perm = [(i, (i + 1) % ring) for i in range(ring)]

    def _local(pages_u8, threshold):
        # threshold: (1,) — this device's own query
        th = threshold[0]

        def body(carry, _):
            block, count, sums = carry
            cols, valid = decode_pages(block, schema)
            sel = valid & pred(cols, th)
            count = count + jnp.sum(sel.astype(jnp.int32))
            sums = sums + jnp.stack([jnp.sum(jnp.where(sel, c, 0))
                                     for c in cols])
            # forward the resident block to the next ring member; the
            # rotation is what lets every query visit every page
            block = jax.lax.ppermute(block, "dp", perm)
            return (block, count, sums), None

        # accumulators are per-device state: mark them dp-varying so the
        # scan carry types match the rotating (varying) block
        init = (pages_u8,
                _mark_varying(jnp.int32(0), "dp"),
                _mark_varying(jnp.zeros((n_cols,), jnp.int32), "dp"))
        (block, count, sums), _ = jax.lax.scan(body, init, None, length=ring)
        # leading axis 1: shard_map concatenates over the mesh into (dp,...)
        return {"count": count[None], "sums": sums[None]}

    shard_mapped = shard_map(
        _local, mesh=mesh,
        in_specs=(P("dp", None), P("dp")),
        out_specs={"count": P("dp"), "sums": P("dp", None)})
    step = jax.jit(shard_mapped)

    def run(pages_np: np.ndarray, thresholds_np: np.ndarray):
        if len(thresholds_np) != ring:
            raise ValueError(f"need {ring} thresholds (one per ring member), "
                             f"got {len(thresholds_np)}")
        pages = jax.device_put(pages_np, NamedSharding(mesh, P("dp", None)))
        ths = jax.device_put(np.asarray(thresholds_np, np.int32),
                             NamedSharding(mesh, P("dp")))
        return step(pages, ths)

    run.step = step
    return run, mesh


def ring_scan_source(source, thresholds_np: np.ndarray, *,
                     batch_pages: int,
                     devices: Optional[Sequence[jax.Device]] = None,
                     schema: HeapSchema = DEFAULT_SCHEMA,
                     predicate=None, session=None) -> dict:
    """Stream a source through the ring scan: the long-sequence shape.

    The table can exceed total HBM: each batch is direct-loaded dp-sharded
    (submit-ahead double buffering, `.stream.ShardedBatchStream`), rotated
    around the ring so every query aggregates over every page, and folded.
    Peak per-device memory stays O(batch/dp) however long the source is —
    ring attention's memory property applied to the scan.

    Returns ``{"count": (dp,), "sums": (dp, n_cols)}`` over the whole
    source (tail pages that do not fill a batch are scanned via a final
    padded batch, so nothing is dropped).
    """
    from .stream import ShardedBatchStream
    from ..scan.heap import PAGE_SIZE

    run, mesh = make_ring_multi_query_scan(devices, schema=schema,
                                           predicate=predicate)
    dp = mesh.shape["dp"]
    if batch_pages % dp:
        raise ValueError(f"batch_pages {batch_pages} must divide by dp {dp}")
    acc = None
    step = run.step
    ths = jax.device_put(np.asarray(thresholds_np, np.int32),
                         NamedSharding(mesh, P("dp")))

    def fold(pages_global):
        nonlocal acc
        out = step(pages_global, ths)
        acc = out if acc is None else jax.tree.map(lambda a, b: a + b,
                                                   acc, out)

    n_pages = source.size // PAGE_SIZE
    covered = 0
    with ShardedBatchStream(source, mesh, batch_pages=batch_pages,
                            session=session) as stream:
        for first, arr in stream:
            fold(arr)
            covered = first + batch_pages
    if covered < n_pages:
        # tail: pad with zero pages (n_tuples == 0 contributes nothing)
        tail = np.zeros((batch_pages, PAGE_SIZE), np.uint8)
        nbytes = (n_pages - covered) * PAGE_SIZE
        view = np.empty(nbytes, np.uint8)
        source.read_buffered(covered * PAGE_SIZE, memoryview(view))
        tail[:n_pages - covered] = view.reshape(-1, PAGE_SIZE)
        fold(jax.device_put(tail, NamedSharding(mesh, P("dp", None))))
    # per-leaf: heterogeneous list leaves keep their acc dtypes
    return {} if acc is None else jax.tree.map(np.asarray, acc)
