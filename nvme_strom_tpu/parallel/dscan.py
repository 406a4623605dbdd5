"""Distributed scan: mesh-sharded page batches + collective aggregation.

The reference's multi-worker scan shares an atomic block cursor over DSM and
each PostgreSQL worker scans a disjoint page subset (`pgsql/nvme_strom.c:
1057-1112`).  The TPU-native generalization is SPMD over a 2-D mesh
(:mod:`.mesh`):

* pages shard across ``dp`` (each device filters a disjoint page subset —
  the worker-cursor analog),
* wide schemas split their columns across ``sp`` lanes (each lane
  aggregates only its own columns — tensor parallelism for tabular data),

and the per-shard aggregates combine with ``psum`` over ICI — process
parallelism replaced by XLA collectives (SURVEY.md SS5.8).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.filter_xla import DEFAULT_SCHEMA, decode_pages
from ..scan.heap import HeapSchema
from jax import shard_map
from .mesh import make_scan_mesh, pages_sharding

__all__ = ["make_distributed_scan_step", "shard_pages"]


def make_distributed_scan_step(devices: Optional[Sequence[jax.Device]] = None,
                               *, sp: int = 1,
                               schema: HeapSchema = DEFAULT_SCHEMA,
                               predicate=None):
    """Build the jitted distributed scan step over a ``(sp, dp)`` mesh.

    Returns ``(step, mesh)``.  ``step(pages_u8, threshold)`` shards the page
    batch across ``dp`` (leading axis; count must divide the dp size),
    replicates it across ``sp`` column lanes, filters locally, and reduces
    with psum.  Output: ``{"count": scalar, "sums": (n_cols,)}`` — the
    selected-row count and per-column masked sums.

    *predicate* is ``predicate(cols, threshold) -> bool (B, T)`` (default:
    ``cols[0] > threshold``).  Every sp lane evaluates the predicate (it may
    read any column); lanes split only the *aggregation* work.
    """
    mesh = make_scan_mesh(devices, sp=sp)
    pred = predicate or (lambda cols, th: cols[0] > th)
    n_cols = schema.n_cols
    cols_per_lane = -(-n_cols // sp)   # ceil

    def _local(pages_u8, threshold):
        cols, valid = decode_pages(pages_u8, schema)
        sel = valid & pred(cols, threshold)
        count = jnp.sum(sel.astype(jnp.int32))
        lane = jax.lax.axis_index("sp")
        lo = lane * cols_per_lane
        col_ids = jnp.arange(n_cols)
        mine = (col_ids >= lo) & (col_ids < lo + cols_per_lane)
        sums = jnp.stack([jnp.sum(jnp.where(sel, c, 0)) for c in cols])
        sums = jnp.where(mine, sums, 0)
        # count is identical on every sp lane: reduce over dp only.
        # sums are disjoint across lanes: reduce over both axes.
        return {"count": jax.lax.psum(count, "dp"),
                "sums": jax.lax.psum(sums, ("sp", "dp"))}

    shard_mapped = shard_map(
        _local, mesh=mesh,
        in_specs=(P("dp", None), P()),
        out_specs={"count": P(), "sums": P()})
    step = jax.jit(shard_mapped)

    def run(pages_np, threshold):
        pages = jax.device_put(pages_np, pages_sharding(mesh))
        return step(pages, jnp.asarray(threshold, jnp.int32))

    return run, mesh


def shard_pages(pages_np: np.ndarray, mesh: Mesh) -> jax.Array:
    """Place a host page batch sharded across the mesh's dp axis."""
    return jax.device_put(pages_np, NamedSharding(mesh, P("dp", None)))
