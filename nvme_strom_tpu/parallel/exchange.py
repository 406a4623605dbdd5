"""All-to-all bucket exchange: repartition scanned rows by key over ICI.

The third collective pattern the framework supplies (after psum
aggregation in :mod:`.dscan` and ppermute ring streaming in :mod:`.ring`):
**all-to-all repartitioning**, the Ulysses/expert-parallel data movement.
Use case here: distributed GROUP BY / bucketed sort where each device must
end up owning *all* rows whose key falls in its bucket range — after a
dp-sharded scan, rows live wherever their page landed, so they must be
exchanged.

XLA needs static shapes, so the exchange uses **fixed per-bucket
capacity** with counts + padding — exactly the MoE token-dispatch
discipline (capacity-factor drops are reported, never silent:
``n_dropped`` comes back with the result).

Layout contract: each device presents ``(n_buckets, capacity, width)``
send slabs (slot ``b`` = rows bound for device ``b``);
``jax.lax.all_to_all`` over ``dp`` swaps slab *b* to device *b*, giving
every device one slab from each peer.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from .mesh import make_scan_mesh

__all__ = ["make_bucket_exchange", "bucket_dispatch"]


def bucket_dispatch(rows, bucket, ok, dp: int, capacity: int, *,
                    fill_value: int = 0):
    """Shard-local MoE-style dispatch + all_to_all (shared by the bucket
    exchange and :mod:`.sort`; call inside shard_map over a ``dp`` axis).

    ``rows (N, width) int32``, ``bucket (N,) int32`` owner device ids,
    ``ok (N,) bool`` rows eligible to send.  Rows rank within their
    (device, bucket); rank ≥ *capacity* is dropped.  Returns

    * ``recv (dp*capacity, width)`` — this device's bucket, one
      capacity-slab per sender, padded with *fill_value*,
    * ``recv_counts (dp,)`` — valid rows per sender slab,
    * ``keep (N,) bool`` — which local rows were actually sent (drop
      accounting is the caller's: ``sum(valid) - sum(keep)``).
    """
    onehot = (bucket[:, None] == jnp.arange(dp)[None, :]) & ok[:, None]
    oh32 = onehot.astype(jnp.int32)
    # rank = number of earlier same-bucket rows (the MoE dispatch rank)
    rank = jnp.cumsum(oh32, axis=0) - oh32              # (N, dp)
    pos = jnp.sum(rank * oh32, axis=1)                  # (N,)
    keep = ok & (pos < capacity)

    # scatter into the (dp, capacity, width) send slab; rejected rows are
    # routed out of bounds so mode="drop" discards them instead of
    # clobbering slot (0, 0)
    width = rows.shape[1]
    slab = jnp.full((dp, capacity, width), fill_value, jnp.int32)
    slot_b = jnp.where(keep, bucket, dp)
    slot_c = jnp.where(keep, pos, capacity)
    slab = slab.at[slot_b, slot_c].set(rows, mode="drop")
    sent = jnp.sum(oh32 * keep[:, None].astype(jnp.int32), axis=0)

    # the collective: slab axis 0 splits across dp, the local batch axis
    # concatenates — every device receives its own bucket from every peer
    recv = jax.lax.all_to_all(slab[None], "dp", split_axis=1,
                              concat_axis=0, tiled=False)
    recv = recv.reshape(dp * capacity, width)
    recv_counts = jax.lax.all_to_all(sent[None, :, None], "dp",
                                     split_axis=1, concat_axis=0,
                                     tiled=False).reshape(dp)
    return recv, recv_counts, keep


def make_bucket_exchange(devices: Optional[Sequence[jax.Device]] = None, *,
                         capacity: int, width: int,
                         fill_value: int = 0):
    """Build the jitted exchange over a 1-D ``dp`` mesh.

    Returns ``(run, mesh)``.  ``run(rows, keys, valid)`` with

    * ``rows`` — ``(N, width)`` int32, dp-sharded on the leading axis,
    * ``keys`` — ``(N,)`` int32 owner bucket in ``[0, dp)``,
    * ``valid`` — ``(N,)`` bool row mask,

    yields per device (stacked to global ``(dp, ...)`` arrays):

    * ``rows`` — ``(dp, dp*capacity, width)``: all rows whose key names
      this device, padded with ``fill_value``,
    * ``count`` — ``(dp,)`` received-row count,
    * ``n_dropped`` — scalar, rows lost to the capacity bound (MoE-style
      capacity overflow, reported for the caller to resize and rerun).
    """
    mesh = make_scan_mesh(devices, sp=1)
    dp = mesh.shape["dp"]

    def _local(rows, keys, valid):
        # out-of-range keys are drops, never silent (and never allowed to
        # reach the scatter, where a negative index would wrap)
        ok = valid & (keys >= 0) & (keys < dp)
        recv, recv_counts, keep = bucket_dispatch(
            rows, keys, ok, dp, capacity, fill_value=fill_value)
        # counts capacity overflow AND bad-key rows the caller marked valid
        n_dropped = jnp.sum(valid) - jnp.sum(keep)
        count = jnp.sum(recv_counts)
        return {"rows": recv[None], "count": count[None],
                "n_dropped": jax.lax.psum(n_dropped, "dp")}

    shard_mapped = shard_map(
        _local, mesh=mesh,
        in_specs=(P("dp", None), P("dp"), P("dp")),
        out_specs={"rows": P("dp", None, None), "count": P("dp"),
                   "n_dropped": P()})
    step = jax.jit(shard_mapped)

    def run(rows_np, keys_np, valid_np=None):
        n = len(keys_np)
        if valid_np is None:
            valid_np = np.ones(n, bool)
        rows_np = np.asarray(rows_np, np.int32)
        keys_np = np.asarray(keys_np, np.int32)
        valid_np = np.asarray(valid_np, bool)
        pad = (-n) % dp
        if pad:
            # scan outputs are rarely dp-divisible: pad with invalid rows
            rows_np = np.concatenate(
                [rows_np, np.zeros((pad, width), np.int32)])
            keys_np = np.concatenate([keys_np, np.zeros(pad, np.int32)])
            valid_np = np.concatenate([valid_np, np.zeros(pad, bool)])
        sh = NamedSharding(mesh, P("dp"))
        rows = jax.device_put(rows_np, NamedSharding(mesh, P("dp", None)))
        keys = jax.device_put(keys_np, sh)
        valid = jax.device_put(valid_np, sh)
        return step(rows, keys, valid)

    return run, mesh
