"""Distributed sample sort: a full ORDER BY at mesh scale.

Completes the scan-compute tier's ordering story: :mod:`..ops.topk` covers
``ORDER BY .. LIMIT k`` with a streaming fold, this module sorts the whole
key set across the ``dp`` mesh — the capability a CUDA framework would
build on multi-GPU radix sort and the reference (a storage engine) leaves
to PostgreSQL's executor.

TPU-native shape (everything static, one jitted shard_map):

1. **local sort** per device (``lax.sort`` — bitonic on TPU),
2. **splitter election**: every device contributes ``dp`` local quantile
   samples; an ``all_gather`` + sort of the ``dp²`` samples yields the
   ``dp-1`` global splitters (classic sample sort — splitters balance the
   buckets to ~N/dp each with high probability),
3. **bucket exchange**: ``searchsorted(splitters, v)`` names each
   element's owner device; a fixed-capacity ``all_to_all`` slab exchange
   moves them (the same MoE token-dispatch discipline as
   :mod:`.exchange` — capacity drops are counted, never silent),
4. **local sort of the received bucket** → device *b* holds the *b*-th
   globally-ordered key range; concatenating the per-device prefixes in
   mesh order is the sorted sequence.

Values may be int32 or float32 (floats ride the slab as an
order-irrelevant bitcast and are restored before the final sort); an
optional int32 payload (e.g. global row positions from the scan)
permutes with the keys.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from .mesh import make_scan_mesh

__all__ = ["make_distributed_sort", "make_distributed_distinct",
           "distributed_sort_u64"]

_I32_MAX = np.int32((1 << 31) - 1)


def make_distributed_sort(devices: Optional[Sequence[jax.Device]] = None, *,
                          capacity: int, dtype=np.int32,
                          descending: bool = False,
                          with_payload: bool = True):
    """Build the jitted distributed sort over a 1-D ``dp`` mesh.

    ``capacity`` — received-elements bound per (sender, receiver) pair;
    a bucket can absorb up to ``dp * capacity`` elements, so ``capacity ≳
    (N/dp²) · safety`` keeps drops at zero for near-uniform data (drops
    are reported via ``n_dropped``, resize and rerun on overflow).

    Returns ``(run, mesh)``.  ``run(values, payload=None, valid=None)``
    with ``values (N,)`` dp-sharded yields global ``(dp, dp*capacity)``
    arrays:

    * ``values`` — device *b*'s row sorted (descending if requested),
      padded at the tail with the dtype's worst value,
    * ``payload`` — int32, permuted with values (-1 padding),
    * ``count`` — ``(dp,)`` valid elements per device row,
    * ``n_dropped`` — scalar capacity-overflow count.

    Global order = concatenation of row ``b``'s first ``count[b]``
    elements for ``b = 0..dp-1``.

    ``with_payload=False`` drops the payload column from the all_to_all
    slab (halves exchange bytes; ``payload`` is then absent from the
    result) — for value-only consumers like COUNT(DISTINCT).
    """
    mesh = make_scan_mesh(devices, sp=1)
    dp = mesh.shape["dp"]
    dt = np.dtype(dtype)
    if dt not in (np.dtype(np.int32), np.dtype(np.uint32),
                  np.dtype(np.float32)):
        raise ValueError(f"sort supports int32/uint32/float32 values, "
                         f"got {dt}")
    is_f = dt.kind == "f"
    if is_f:
        worst = np.array(-np.inf if descending else np.inf, dt)
    else:
        info = np.iinfo(dt)
        worst = np.array(info.min if descending else info.max, dt)
    # the all_to_all slab is int32; float AND uint values ride it as an
    # order-free bitcast (restored on receive)
    rebit = dt != np.dtype(np.int32)

    def key_of(v):
        # order-reversing transforms that cannot overflow (ops/topk.py)
        if not descending:
            return v
        return -v if is_f else ~v

    def _local(values, payload, valid):
        n = values.shape[0]
        # 1+2. splitter election: sort the local keys (invalid ride as the
        # worst key, i.e. to the tail), take dp quantiles of the valid
        # prefix, all_gather them, and cut the dp-1 global splitters — all
        # in key space, so descending order works unchanged
        v = jnp.where(valid, values, worst)
        nvalid = jnp.sum(valid.astype(jnp.int32))
        sorted_keys = jnp.sort(key_of(v))
        qpos = ((jnp.arange(dp) + 1) * nvalid) // (dp + 1)
        qpos = jnp.clip(qpos, 0, n - 1)
        local_samples = sorted_keys[qpos]
        all_samples = jax.lax.all_gather(local_samples, "dp").reshape(-1)
        all_samples = jnp.sort(all_samples)
        splitters = all_samples[(jnp.arange(dp - 1) + 1) * dp]

        # 3. owner bucket per element (key space keeps it monotone);
        # dispatch + all_to_all shared with the bucket exchange
        from .exchange import bucket_dispatch
        bucket = jnp.searchsorted(splitters, key_of(values),
                                  side="right").astype(jnp.int32)
        vbits = jax.lax.bitcast_convert_type(values, jnp.int32) \
            if rebit else values
        cols = [vbits, payload] if with_payload else [vbits]
        recv, counts, keep = bucket_dispatch(
            jnp.stack(cols, -1), bucket, valid, dp, capacity)
        n_dropped = jnp.sum(valid) - jnp.sum(keep)

        # 4. local sort of the received bucket; pad slots (slot >= its
        # sub-slab's count) sort to the tail
        slot = jnp.arange(dp * capacity) % capacity
        src = jnp.arange(dp * capacity) // capacity
        got = slot < counts[src]
        rv = recv[:, 0]
        if rebit:
            rv = jax.lax.bitcast_convert_type(rv, jnp.dtype(dt))
        rv = jnp.where(got, rv, worst)
        out = {"count": jnp.sum(counts)[None],
               "n_dropped": jax.lax.psum(n_dropped, "dp")}
        # secondary pad-flag key: a REAL key equal to the worst value
        # (e.g. uint32 max in a packed composite word) must sort before
        # the pad slots sharing that value, or the count-prefix read
        # would swallow pads and drop real rows
        padflag = (~got).astype(jnp.int32)
        if with_payload:
            rp = jnp.where(got, recv[:, 1], -1)
            _, _, sv, sp = jax.lax.sort((key_of(rv), padflag, rv, rp),
                                        num_keys=2)
            out["values"], out["payload"] = sv[None], sp[None]
        else:
            sv = jax.lax.sort((key_of(rv), padflag, rv), num_keys=2)[2]
            out["values"] = sv[None]
        return out

    out_specs = {"values": P("dp", None), "count": P("dp"),
                 "n_dropped": P()}
    if with_payload:
        out_specs["payload"] = P("dp", None)
    shard_mapped = shard_map(
        _local, mesh=mesh,
        in_specs=(P("dp"), P("dp"), P("dp")),
        out_specs=out_specs)
    step = jax.jit(shard_mapped)

    def run(values_np, payload_np=None, valid_np=None):
        values_np = np.asarray(values_np, dt)
        n = len(values_np)
        if payload_np is None:
            payload_np = np.arange(n, dtype=np.int32)
        payload_np = np.asarray(payload_np, np.int32)
        if valid_np is None:
            valid_np = np.ones(n, bool)
        valid_np = np.asarray(valid_np, bool)
        # zero-length shards break the in-kernel gathers: an empty input
        # still ships one invalid row per shard
        pad = (-n) % dp if n else dp
        if pad:
            values_np = np.concatenate([values_np, np.zeros(pad, dt)])
            payload_np = np.concatenate(
                [payload_np, np.full(pad, -1, np.int32)])
            valid_np = np.concatenate([valid_np, np.zeros(pad, bool)])
        sh = NamedSharding(mesh, P("dp"))
        out = step(jax.device_put(values_np, sh),
                   jax.device_put(payload_np, sh),
                   jax.device_put(valid_np, sh))
        return out

    return run, mesh


def distributed_sort_u64(mesh, values: np.ndarray,
                         payload: np.ndarray):
    """STABLE distributed sort of uint64 keys over the mesh — LSD radix
    riding the uint32 sample sort twice (VERDICT r3 #4: composite-index
    packed keys scale through the same machinery as single-column ORDER
    BY, no host argsort).

    Two stable passes: sort by the low word carrying the row index, then
    sort by the high word in low-sorted order.  Stability end-to-end
    (rank-preserving dispatch + sender-major slabs over contiguous input
    ranges + ``is_stable`` local sorts) makes the result permutation
    bit-identical to ``np.argsort(values, kind="stable")`` — duplicate
    keys keep physical order, the sidecar contract.

    Returns ``(sorted_values, payload_permuted)`` as host arrays.
    *payload* may be any dtype (it is permuted host-side; only the int32
    row index rides the exchange, so ``len(values)`` must fit int32)."""
    values = np.ascontiguousarray(values, np.uint64)
    payload = np.asarray(payload)
    n = len(values)
    if n == 0:
        return values.copy(), payload.copy()
    if n > np.iinfo(np.int32).max:
        raise ValueError("distributed_sort_u64: row index exceeds int32")
    devices = list(mesh.devices.reshape(-1))
    dp = len(devices)
    hi = (values >> np.uint64(32)).astype(np.uint32)
    lo = (values & np.uint64(0xFFFFFFFF)).astype(np.uint32)

    def one_pass(keys32: np.ndarray, pay: np.ndarray) -> np.ndarray:
        # same 2.5x-slack + double-on-overflow capacity loop as the
        # ORDER BY family (scan/query.py _mesh_sort_loop)
        capacity = max(64, -(-n * 5 // (2 * dp * dp)))
        while True:
            run, _ = make_distributed_sort(devices, capacity=capacity,
                                           dtype=np.uint32)
            out = run(keys32, pay)
            if int(out["n_dropped"]) == 0:
                counts = np.asarray(out["count"])
                pays = np.asarray(out["payload"])
                return np.concatenate(
                    [pays[b][:counts[b]] for b in range(dp)])
            capacity *= 2

    perm1 = one_pass(lo, np.arange(n, dtype=np.int32))
    perm = one_pass(hi[perm1], perm1)
    return values[perm], payload[perm]


def make_distributed_distinct(devices=None, *, capacity: int,
                              dtype=np.int32):
    """COUNT(DISTINCT col) over the mesh: distributed sample sort, then an
    on-device adjacent-diff per bucket, reduced with psum.

    No cross-device boundary handling is needed — bucket assignment is
    ``searchsorted`` on the VALUE, so every copy of an equal key lands in
    the same bucket by construction; a run can never span devices.  (A
    ppermute "dedup" here would only ever misfire, e.g. on a sentinel
    collision with an empty predecessor bucket.)

    NaNs count individually (IEEE ``!=`` semantics — each NaN is its own
    value, as the local path also implements).

    Returns ``(run, mesh)``; ``run(values, valid=None)`` yields
    ``{"distinct": scalar int32, "n_dropped": scalar}``."""
    import jax

    sort_run, mesh = make_distributed_sort(devices, capacity=capacity,
                                           dtype=dtype, with_payload=False)

    def _local(vals_row, count_row):
        v = vals_row.reshape(-1)                  # (dp*capacity,) sorted,
        n = count_row.reshape(())                 # first n valid
        idx = jnp.arange(v.shape[0])
        valid = idx < n
        prev_ok = valid & (idx > 0)
        new_run = valid & jnp.where(
            prev_ok, v != jnp.roll(v, 1), True)   # first valid starts a run
        return jax.lax.psum(jnp.sum(new_run.astype(jnp.int32)), "dp")[None]

    counted = jax.jit(shard_map(
        _local, mesh=mesh,
        in_specs=(P("dp", None), P("dp")),
        out_specs=P()))

    def run(values_np, valid_np=None):
        out = sort_run(values_np, valid_np=valid_np)
        distinct = counted(out["values"], out["count"])
        return {"distinct": np.asarray(distinct).reshape(())[()],
                "n_dropped": np.asarray(out["n_dropped"])}

    return run, mesh
