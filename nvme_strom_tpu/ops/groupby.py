"""Grouped aggregation over HBM-resident heap pages.

Extends the scan-compute tier (the pgsql per-tuple walk redesigned as
tensor ops, `pgsql/nvme_strom.c:941-979`) from flat filter/sum to
GROUP BY: per-group count/sum/min/max in one pass over a page batch.

TPU-first shape: the group reduction is a **one-hot contraction** —
``(B·T, G) one-hot  x  (B·T, V) values -> (G, V)`` — which XLA lowers to
an MXU matmul for the sum path (integer-exact via
``preferred_element_type``), instead of the scatter-add a CUDA port
would reach for (scatters serialize on TPU; matmuls do not).  Min/max
ride masked segment reductions on the VPU.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..scan.heap import HeapSchema
from .filter_xla import DEFAULT_SCHEMA, decode_pages

__all__ = ["make_groupby_fn", "scan_groupby_step", "combine_groupby",
           "groupby_kernel_auto"]

def groupby_kernel_auto(agg_kind: str):
    """Kernel routing for on-chip GROUP BY: ``(kernel, why)``.

    Integer accumulation stays on the Pallas kernel.  Float accumulation
    pays an SMEM accumulator round-trip per group that the XLA MXU
    contraction amortizes, so it takes Pallas only where this device's
    measured speedup (``device_figures``) is at least 1.0."""
    if agg_kind != "f":
        return "pallas", "int accumulators stay on the pallas kernel"
    from ..device_figures import device_figures
    fig = device_figures()
    ratio = fig.groupby_f32_pallas_speedup
    if ratio < 1.0:
        return "xla", (f"float aggregation routes to XLA (pallas "
                       f"speedup {ratio:g} < 1.0; {fig.source})")
    return "pallas", (f"float aggregation stays on pallas (speedup "
                      f"{ratio:g} >= 1.0; {fig.source})")


def combine_groupby(acc: dict, out: dict) -> dict:
    """Batch-fold combiner for grouped results (pass as
    ``TableScanner.scan_filter(..., combine=combine_groupby)`` or to
    ``distributed_scan_filter``): counts/sums/sumsqs add, mins/maxs meet."""
    folded = {"count": acc["count"] + out["count"],
              "sums": acc["sums"] + out["sums"],
              "sumsqs": acc["sumsqs"] + out["sumsqs"],
              "mins": jnp.minimum(acc["mins"], out["mins"]),
              "maxs": jnp.maximum(acc["maxs"], out["maxs"])}
    if "nncounts" in acc and "nncounts" in out:
        # per-column non-NULL counts (the XLA kernel emits them for
        # nullable schemas; the pallas twin never sees one)
        folded["nncounts"] = acc["nncounts"] + out["nncounts"]
    return folded

def acc_dtypes(agg_dt: np.dtype):
    """THE accumulation convention, in one place — returns
    ``(sum accumulator dtype, sumsq dtype, lo, hi)`` where ``lo`` is the
    dtype's worst/lowest value (initializes MAX accumulators) and ``hi``
    its best/highest (initializes MIN accumulators).  Float sums
    stay at the column dtype; int sums widen to 8 bytes only under x64
    (the MXU contraction's preferred_element_type); sumsqs are floating
    (f64 under x64).  Both the page kernels and the index-path host
    emulations (`scan/query._run_*_indexed`) derive from this, so the
    access paths cannot drift."""
    x64 = jax.config.jax_enable_x64
    is_f = agg_dt.kind == "f"
    acc = agg_dt if is_f or not x64 else np.dtype(agg_dt.kind + "8")
    sq = np.dtype(np.float64 if x64 else np.float32)
    if is_f:
        lo, hi = agg_dt.type(-np.inf), agg_dt.type(np.inf)
    else:
        info = np.iinfo(agg_dt)
        lo, hi = agg_dt.type(info.min), agg_dt.type(info.max)
    return acc, sq, lo, hi


def _check_agg_cols(schema: HeapSchema, agg_cols):
    """Validate + resolve aggregation columns: one shared dtype — int32,
    uint32, or float32.  Returns (indices, dtype)."""
    cols_idx = list(agg_cols) if agg_cols is not None else \
        list(range(schema.n_cols))
    if not cols_idx:
        raise ValueError("groupby needs at least one aggregation column")
    for ci in cols_idx:
        if not 0 <= ci < schema.n_cols:
            raise ValueError(f"aggregation column {ci} out of range — "
                             f"this schema has columns 0..{schema.n_cols - 1}")
    dts = {schema.col_dtype(ci) for ci in cols_idx}
    if len(dts) > 1:
        raise ValueError(f"groupby aggregation columns must share one "
                         f"dtype, got {sorted(str(d) for d in dts)}; "
                         f"split into one groupby per dtype")
    dt = dts.pop()
    if dt in (np.dtype(np.int64), np.dtype(np.float64)):
        # 8-byte aggregation rides the XLA path under x64 (round 5)
        if not jax.config.jax_enable_x64:
            raise ValueError(f"aggregating {dt} columns requires "
                             f"jax_enable_x64 (32-bit accumulation "
                             f"would silently truncate)")
    elif dt not in (np.dtype(np.int32), np.dtype(np.uint32),
                    np.dtype(np.float32)):
        raise ValueError(f"groupby aggregates int32, uint32, float32, "
                         f"int64, or float64 columns (got {dt})")
    return cols_idx, dt


def make_groupby_fn(schema: HeapSchema, key_fn: Callable, n_groups: int, *,
                    agg_cols: Optional[Sequence[int]] = None,
                    predicate: Optional[Callable] = None):
    """Build a jitted ``run(pages_u8, *params) -> dict`` grouped aggregate.

    ``key_fn(cols, *params) -> (B, T) int32`` group ids in ``[0, n_groups)``
    (out-of-range ids fall into no group); ``predicate(cols, *params)`` an
    optional row filter.  ``agg_cols`` — column indices to aggregate
    (default: all).  Returns per group: ``count (G,)``, and ``sums / sumsqs
    / mins / maxs`` of shape ``(len(agg_cols), G)``; empty groups report 0
    count, 0 sum, and the dtype's worst-value sentinels for min/max.
    ``sumsqs`` (for VAR/STDDEV) accumulates in floating point on every
    path — int32 squares overflow long before sums do, and variance is a
    statistical quantity, so float semantics are the honest contract.

    Aggregation columns must share one dtype — int32, uint32, or float32
    (uniform ``(V, G)`` result arrays; the reference's per-tuple walk had
    the same one-type-at-a-time shape).  Mixed sets raise.
    """
    cols_idx, agg_dt = _check_agg_cols(schema, agg_cols)
    G = int(n_groups)
    acc_np, sq_np, lo, hi = acc_dtypes(agg_dt)

    @jax.jit
    def run(pages_u8, *params):
        cols, valid = decode_pages(pages_u8, schema)
        keys = key_fn(cols, *params)
        sel = valid & (keys >= 0) & (keys < G)
        if predicate is not None:
            sel = sel & predicate(cols, *params)
        keys = jnp.where(sel, keys, G)  # overflow bucket, sliced off below
        flat_keys = keys.reshape(-1)
        onehot = jax.nn.one_hot(flat_keys, G + 1, dtype=jnp.int32)[:, :G]
        # NULL-aware aggregation (round 5): a nullable column's NULL
        # rows contribute nothing to its sums (stored zeros already do
        # that for + paths) and are excluded from its min/max/sumsq
        # masks; group COUNT stays the row count (SQL COUNT(*))
        nullm = [getattr(cols, "nulls", {}).get(i) for i in cols_idx]
        flat_nn = [sel.reshape(-1) if m is None
                   else (sel & ~m).reshape(-1) for m in nullm]
        vals = jnp.stack([c.reshape(-1) for c in (cols[i] for i in cols_idx)],
                         axis=-1)                       # (N, V)
        count = jnp.sum(onehot, axis=0)                 # (G,)
        flat_sel = sel.reshape(-1)
        if agg_dt.kind == "i" and np.dtype(acc_np).itemsize == 4:
            # the MXU path: (N,G)x(N,V)->(G,V) integer contraction,
            # exact within int32 (sums past 2^31 wrap, as any int32
            # engine would).  Only when the ACCUMULATOR is 32-bit: an
            # s64 dot_general does not lower on TPU (the X64-rewriter
            # has no dot rule — found live on v5e), so int64
            # accumulation (x64 mode, and int64 columns) rides
            # segment_sum below instead
            sums = jax.lax.dot_general(
                onehot, vals, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.dtype(acc_np)).T   # (V, G)
        else:
            # per-group scatter sum, NOT the matmul.  float: 0*NaN = NaN,
            # so one selected NaN row would poison EVERY group's sum
            # through the contraction — segment_sum confines it to its own
            # group, matching the pallas twin's per-group masking.  uint:
            # keeps the modular uint32 (u64 under x64) accumulation exact
            # without relying on unsigned dot support
            zero = agg_dt.type(0)
            sums = jnp.stack([
                jax.ops.segment_sum(
                    jnp.where(flat_sel, v, zero).astype(jnp.dtype(acc_np)),
                    flat_keys, num_segments=G + 1)[:G]
                for v in vals.T])
        # sum of squares for VAR/STDDEV: always floating (int32 squares
        # wrap far earlier than sums; f64 under x64, else f32) and
        # per-group confined like the float sums (NaN stays in its group)
        sq_t = jnp.dtype(sq_np)
        sumsqs = jnp.stack([
            jax.ops.segment_sum(
                jnp.where(m, v.astype(sq_t) * v.astype(sq_t), 0.0),
                flat_keys, num_segments=G + 1)[:G]
            for v, m in zip(vals.T, flat_nn)])
        mins = jnp.stack([
            jax.ops.segment_min(jnp.where(m, v, hi), flat_keys,
                                num_segments=G + 1)[:G]
            for v, m in zip(vals.T, flat_nn)])
        maxs = jnp.stack([
            jax.ops.segment_max(jnp.where(m, v, lo), flat_keys,
                                num_segments=G + 1)[:G]
            for v, m in zip(vals.T, flat_nn)])
        out = {"count": count, "sums": sums, "sumsqs": sumsqs,
               "mins": mins, "maxs": maxs}
        if any(m is not None for m in nullm):
            # per-column non-NULL group counts: AVG/VAR/STD over a
            # nullable column divide by THESE, not the row count
            # (review finding: sums skipped NULLs, denominators did not)
            out["nncounts"] = jnp.stack([
                jax.ops.segment_sum(m.astype(jnp.int32), flat_keys,
                                    num_segments=G + 1)[:G]
                for m in flat_nn])
        return out

    return run


@partial(jax.jit, static_argnums=(2,))
def scan_groupby_step(pages_u8: jax.Array, threshold: jax.Array,
                      n_groups: int = 16):
    """Demo step over the default schema: GROUP BY (col1 mod n_groups)
    WHERE col0 > threshold, aggregating col0."""
    fn = make_groupby_fn(
        DEFAULT_SCHEMA,
        lambda cols, th: jnp.abs(cols[1]) % n_groups,
        n_groups,
        agg_cols=[0],
        predicate=lambda cols, th: cols[0] > th)
    return fn(pages_u8, threshold)
