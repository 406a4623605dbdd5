"""Fused decode+filter+project over packed columnar extents (XLA path).

The wire carries ``scan/colpack.py`` packed blocks (8KB pages holding
``rows_per_block`` rows each); this module expands them ON THE DEVICE and
folds the filter + masked aggregate in the same fused dispatch, so the
host->HBM link — where it is the ceiling — moves
packed bytes while the query still sees logical rows.

``decode_block_words`` is deliberately built from nothing but slices,
shifts, masks, compares and minor-axis concatenation — every codec decode
is static control flow over fixed region geometry, so the SAME function
traces inside the Pallas kernels (:mod:`.decode_pallas`) and here under
plain jit.  The independent numpy decoder in ``scan/colpack.py`` is the
correctness oracle for both.

Projection is part of the fusion: columns outside ``need_cols`` are never
expanded — their sums are constant zeros the compiler folds away.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..scan.colpack import CPK_MAGIC, ColCodec, PackedMeta

__all__ = ["decode_block_words", "make_decode_filter_fn_xla"]


def _unpack_bits_jnp(packed_u32, bits: int, rpb: int):
    """Planar bit-unpack: (bp, nw) uint32 words -> (bp, rpb) uint32.

    Value ``j`` lives in word ``j % nw`` at shift ``(j // nw) * bits``
    (colpack's planar layout), so plane k is one shift+mask of the whole
    region and planes concatenate along the minor axis — no gather, no
    reshape."""
    nw = packed_u32.shape[1]
    vpw = 32 // bits
    mask = jnp.uint32((1 << bits) - 1) if bits < 32 \
        else jnp.uint32(0xFFFFFFFF)
    planes = [(packed_u32 >> jnp.uint32(k * bits)) & mask
              for k in range(vpw)]
    return jnp.concatenate(planes, axis=1)[:, :rpb]


def _decode_col(wu, cm: ColCodec, rpb: int, iota):
    """One column's region -> (bp, rpb) uint32 bit patterns."""
    r = wu[:, cm.off:cm.off + cm.nwords]
    if cm.codec == "raw":
        return r[:, :rpb]
    if cm.codec == "bitpack":
        base = r[:, 0:1]
        return _unpack_bits_jnp(r[:, 1:], cm.bits, rpb) + base
    if cm.codec == "dict":
        dvals = r[:, :cm.dsize]
        idx = _unpack_bits_jnp(r[:, cm.dsize:], cm.bits, rpb)
        # static D-way select-sum: exactly one slot matches, the rest
        # contribute 0 — a gather TPUs can actually vectorize
        acc = jnp.zeros_like(idx)
        for d in range(cm.dsize):
            acc = acc + jnp.where(idx == jnp.uint32(d),
                                  dvals[:, d:d + 1], jnp.uint32(0))
        return acc
    # rle: run values + cumulative ends; padded runs are empty [n, n)
    # intervals, so walking every rmax slot is mask-correct
    vals = r[:, 1:1 + cm.rmax]
    ends = jax.lax.bitcast_convert_type(
        r[:, 1 + cm.rmax:1 + 2 * cm.rmax], jnp.int32)
    acc = jnp.zeros(iota.shape, jnp.uint32)
    prev = jnp.zeros((iota.shape[0], 1), jnp.int32)
    for k in range(cm.rmax):
        end = ends[:, k:k + 1]
        m = (iota >= prev) & (iota < end)
        acc = acc + jnp.where(m, vals[:, k:k + 1], jnp.uint32(0))
        prev = end
    return acc


def decode_block_words(w, meta: PackedMeta,
                       need: Optional[Sequence[int]] = None):
    """(bp, 2048) int32 packed-page words -> ([typed (bp, rpb) col ...],
    valid mask).

    Pages without the data-block magic (the file header page, zero
    padding) decode to an all-False mask, so a packed file scans through
    the unmodified chunk pipeline.  Columns outside *need* come back as
    constant zeros (projection fused into the decode)."""
    rpb = meta.rows_per_block
    bp = w.shape[0]
    wu = jax.lax.bitcast_convert_type(w, jnp.uint32)
    iota = jax.lax.broadcasted_iota(jnp.int32, (bp, rpb), 1)
    n_rows = w[:, 2:3]
    valid = (w[:, 0:1] == CPK_MAGIC) & (iota < n_rows)
    cols = []
    for c, cm in enumerate(meta.cols):
        dt = jnp.dtype(np.dtype(meta.dtypes[c]))
        if need is not None and c not in need:
            cols.append(jnp.zeros((bp, rpb), dt))
            continue
        u = _decode_col(wu, cm, rpb, iota)
        cols.append(u if dt == jnp.uint32
                    else jax.lax.bitcast_convert_type(u, dt))
    return cols, valid


def make_decode_filter_fn_xla(meta: PackedMeta, predicate=None, *,
                              need_cols: Optional[Sequence[int]] = None):
    """Fused decode->filter->project for packed page batches (XLA).

    Same contract as :func:`.filter_xla.make_filter_fn`: a jitted
    ``run(pages_u8) -> {"count", "sums"}`` with per-column masked sums in
    the column dtypes — accumulation is dtype-identical to the unpacked
    scan, so integer aggregates are byte-identical between the two
    representations.  ``predicate(cols)`` sees the full positional column
    list (un-needed columns as zeros), exactly like the heap kernels."""
    need = tuple(need_cols) if need_cols is not None else None
    words_per_page = 8192 // 4

    @jax.jit
    def run(pages_u8):
        b = pages_u8.shape[0]
        w = jax.lax.bitcast_convert_type(
            pages_u8.reshape(b, words_per_page, 4),
            jnp.int32).reshape(b, words_per_page)
        cols, valid = decode_block_words(w, meta, need)
        sel = valid if predicate is None else valid & predicate(cols)
        return {
            "count": jnp.sum(sel.astype(jnp.int32)),
            "sums": [jnp.sum(jnp.where(sel, v, v.dtype.type(0)))
                     for v in cols],
        }

    return run
