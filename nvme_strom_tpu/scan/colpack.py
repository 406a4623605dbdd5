"""Packed columnar extents: the compressed wire format for compute pushdown.

Where the h2d link is the ceiling of a scan, the way past a transport
ceiling is to move fewer, denser bytes and expand them on-chip
(ROADMAP item 5; AXI4MLIR's host<->accelerator transfer codegen is the
model for the per-column host-vs-chip expansion decision).  This module is
the *format* half: a ``<table>.cpk`` sidecar holding the same rows as the
heap table, re-encoded so each 8KB page carries ``rows_per_block`` rows
instead of the heap's ``tuples_per_page``.

Layout — every page is PAGE_SIZE bytes, so the packed file rides the whole
existing stack (chunked DMA ring, landing buffers, fault ladder, residency
cache) with zero special-casing:

* page 0: file header — ``CPK_FILE_MAGIC`` then a length-prefixed JSON
  metadata blob (schema facts, per-column codec + fixed region layout,
  source-table staleness stamp, exact packed/logical byte counts).
* pages 1..n_blocks: data blocks — a 64-byte header (``CPK_MAGIC``,
  block id, n_rows, payload crc32c) then per-column regions at the word
  offsets the file header declared.  Every block shares ONE layout, so
  the decode kernels are fully static: offsets, widths, dict capacities
  and run bounds are compile-time constants, never data.

Codecs (all chosen per column, globally for the file, so a region's shape
never varies block to block):

* ``raw``      — 32-bit words verbatim (bitcast for float32).
* ``bitpack``  — frame-of-reference base (region word 0) + deltas packed
  at a width that divides 32 (1/2/4/8/16) in a PLANAR layout: value ``j``
  lives in word ``j % nw`` at shift ``(j // nw) * bits``.  Planar (not
  word-major) on purpose: the chip decode is then shift + mask +
  concatenate along the minor axis — no gather and no reshape, neither
  of which TPU vector memory does cheaply.
* ``dict``     — per-block dictionary (``dsize`` slots, pow2) followed by
  bit-packed indices; decode is a ``dsize``-way static select-sum.
* ``rle``      — run values + cumulative run ends, ``rmax`` slots; decode
  is an ``rmax``-step static interval mask over a row iota.

The pure-numpy decoder here is the correctness oracle for the fused
Pallas/XLA kernels in ``ops/decode_pallas.py`` / ``ops/decode_xla.py``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .heap import (HEADER_WORDS, PAGE_SIZE, HeapSchema, crc32c,
                   pages_from_bytes, read_column)

__all__ = [
    "CPK_MAGIC", "CPK_FILE_MAGIC", "ColCodec", "PackedMeta",
    "packed_path_for", "build_packed", "load_meta", "probe_packed",
    "decode_pages_numpy", "decode_file_numpy",
]

CPK_MAGIC = 0x43504B31        # 'CPK1' — data-block header word 0
CPK_FILE_MAGIC = 0x43504B46   # 'CPKF' — file-header page word 0

_WORDS = PAGE_SIZE // 4
_PAYLOAD_WORDS = _WORDS - HEADER_WORDS

# static-unroll bounds for the chip decoders: a dict decode is a D-way
# select-sum and an RLE decode an R-step interval mask, so both must stay
# small enough to unroll (and to keep encode-side per-block stats cheap)
DICT_MAX = 64
RLE_MAX = 64
# the largest rows_per_block the encoder will emit: bounds the (bp, rpb)
# decoded-column tensors the kernels materialize in VMEM
_RPB_CANDIDATES = tuple(1 << k for k in range(15, 4, -1))   # 32768 .. 32

CODECS = ("raw", "bitpack", "dict", "rle")


@dataclasses.dataclass(frozen=True)
class ColCodec:
    """One column's codec + fixed region geometry (identical every block)."""

    codec: str            # raw | bitpack | dict | rle
    off: int              # region word offset within the page
    nwords: int           # region length in words
    bits: int = 0         # packed value/index width (bitpack/dict)
    dsize: int = 0        # dictionary capacity (dict; power of two)
    rmax: int = 0         # max runs per block (rle)
    packed_bytes: int = 0   # region bytes summed over all blocks
    logical_bytes: int = 0  # n_rows * 4

    @property
    def ratio(self) -> float:
        """Observed codec ratio: logical bytes per packed byte."""
        return self.logical_bytes / self.packed_bytes \
            if self.packed_bytes else 1.0


@dataclasses.dataclass(frozen=True)
class PackedMeta:
    """Parsed ``.cpk`` file header: everything the planner and the decode
    kernels need, all static."""

    version: int
    rows_per_block: int
    n_blocks: int
    n_rows: int
    dtypes: Tuple[str, ...]
    cols: Tuple[ColCodec, ...]
    table_size: int        # staleness stamp (scan/index.py idiom)
    table_mtime_ns: int
    path: str = ""

    @property
    def packed_bytes(self) -> int:
        """Wire bytes for a full scan: header page + data pages."""
        return (1 + self.n_blocks) * PAGE_SIZE

    @property
    def logical_bytes(self) -> int:
        return self.n_rows * 4 * len(self.dtypes)

    @property
    def ratio(self) -> float:
        return self.logical_bytes / self.packed_bytes \
            if self.packed_bytes else 1.0


def packed_path_for(table_path: str) -> str:
    return table_path + ".cpk"


# -- encode ---------------------------------------------------------------

def _pow2_width(span: int) -> int:
    """Smallest width in {1,2,4,8,16,32} holding *span* distinct deltas."""
    for b in (1, 2, 4, 8, 16):
        if span < (1 << b):
            return b
    return 32


def _pack_bits(vals: np.ndarray, bits: int, nw: int) -> np.ndarray:
    """Planar bit-pack of uint32 *vals* into exactly *nw* words: value
    ``j`` goes to word ``j % nw`` at shift ``(j // nw) * bits``.  *nw*
    is the region's fixed capacity (derived from rows_per_block), so a
    partial block packs identically to a full one."""
    vpw = 32 // bits
    v = np.zeros(nw * vpw, np.uint64)
    v[:len(vals)] = vals.astype(np.uint64)
    planes = v.reshape(vpw, nw)      # plane k = values [k*nw, (k+1)*nw)
    shifts = (np.arange(vpw, dtype=np.uint64) * np.uint64(bits))
    return ((planes << shifts[:, None]).sum(axis=0, dtype=np.uint64)
            & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def _unpack_bits(words: np.ndarray, bits: int, n: int) -> np.ndarray:
    """Inverse of :func:`_pack_bits` (nw = len(words))."""
    vpw = 32 // bits
    mask = np.uint32((1 << bits) - 1) if bits < 32 else np.uint32(0xFFFFFFFF)
    shifts = (np.arange(vpw, dtype=np.uint32) * np.uint32(bits))
    planes = (words.astype(np.uint32)[None, :] >> shifts[:, None]) & mask
    return planes.reshape(-1)[:n]


def _block_slices(n_rows: int, rpb: int) -> List[slice]:
    return [slice(i, min(i + rpb, n_rows)) for i in range(0, n_rows, rpb)]


def _col_u32(col: np.ndarray) -> np.ndarray:
    """Bit-pattern view: every codec below works on uint32 words."""
    return np.ascontiguousarray(col).view(np.uint32)


def _runs_per_block(u: np.ndarray, rpb: int) -> int:
    """Max run count over rpb-row blocks (block boundaries break runs)."""
    if len(u) == 0:
        return 0
    change = np.flatnonzero(np.diff(u) != 0) + 1
    # a run starts at 0, at every value change, and at every block edge
    starts = np.union1d(change, np.arange(0, len(u), rpb))
    return int(np.max(np.bincount(starts // rpb))) if len(starts) else 1


def _distinct_per_block(u: np.ndarray, rpb: int) -> int:
    if len(u) == 0:
        return 0
    return max(len(np.unique(u[sl])) for sl in _block_slices(len(u), rpb))


def _codec_candidates(u: np.ndarray, is_float: bool, rpb: int,
                      allowed: Sequence[str]):
    """[(words_per_block, codec, bits, dsize, rmax)] for one column."""
    out = [(rpb, "raw", 0, 0, 0)]
    n = len(u)
    if n == 0:
        return out
    if "bitpack" in allowed and not is_float:
        span = int(u.max()) - int(u.min())   # uint32 domain: span < 2^32
        b = _pow2_width(span)
        if b < 32:
            out.append((1 + (rpb * b + 31) // 32, "bitpack", b, 0, 0))
    if "dict" in allowed:
        d = _distinct_per_block(u, rpb)
        if 0 < d <= DICT_MAX:
            dsize = 1 << max(int(np.ceil(np.log2(d))), 0)
            bi = max(_pow2_width(dsize - 1), 1)
            out.append((dsize + (rpb * bi + 31) // 32, "dict", bi, dsize, 0))
    if "rle" in allowed:
        r = _runs_per_block(u, rpb)
        if 0 < r <= RLE_MAX:
            out.append((1 + 2 * r, "rle", 0, 0, r))
    return out


def _choose_layout(cols_u32: List[np.ndarray], floats: List[bool],
                   allowed: Sequence[str]):
    """Largest rows_per_block whose per-column best codecs fit one page.

    rows_per_block IS the compression ratio (rows delivered per 8KB of
    wire), so the search is simply: biggest rpb that fits."""
    for rpb in _RPB_CANDIDATES:
        picks, total = [], HEADER_WORDS
        for u, isf in zip(cols_u32, floats):
            cands = _codec_candidates(u, isf, rpb, allowed)
            picks.append(min(cands))
            total += picks[-1][0]
        if total <= _WORDS:
            return rpb, picks
    raise ValueError(f"schema too wide to pack ({len(cols_u32)} columns)")


def _encode_block(u: np.ndarray, pick, rpb: int) -> np.ndarray:
    nwords, codec, bits, dsize, rmax = pick
    out = np.zeros(nwords, np.uint32)
    n = len(u)
    if codec == "raw":
        out[:n] = u
    elif codec == "bitpack":
        base = u.min() if n else np.uint32(0)
        out[0] = base
        out[1:] = _pack_bits((u - base).astype(np.uint32), bits,
                             nwords - 1)
    elif codec == "dict":
        vals, idx = np.unique(u, return_inverse=True)
        out[:len(vals)] = vals
        out[dsize:] = _pack_bits(idx.astype(np.uint32), bits,
                                 nwords - dsize)
    else:   # rle
        if n:
            change = np.flatnonzero(np.diff(u) != 0) + 1
            starts = np.concatenate(([0], change))
            ends = np.concatenate((change, [n]))
            nr = len(starts)
            out[0] = nr
            out[1:1 + nr] = u[starts]
            out[1 + rmax:1 + rmax + nr] = ends.astype(np.uint32)
            # padded runs are empty intervals [n, n): decoders that walk
            # all rmax slots see zero-width masks past n_runs
            out[1 + nr:1 + rmax] = 0
            out[1 + rmax + nr:1 + 2 * rmax] = n
    return out


def build_packed(table_path: str, schema: HeapSchema, *,
                 out_path: Optional[str] = None,
                 codecs: Optional[Sequence[str]] = None) -> PackedMeta:
    """Encode a heap table into its ``.cpk`` packed twin (atomic rename).

    MVCC-invisible rows are dropped at encode time — the packed file holds
    exactly the rows a scan would aggregate, and the staleness stamp makes
    any later table write invalidate the sidecar."""
    if schema.has_wide or any(schema.nullable or ()):
        raise ValueError("packed extents serve the 4-byte non-null layout")
    if codecs is not None:
        allowed = tuple(codecs)
    else:
        from ..config import config
        allowed = tuple(c.strip()
                        for c in config.get("pushdown_codecs").split(",")
                        if c.strip())
    st = os.stat(table_path)
    with open(table_path, "rb") as f:
        pages = pages_from_bytes(f.read())
    cols = [read_column(pages, schema, c) for c in range(schema.n_cols)]
    if schema.visibility:
        words = pages.view(np.int32).reshape(len(pages), _WORDS)
        s, _e = schema.col_word_range(schema.n_cols)
        vis = np.concatenate([
            words[p, s:s + int(words[p, 2])] for p in range(len(pages))]) \
            if len(pages) else np.empty(0, np.int32)
        keep = vis != 0
        cols = [c[keep] for c in cols]
    n_rows = len(cols[0]) if cols else 0
    floats = [schema.col_dtype(c).kind == "f" for c in range(schema.n_cols)]
    cols_u32 = [_col_u32(c) for c in cols]
    rpb, picks = _choose_layout(cols_u32, floats, allowed)
    n_blocks = (n_rows + rpb - 1) // rpb

    col_metas, off = [], HEADER_WORDS
    for c, (nwords, codec, bits, dsize, rmax) in enumerate(picks):
        col_metas.append(ColCodec(
            codec=codec, off=off, nwords=nwords, bits=bits, dsize=dsize,
            rmax=rmax, packed_bytes=nwords * 4 * n_blocks,
            logical_bytes=n_rows * 4))
        off += nwords

    blocks = np.zeros((n_blocks, _WORDS), np.uint32)
    for bi, sl in enumerate(_block_slices(n_rows, rpb)):
        blocks[bi, 0] = CPK_MAGIC
        blocks[bi, 1] = bi
        blocks[bi, 2] = sl.stop - sl.start
        for c, (u, pick, cm) in enumerate(zip(cols_u32, picks, col_metas)):
            blocks[bi, cm.off:cm.off + cm.nwords] = \
                _encode_block(u[sl], pick, rpb)
        payload = blocks[bi, HEADER_WORDS:].tobytes()
        blocks[bi, 3] = np.uint32(crc32c(payload))

    meta = PackedMeta(
        version=1, rows_per_block=rpb, n_blocks=n_blocks, n_rows=n_rows,
        dtypes=tuple(np.dtype(schema.col_dtype(c)).name
                     for c in range(schema.n_cols)),
        cols=tuple(col_metas), table_size=st.st_size,
        table_mtime_ns=st.st_mtime_ns)
    head = np.zeros(_WORDS, np.uint32)
    head[0] = CPK_FILE_MAGIC
    blob = json.dumps(_meta_to_json(meta)).encode()
    head[1] = len(blob)
    head_bytes = bytearray(head.tobytes())
    head_bytes[8:8 + len(blob)] = blob
    if len(blob) > PAGE_SIZE - 8:
        raise ValueError("packed metadata blob exceeds the header page")

    dest = out_path or packed_path_for(table_path)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(dest) or ".",
                               prefix=".cpk-")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(bytes(head_bytes))
            f.write(blocks.tobytes())
        os.replace(tmp, dest)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return dataclasses.replace(meta, path=dest)


def _meta_to_json(m: PackedMeta) -> dict:
    return {
        "version": m.version, "page_size": PAGE_SIZE,
        "rows_per_block": m.rows_per_block, "n_blocks": m.n_blocks,
        "n_rows": m.n_rows, "dtypes": list(m.dtypes),
        "cols": [dataclasses.asdict(c) for c in m.cols],
        "table_size": m.table_size, "table_mtime_ns": m.table_mtime_ns,
    }


def load_meta(path: str) -> PackedMeta:
    """Parse a ``.cpk`` file header (no freshness check)."""
    with open(path, "rb") as f:
        head = f.read(PAGE_SIZE)
    if len(head) < PAGE_SIZE:
        raise ValueError(f"{path}: short packed header page")
    w = np.frombuffer(head[:8], np.uint32)
    if int(w[0]) != CPK_FILE_MAGIC:
        raise ValueError(f"{path}: bad packed-file magic 0x{int(w[0]):08x}")
    blob = head[8:8 + int(w[1])]
    d = json.loads(blob.decode())
    return PackedMeta(
        version=int(d["version"]), rows_per_block=int(d["rows_per_block"]),
        n_blocks=int(d["n_blocks"]), n_rows=int(d["n_rows"]),
        dtypes=tuple(d["dtypes"]),
        cols=tuple(ColCodec(**c) for c in d["cols"]),
        table_size=int(d["table_size"]),
        table_mtime_ns=int(d["table_mtime_ns"]), path=path)


def probe_packed(table_path: str, *,
                 path: Optional[str] = None) -> Optional[PackedMeta]:
    """Fresh packed sidecar for *table_path*, or None.

    Same contract as ``scan/index.py``'s probe: the stamp (source size +
    mtime_ns) must match the live table exactly, so any write to the
    table silently retires the packed representation."""
    p = path or packed_path_for(table_path)
    try:
        meta = load_meta(p)
        st = os.stat(table_path)
    except (OSError, ValueError):
        return None
    if meta.table_size != st.st_size \
            or meta.table_mtime_ns != st.st_mtime_ns:
        return None
    return meta


# -- numpy reference decoder (the kernels' correctness oracle) ------------

def _decode_region_numpy(words: np.ndarray, cm: ColCodec, n: int,
                         rpb: int) -> np.ndarray:
    r = words[cm.off:cm.off + cm.nwords].astype(np.uint32)
    if cm.codec == "raw":
        return r[:n].copy()
    if cm.codec == "bitpack":
        base = r[0]
        return (_unpack_bits(r[1:], cm.bits, n) + base).astype(np.uint32)
    if cm.codec == "dict":
        dvals = r[:cm.dsize]
        idx = _unpack_bits(r[cm.dsize:], cm.bits, n)
        return dvals[idx]
    # rle
    nr = int(r[0])
    vals = r[1:1 + nr]
    ends = r[1 + cm.rmax:1 + cm.rmax + nr].astype(np.int64)
    return np.repeat(vals, np.diff(ends, prepend=0))[:n]


def decode_pages_numpy(pages_u8: np.ndarray, meta: PackedMeta,
                       *, verify: bool = False
                       ) -> Tuple[List[np.ndarray], int]:
    """Decode packed pages to logical columns (pure numpy, independent of
    the jnp kernels — this is the oracle).  Pages that do not carry the
    data-block magic (the file header, zero padding) contribute no rows.
    Returns ``([col arrays in schema dtypes], n_rows)``."""
    pages = pages_from_bytes(pages_u8)
    words = pages.view(np.uint32).reshape(len(pages), _WORDS)
    outs: List[List[np.ndarray]] = [[] for _ in meta.cols]
    n_total = 0
    for p in range(len(pages)):
        if int(words[p, 0]) != CPK_MAGIC:
            continue
        n = int(words[p, 2])
        if verify:
            got = crc32c(words[p, HEADER_WORDS:].tobytes())
            if np.uint32(got) != words[p, 3]:
                raise ValueError(f"packed block {int(words[p, 1])}: "
                                 f"payload crc mismatch")
        n_total += n
        for c, cm in enumerate(meta.cols):
            outs[c].append(_decode_region_numpy(words[p], cm, n,
                                                meta.rows_per_block))
    cols = []
    for c, cm in enumerate(meta.cols):
        u = np.concatenate(outs[c]) if outs[c] \
            else np.empty(0, np.uint32)
        cols.append(u.view(np.dtype(meta.dtypes[c])))
    return cols, n_total


def decode_file_numpy(path: str,
                      meta: Optional[PackedMeta] = None
                      ) -> Tuple[List[np.ndarray], int]:
    meta = meta or load_meta(path)
    with open(path, "rb") as f:
        raw = f.read()
    return decode_pages_numpy(np.frombuffer(raw, np.uint8), meta)
