"""TPU-native heap page format.

The reference scans PostgreSQL heap pages: 8KB blocks with line-pointer
arrays and variable-width tuples walked one at a time
(`pgsql/nvme_strom.c:941-979`).  That layout is pointer-chasing and
scalar — hostile to the MXU/VPU.  This framework's table format keeps the
8KB-block granularity (so the whole chunk/DMA machinery is shared) but lays
tuples out **columnar within the page**, fixed width, so a batch of pages
bitcasts to typed tensors and every predicate is a vectorized op:

``page[8192] = header[64B] | col regions | visibility | validity | pad``

header words (int32): [0]=magic [1]=page_id [2]=n_tuples [3]=n_cols
[4]=visibility_mode [5]=wide-column bitmask [6]=nullable bitmask
[7..15]=reserved.

Column regions sit in schema order; each holds ``T`` values of the
column's width (4 or 8 bytes — int32/uint32/float32/int64/float64,
round 5), 8-byte regions padded up to 8-byte file offsets so the
device decode is a pure bitcast.  Tuple *visibility* (the MVCC analog
the reference arbitrates per tuple, pgsql/nvme_strom.c:767-811) is a
per-tuple int32 mask column after the data regions when
``visibility_mode == 1``.  NULLABLE columns (round 5 — PG heap tuples
carry null bitmaps, `pgsql/nvme_strom.c:767-811` preserves them) each
append a VALIDITY bitmap after that: ``ceil(T/32)`` words, bit i set =
row i carries a real value (Arrow's convention); the stored word under
a NULL is zero, and NULL-awareness lives in the masks, never in
sentinel values.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import List, Optional, Sequence

import numpy as np

__all__ = ["PAGE_SIZE", "HEAP_MAGIC", "HEADER_BYTES", "CHECKSUM_WORD",
           "HeapSchema", "build_heap_file", "pages_from_bytes",
           "validate_heap_header", "page_checksum",
           "verify_page_checksums"]

PAGE_SIZE = 8192                  # BLCKSZ, matching the reference
HEADER_BYTES = 64
HEADER_WORDS = HEADER_BYTES // 4
HEAP_MAGIC = 0x53545250           # 'PRTS'
#: header word carrying the page's crc32c (PR 1, torn-read detection) —
#: first of the reserved words [7..15]; 0 = unchecksummed (pre-PR-1 file,
#: or the 2^-32 crc that happens to be zero — treated as absent)
CHECKSUM_WORD = 7

_DTS_4 = (np.dtype(np.int32), np.dtype(np.uint32), np.dtype(np.float32))
_DTS_8 = (np.dtype(np.int64), np.dtype(np.float64))


# -- page checksums (PR 1) -------------------------------------------------
# crc32c (Castagnoli, the poly NVMe end-to-end protection and PG's data
# checksums use): the C wheel when the image carries one, else a
# table-driven software fallback — same polynomial, so files verify
# identically either way.
try:
    from google_crc32c import value as _crc32c          # C extension
except ImportError:   # pragma: no cover - depends on image
    try:
        from crc32c import crc32c as _crc32c
    except ImportError:
        _CRC32C_TABLE = []
        for _i in range(256):
            _c = _i
            for _ in range(8):
                _c = (_c >> 1) ^ (0x82F63B78 if _c & 1 else 0)
            _CRC32C_TABLE.append(_c)

        def _crc32c(data) -> int:
            crc = 0xFFFFFFFF
            for b in bytes(data):
                crc = (crc >> 8) ^ _CRC32C_TABLE[(crc ^ b) & 0xFF]
            return crc ^ 0xFFFFFFFF


def crc32c(data) -> int:
    """crc32c of an arbitrary buffer (the page-checksum polynomial) —
    the shared checksum for checkpoint leaves and write_verify
    read-back, so files verify identically whichever backend computed
    them."""
    return int(_crc32c(bytes(data)))


# incremental form (crc32c(a+b) == crc32c_update(crc32c(a), b)) for
# streaming verification over leaf spans that never assemble on host
try:
    from google_crc32c import extend as _crc32c_extend    # C extension

    def crc32c_update(crc: int, data) -> int:
        return int(_crc32c_extend(crc, bytes(data)))
except ImportError:   # pragma: no cover - depends on image
    try:
        from crc32c import crc32c as _crc32c_pkg

        def crc32c_update(crc: int, data) -> int:
            return int(_crc32c_pkg(bytes(data), crc))
    except ImportError:

        def crc32c_update(crc: int, data) -> int:
            c = crc ^ 0xFFFFFFFF
            for b in bytes(data):
                c = (c >> 8) ^ _CRC32C_TABLE[(c ^ b) & 0xFF]
            return c ^ 0xFFFFFFFF


def page_checksum(page) -> int:
    """crc32c of one page with its CHECKSUM_WORD zeroed (what the builder
    stores there and the verifier recomputes)."""
    buf = bytearray(bytes(page))
    if len(buf) != PAGE_SIZE:
        raise ValueError(f"page must be {PAGE_SIZE} bytes, got {len(buf)}")
    buf[CHECKSUM_WORD * 4:CHECKSUM_WORD * 4 + 4] = b"\0\0\0\0"
    return int(_crc32c(bytes(buf)))


def verify_page_checksums(data) -> List[int]:
    """Verify every whole heap page in *data* (bytes/memoryview/uint8
    array); returns the indices of pages whose stored crc32c mismatches.
    Pages without the heap magic or with a zero checksum word (legacy
    files) are skipped, so the check is safe to run over arbitrary chunk
    payloads.  A trailing partial page is ignored."""
    arr = np.frombuffer(data, dtype=np.uint8) if not isinstance(data, np.ndarray) \
        else data.reshape(-1)
    n = arr.size // PAGE_SIZE
    if n == 0:
        return []
    pages = arr[:n * PAGE_SIZE].reshape(n, PAGE_SIZE)
    words = pages.view(np.int32)
    stored = words[:, CHECKSUM_WORD].view(np.uint32)
    bad: List[int] = []
    for p in range(n):
        if int(words[p, 0]) != HEAP_MAGIC or int(stored[p]) == 0:
            continue
        if page_checksum(pages[p]) != int(stored[p]):
            bad.append(p)
    return bad


@lru_cache(maxsize=256)
def _layout(schema: "HeapSchema"):
    """(tuples_per_page, col word offsets, visibility offset|None,
    validity word offsets {col: off}) — the single layout derivation
    both the host builder and the device decode use."""
    widths = [schema.col_dtype(c).itemsize for c in range(schema.n_cols)]
    nullable = schema.nullable or (False,) * schema.n_cols
    fixed_words = sum(w // 4 for w in widths) \
        + (1 if schema.visibility else 0)

    def fits(t: int) -> Optional[tuple]:
        off = HEADER_WORDS
        col_off = []
        for w in widths:
            if w == 8 and off % 2:
                off += 1          # 8-byte regions start 8-aligned
            col_off.append(off)
            off += (w // 4) * t
        vis_off = None
        if schema.visibility:
            vis_off = off
            off += t
        nb = (t + 31) // 32
        valid_off = {}
        for c in range(schema.n_cols):
            if nullable[c]:
                valid_off[c] = off
                off += nb
        if off > PAGE_SIZE // 4:
            return None
        return t, tuple(col_off), vis_off, dict(valid_off)

    t = (PAGE_SIZE - HEADER_BYTES) * 8 // \
        (fixed_words * 32 + sum(nullable))
    while t > 0:
        got = fits(t)
        if got is not None:
            return got
        t -= 1
    raise ValueError("schema too wide for one page")


@dataclass(frozen=True)
class HeapSchema:
    """Fixed-width column schema.

    ``dtypes`` — optional per-column dtype strings (default: all
    int32); int32/uint32/float32 plus (round 5) int64/float64.
    ``nullable`` — optional per-column bools; nullable columns carry a
    validity bitmap per page."""

    n_cols: int
    visibility: bool = False       # append a per-tuple visibility column
    dtypes: Optional[tuple] = None
    nullable: Optional[tuple] = None

    def __post_init__(self):
        if self.dtypes is not None:
            if len(self.dtypes) != self.n_cols:
                raise ValueError(f"{len(self.dtypes)} dtypes for "
                                 f"{self.n_cols} columns")
            for d in self.dtypes:
                if np.dtype(d) not in _DTS_4 + _DTS_8:
                    raise ValueError(f"column dtype {d} not supported "
                                     f"(int32/uint32/float32/int64/"
                                     f"float64)")
        if self.nullable is not None:
            if len(self.nullable) != self.n_cols:
                raise ValueError(f"{len(self.nullable)} nullable flags "
                                 f"for {self.n_cols} columns")
            object.__setattr__(self, "nullable",
                               tuple(bool(b) for b in self.nullable))
        if (self.has_wide or any(self.nullable or ())) \
                and self.n_cols > 31:
            raise ValueError("wide/nullable schemas support up to 31 "
                             "columns (header bitmask width)")

    def col_dtype(self, c: int) -> np.dtype:
        return np.dtype(self.dtypes[c]) if self.dtypes else np.dtype(np.int32)

    def col_nullable(self, c: int) -> bool:
        return bool(self.nullable[c]) if self.nullable else False

    @property
    def has_wide(self) -> bool:
        return self.dtypes is not None and \
            any(np.dtype(d).itemsize == 8 for d in self.dtypes)

    @property
    def phys_cols(self) -> int:
        return self.n_cols + (1 if self.visibility else 0)

    @property
    def tuples_per_page(self) -> int:
        return _layout(self)[0]

    def col_word_range(self, c: int):
        """(start, stop) word offsets of column *c* within a page
        (``c == n_cols`` addresses the visibility column)."""
        t, col_off, vis_off, _valid = _layout(self)
        if c == self.n_cols:
            if vis_off is None:
                raise ValueError("schema has no visibility column")
            return vis_off, vis_off + t
        w = self.col_dtype(c).itemsize // 4
        return col_off[c], col_off[c] + w * t

    def validity_word_range(self, c: int):
        """(start, stop) word offsets of column *c*'s validity bitmap."""
        t, _col_off, _vis, valid = _layout(self)
        if c not in valid:
            raise ValueError(f"column {c} is not nullable")
        nb = (t + 31) // 32
        return valid[c], valid[c] + nb

    def _bitmask(self, pred) -> int:
        return sum(1 << c for c in range(self.n_cols) if pred(c))

    @property
    def wide_mask(self) -> int:
        return self._bitmask(lambda c: self.col_dtype(c).itemsize == 8)

    @property
    def null_mask(self) -> int:
        return self._bitmask(self.col_nullable)


def _pack_validity(mask: np.ndarray, t: int) -> np.ndarray:
    """(n,) present-bool -> ceil(t/32) int32 bitmap words; bit ``i % 32``
    of word ``i // 32`` set when row i holds a value — the same
    shift-and-mask the device decode applies."""
    nb = (t + 31) // 32
    bits = np.zeros(nb * 32, dtype=bool)
    bits[:len(mask)] = mask
    weights = (np.uint64(1) << np.arange(32, dtype=np.uint64))
    words = (bits.reshape(nb, 32).astype(np.uint64) * weights) \
        .sum(axis=1).astype(np.uint32)
    return words.view(np.int32)


def _unpack_validity(words: np.ndarray, n: int) -> np.ndarray:
    """Inverse of :func:`_pack_validity` for the first *n* rows."""
    w = words.astype(np.int64) & 0xFFFFFFFF
    bits = (w[:, None] >> np.arange(32, dtype=np.int64)[None, :]) & 1
    return bits.reshape(-1)[:n].astype(bool)


def build_pages(columns: Sequence[np.ndarray], schema: HeapSchema, *,
                visibility: Optional[np.ndarray] = None,
                nulls: Optional[dict] = None,
                start_page_id: int = 0) -> np.ndarray:
    """Pack column arrays (each shape (n_rows,), schema dtypes) into
    pages.  ``nulls`` — optional ``{col: (n_rows,) bool}`` NULL masks
    for nullable columns (True = NULL; stored word zeroed, validity bit
    cleared).  Returns a uint8 array of shape (n_pages, PAGE_SIZE)."""
    if len(columns) != schema.n_cols:
        raise ValueError(f"expected {schema.n_cols} columns, got {len(columns)}")
    nulls = dict(nulls or {})
    for c in nulls:
        if not schema.col_nullable(c):
            raise ValueError(f"column {c} is not nullable in the schema")
    n_rows = len(columns[0])
    for ci, c in enumerate(columns):
        if len(c) != n_rows:
            raise ValueError("ragged columns")
        if schema.dtypes is not None and c.dtype != schema.col_dtype(ci):
            raise ValueError(f"column {ci} dtype {c.dtype} != schema "
                             f"{schema.col_dtype(ci)}")
        if schema.dtypes is None and c.dtype.itemsize != 4:
            raise ValueError("columns must be 4-byte dtypes")
    if schema.visibility:
        if visibility is None:
            visibility = np.ones(n_rows, dtype=np.int32)
        if len(visibility) != n_rows:
            raise ValueError("visibility length mismatch")
    t, col_off, vis_off, valid_off = _layout(schema)
    n_pages = max((n_rows + t - 1) // t, 1)
    pages = np.zeros((n_pages, PAGE_SIZE // 4), dtype=np.int32)
    pages[:, 0] = HEAP_MAGIC
    pages[:, 1] = np.arange(start_page_id, start_page_id + n_pages)
    pages[:, 3] = schema.n_cols
    pages[:, 4] = 1 if schema.visibility else 0
    pages[:, 5] = schema.wide_mask
    pages[:, 6] = schema.null_mask
    nb = (t + 31) // 32
    for p in range(n_pages):
        lo, hi = p * t, min((p + 1) * t, n_rows)
        n = hi - lo
        pages[p, 2] = n
        for ci in range(schema.n_cols):
            vals = columns[ci][lo:hi]
            if ci in nulls:
                vals = np.where(nulls[ci][lo:hi],
                                vals.dtype.type(0), vals)
            w = schema.col_dtype(ci).itemsize // 4
            s = col_off[ci]
            pages[p, s:s + n * w] = vals.view(np.int32).reshape(-1)
        if schema.visibility:
            pages[p, vis_off:vis_off + n] = \
                visibility[lo:hi].astype(np.int32)
        for ci, s in valid_off.items():
            present = np.ones(n, dtype=bool)
            if ci in nulls:
                present = ~np.asarray(nulls[ci][lo:hi], dtype=bool)
            pages[p, s:s + nb] = _pack_validity(present, t)
    out = pages.view(np.uint8).reshape(n_pages, PAGE_SIZE)
    # stamp per-page crc32c into the reserved header word so torn/corrupt
    # reads are detectable end to end (config checksum_verify); computed
    # last, over the page with the word still zero
    csum = pages.view(np.uint32).reshape(n_pages, PAGE_SIZE // 4)
    for p in range(n_pages):
        csum[p, CHECKSUM_WORD] = page_checksum(out[p])
    return out


#: pages built per slab by build_heap_file: bounds its host memory to
#: ~512 MiB of pages whatever the table size
_SLAB_PAGES = 1 << 16


def build_heap_file(path: str, columns: Sequence[np.ndarray],
                    schema: HeapSchema, *,
                    visibility: Optional[np.ndarray] = None,
                    nulls: Optional[dict] = None) -> int:
    """Write a heap file, slab by slab; returns number of pages."""
    t = schema.tuples_per_page
    n_rows = len(columns[0])
    n_pages = max((n_rows + t - 1) // t, 1)
    with open(path, "wb") as f:
        for p0 in range(0, n_pages, _SLAB_PAGES):
            rows = slice(p0 * t, (p0 + _SLAB_PAGES) * t)
            f.write(build_pages(
                [c[rows] for c in columns], schema,
                visibility=None if visibility is None else visibility[rows],
                nulls={c: m[rows] for c, m in (nulls or {}).items()},
                start_page_id=p0))
    return n_pages


def validate_heap_header(path: str, schema: HeapSchema) -> None:
    """One 64-byte read checks the first page header against *schema*:
    magic, column count (header word 3), visibility mode (word 4), and
    the wide/nullable bitmasks (words 5/6) — the cheap guard that turns
    a wrong column count or a non-heap file into a clear error instead
    of silently garbled columns (pages carry their schema facts exactly
    so consumers CAN check; the reference trusts the catalog the same
    way, pgsql/nvme_strom.c:448-474).  Raises OSError (unreadable) or
    ValueError (mismatch)."""
    with open(path, "rb") as f:
        head = f.read(HEADER_BYTES)
    if len(head) < HEADER_BYTES:
        raise ValueError(f"{path}: not a heap file (short header)")
    w = np.frombuffer(head, np.int32)
    if int(w[0]) != HEAP_MAGIC:
        raise ValueError(f"{path}: bad heap magic "
                         f"0x{int(w[0]) & 0xffffffff:08x}")
    if int(w[3]) != schema.n_cols:
        raise ValueError(f"{path}: file pages carry {int(w[3])} columns, "
                         f"schema says {schema.n_cols}")
    vm = 1 if schema.visibility else 0
    if int(w[4]) != vm:
        raise ValueError(f"{path}: file visibility_mode {int(w[4])} != "
                         f"schema's {vm}")
    if int(w[5]) != schema.wide_mask:
        raise ValueError(f"{path}: file wide-column mask 0x{int(w[5]):x}"
                         f" != schema's 0x{schema.wide_mask:x}")
    if int(w[6]) != schema.null_mask:
        raise ValueError(f"{path}: file nullable mask 0x{int(w[6]):x} "
                         f"!= schema's 0x{schema.null_mask:x}")


def pages_from_bytes(raw: bytes | np.ndarray) -> np.ndarray:
    """View raw bytes as (n_pages, PAGE_SIZE) uint8 without copying."""
    arr = np.frombuffer(raw, dtype=np.uint8) if isinstance(raw, bytes) else raw
    if arr.size % PAGE_SIZE:
        raise ValueError(f"byte length {arr.size} not page-aligned")
    return arr.reshape(-1, PAGE_SIZE)


def read_column(pages: np.ndarray, schema: HeapSchema, c: int,
                dtype=None) -> np.ndarray:
    """Host-side column extraction (test oracle for the XLA kernels)."""
    dtype = dtype if dtype is not None else schema.col_dtype(c)
    words = pages.view(np.int32).reshape(pages.shape[0], PAGE_SIZE // 4)
    s, e = schema.col_word_range(c)
    out = []
    for p in range(pages.shape[0]):
        n = int(words[p, 2])
        w = np.dtype(dtype).itemsize // 4
        out.append(words[p, s:s + n * w].view(dtype))
    return np.concatenate(out) if out else np.empty(0, dtype)


def read_nulls(pages: np.ndarray, schema: HeapSchema,
               c: int) -> np.ndarray:
    """Host-side NULL-mask extraction (True = NULL) — the oracle twin
    of :func:`read_column` for nullable columns."""
    words = pages.view(np.int32).reshape(pages.shape[0], PAGE_SIZE // 4)
    s, e = schema.validity_word_range(c)
    out = []
    for p in range(pages.shape[0]):
        n = int(words[p, 2])
        out.append(~_unpack_validity(words[p, s:e], n))
    return np.concatenate(out) if out else np.empty(0, bool)
