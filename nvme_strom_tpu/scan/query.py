"""Declarative query layer: plan → stream → fold, transparently.

The reference's end-user surface is SQL-transparent — a query planner hook
decides per table whether the direct path is worth it and swaps in the
"NVMe Strom" CustomScan without the user changing a line of SQL
(`pgsql/nvme_strom.c:1642-1667`, cost model `:448-633`).  This module is
that surface for the TPU framework: one :class:`Query` builder that

* plans the access path (direct engine scan vs buffered VFS) with the
  planner's threshold + cost model (`scan/planner.py`),
* plans the compute kernel (Pallas single-pass vs XLA) by backend and
  operator support,
* executes by streaming batches through the async ring
  (:class:`..scan.executor.TableScanner`) or, given a mesh, through the
  sharded batch stream (:func:`..parallel.stream.distributed_scan_filter`)
  where XLA inserts the cross-device collectives,

and :meth:`Query.explain` shows the chosen plan the way ``EXPLAIN`` shows
the reference's custom scan node.

One terminal operator per query (it is one scan node): ``select`` |
``aggregate`` | ``group_by`` | ``top_k`` | ``order_by`` | ``quantiles``
| ``count_distinct`` | ``join``.  Predicates are plain jnp lambdas over
decoded columns — ``lambda cols: cols[0] > 10``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

import numpy as np

from ..api import StromError
from ..scan.heap import PAGE_SIZE, HeapSchema
from .group_keys import GroupKeySet, GroupSpill, lookup_group_ids
from .planner import (capability_cache, cost_direct_scan, cost_vfs_scan,
                      should_use_direct_scan)

__all__ = ["Query", "QueryPlan"]

_PALLAS_MAX_GROUPS = 64   # static unroll bound (ops/groupby_pallas.py)
#: pages of one buffered-scan batch (8 MiB); a mesh step gives each chip
#: one such batch
BATCH_PAGES = (8 << 20) // PAGE_SIZE


def _mesh_steps(n_pages: int, n_shards: int, batch_pages=None):
    """``(step_pages, covered_pages)`` of a mesh scan: one batch a shard
    per step by default; *batch_pages* (the caller's) rounds to a shard
    multiple, never below one page a shard, and shrinks to the largest
    step the table fills, so a small table or an odd *batch_pages* still
    scans.  The pages past *covered_pages* are the tail."""
    bp = batch_pages or BATCH_PAGES * n_shards
    bp = max(bp // n_shards * n_shards, n_shards)
    bp = min(bp, n_pages // n_shards * n_shards)
    if bp < n_shards:
        return n_shards, 0
    return bp, n_pages // bp * bp


@functools.lru_cache(maxsize=64)
def _fetch_gather_fn(schema: HeapSchema, cols: tuple):
    """Jitted point-lookup gather, cached per (schema, cols) so repeated
    fetches hit the jit cache instead of recompiling decode_pages (a
    per-call closure would make every sub-ms lookup pay a compile)."""
    import jax

    from ..ops.filter_xla import decode_pages

    @jax.jit
    def gather(pages_u8, page_idx, slot):
        dcols, valid = decode_pages(pages_u8, schema)
        out = {f"col{c}": dcols[c][page_idx, slot] for c in cols}
        for c in cols:
            if c in dcols.nulls:     # True = NULL (round 5)
                out[f"null{c}"] = dcols.nulls[c][page_idx, slot]
        out["valid"] = valid[page_idx, slot]
        return out

    return gather


class _ScanLimitReached(Exception):
    """Private control flow: the gather collected ``LIMIT`` rows early and
    the scan can stop issuing DMA (the executor stops pulling tuples once
    the plan's limit is satisfied)."""


class _HostCols(dict):
    """Host-side column mapping that quacks like the device decode's
    ``Cols`` for predicate evaluation: ``cols[c]`` values plus
    ``cols.nulls`` masks — the index-path recheck must see the same
    NULL facts the scan kernels see (review finding: a plain dict
    dropped them, and NULL rows' stored zeros matched residuals)."""

    def __init__(self, items, nulls=None):
        super().__init__(items)
        self.nulls = dict(nulls or {})


class _SortedGroupAcc:
    """Running sorted-aggregation state for the GROUP BY spill path:
    a sorted packed-key array plus per-key count/sums/sumsqs/mins/maxs,
    merged batch by batch — footprint O(distinct keys).  Accumulator
    dtypes follow :func:`..ops.groupby.acc_dtypes` exactly so the spill
    path and the one-hot kernels cannot drift (int sums wrap at the
    same width on both)."""

    def __init__(self, n_vals: int, acc_np, sq_np, lo, hi, cap: int):
        self.V, self.cap = n_vals, cap
        self.acc_np, self.sq_np, self.lo, self.hi = acc_np, sq_np, lo, hi
        self.keys: Optional[np.ndarray] = None
        self.count = self.sums = self.sumsqs = None
        self.mins = self.maxs = None

    def _batch_partial(self, kv: np.ndarray, vals: np.ndarray):
        """Sort one batch's (keys, (V, n) values) and segment-reduce."""
        order = np.argsort(kv, kind="stable")
        kv, vals = kv[order], vals[:, order]
        uk, starts = np.unique(kv, return_index=True)
        count = np.diff(np.append(starts, len(kv))).astype(np.int64)
        av = vals.astype(self.acc_np)
        sums = np.add.reduceat(av, starts, axis=1)
        fv = vals.astype(np.float64)
        sumsqs = np.add.reduceat(fv * fv, starts,
                                 axis=1).astype(self.sq_np)
        mins = np.minimum.reduceat(vals, starts, axis=1)
        maxs = np.maximum.reduceat(vals, starts, axis=1)
        return uk, count, sums, sumsqs, mins, maxs

    def add_batch(self, kv: np.ndarray, vals: np.ndarray) -> None:
        if not len(kv):
            return
        self.merge_state(dict(zip(
            ("keys", "count", "sums", "sumsqs", "mins", "maxs"),
            self._batch_partial(kv, vals))))

    def merge_state(self, st: dict) -> None:
        """Merge another sorted partial (a batch's, or a worker's whole
        state) into this one."""
        uk = st["keys"]
        if uk is None or not len(uk):
            return
        if self.keys is None:
            self.keys = uk
            self.count, self.sums = st["count"], st["sums"]
            self.sumsqs = st["sumsqs"]
            self.mins, self.maxs = st["mins"], st["maxs"]
        else:
            merged = np.union1d(self.keys, uk)
            if len(merged) > self.cap:
                raise StromError(12, f"group_by_cols: {len(merged)} "
                                     f"distinct keys exceed even the "
                                     f"sorted-aggregation cap "
                                     f"{self.cap} (unbounded key set)")
            io = np.searchsorted(merged, self.keys)
            iN = np.searchsorted(merged, uk)
            g = len(merged)
            count = np.zeros(g, np.int64)
            count[io] = self.count
            np.add.at(count, iN, st["count"])
            sums = np.zeros((self.V, g), self.acc_np)
            sumsqs = np.zeros((self.V, g), self.sq_np)
            mins = np.full((self.V, g), self.hi)
            maxs = np.full((self.V, g), self.lo)
            sums[:, io] = self.sums
            sumsqs[:, io] = self.sumsqs
            mins[:, io] = self.mins
            maxs[:, io] = self.maxs
            for v in range(self.V):
                np.add.at(sums[v], iN, st["sums"][v])
                np.add.at(sumsqs[v], iN, st["sumsqs"][v])
                np.minimum.at(mins[v], iN, st["mins"][v])
                np.maximum.at(maxs[v], iN, st["maxs"][v])
            self.keys, self.count = merged, count
            self.sums, self.sumsqs = sums, sumsqs
            self.mins, self.maxs = mins, maxs

    def state(self) -> dict:
        """Picklable state (the worker's return value / the leader's
        fold input) — empty-scan state is a zero-group result."""
        if self.keys is None:
            z = np.zeros(0, np.int64)
            return {"keys": z, "count": z,
                    "sums": np.zeros((self.V, 0), self.acc_np),
                    "sumsqs": np.zeros((self.V, 0), self.sq_np),
                    "mins": np.zeros((self.V, 0)),
                    "maxs": np.zeros((self.V, 0))}
        return {"keys": self.keys, "count": self.count,
                "sums": self.sums, "sumsqs": self.sumsqs,
                "mins": self.mins, "maxs": self.maxs}


@dataclass(frozen=True)
class QueryPlan:
    """What ``run()`` will do, decided before any I/O (EXPLAIN analog)."""
    operator: str          # aggregate | group_by | top_k | join | ...
    access_path: str       # direct | vfs | index
    kernel: str            # pallas | xla
    mode: str              # local | mesh
    n_pages: int
    cost_direct: float
    cost_vfs: float
    reason: str
    join_strategy: Optional[str] = None  # broadcast | partitioned(N)
    workers: int = 0       # parallel worker processes (0 = serial)
    cache_hit_ratio: float = 0.0  # expected residency-tier hit fraction
    hbm_hit_ratio: float = 0.0    # expected DEVICE-tier hit fraction
    pushdown: str = ""     # "" | chip | host | raw (packed-sidecar scan)

    def __str__(self) -> str:
        par = f", workers={self.workers}" if self.workers else ""
        cache = (f"  cache-resident: ~{self.cache_hit_ratio:.0%}"
                 if self.cache_hit_ratio > 0 else "")
        cache += (f"  hbm-resident: ~{self.hbm_hit_ratio:.0%}"
                  if self.hbm_hit_ratio > 0 else "")
        return (f"{self.operator} scan  [{self.access_path} path, "
                f"{self.kernel} kernel, {self.mode}{par}]\n"
                f"  pages: {self.n_pages}  cost: direct={self.cost_direct:.0f} "
                f"vfs={self.cost_vfs:.0f}{cache}\n"
                f"  {self.reason}")


class Query:
    """Fluent scan builder over one heap source.

    >>> q = (Query("/data/t.heap", schema)
    ...      .where(lambda cols: cols[0] > 10)
    ...      .group_by(lambda cols: cols[1] % 8, 8, agg_cols=[0]))
    >>> print(q.explain())
    >>> out = q.run()
    """

    def __init__(self, source, schema: HeapSchema, *,
                 stripe_chunk_size: int = 512 << 10, workers: int = 0):
        if isinstance(source, os.PathLike):
            source = str(source)
        elif isinstance(source, (list, tuple)):
            source = [str(p) for p in source]
        self.source = source
        self.schema = schema
        self._stripe_chunk = stripe_chunk_size
        self._workers = int(workers)   # >= 2: parallel worker processes
        self._pred_trees: List[tuple] = []   # picklable predicate trees
        self._opaque_pred = False            # a where() lambda w/o tree
        self._pred: Optional[Callable] = None
        self._residual: Optional[Callable] = None  # index-path recheck
        self._op = "aggregate"
        self._terminal_set = False
        self._agg_cols: Optional[Sequence[int]] = None
        self._agg_exprs: Optional[list] = None   # expression sums
        self._star: Optional[dict] = None        # multi-dim star join
        self._star_resolved: Optional[list] = None
        self._group: Optional[tuple] = None
        self._topk: Optional[tuple] = None
        self._order: Optional[tuple] = None
        self._join: Optional[tuple] = None
        self._join_src: Optional[tuple] = None  # on-disk build side
        self._join_how: str = "inner"           # inner | left | semi | anti
        self._group_cols: Optional[tuple] = None  # value-keyed GROUP BY
        self._group_exprs: Optional[tuple] = None  # its expression sums
        self._select: Optional[tuple] = None
        self._quantiles: Optional[List[float]] = None
        self._eq: Optional[tuple] = None     # structured equality (col, v)
        self._range: Optional[tuple] = None  # structured range (col, lo, hi)
        self._in: Optional[tuple] = None     # structured IN (col, members)

    # -- builders -----------------------------------------------------------
    def where(self, predicate: Callable, *, _tree=None) -> "Query":
        """Row filter: ``predicate(cols) -> (B, T) bool`` (jnp ops only).

        Chained filters COMPOSE as a conjunction (the SQL-builder
        convention): ``where(a).where(b)`` selects rows passing both.
        Composed onto a STRUCTURED filter (:meth:`where_eq` /
        :meth:`where_range` / :meth:`where_in`), the predicate becomes a
        RESIDUAL — the seqscan applies the conjunction and the index
        path RECHECKS index-resolved rows against it (PG's Index Cond +
        Filter shape), so adding a predicate never demotes an
        index-capable query to a seqscan.  The structured setters
        replace the WHOLE filter (they define a new index condition).

        ``_tree`` (internal, set by the SQL facade) carries the
        predicate's picklable condition tree so worker processes can
        reconstruct it; a bare lambda marks the query non-parallel."""
        if _tree is not None:
            self._pred_trees.append(_tree)
        else:
            self._opaque_pred = True
        if self._pred is not None:
            old = self._pred
            self._pred = lambda cols: old(cols) & predicate(cols)
            if self._index_col() is not None:
                prev = self._residual
                self._residual = predicate if prev is None else \
                    (lambda cols, p=prev: p(cols) & predicate(cols))
            return self
        self._pred = predicate
        return self

    def _null_guard(self, pred, *cols_):
        """SQL comparison semantics on nullable columns: NULL cmp x is
        never true — wrap a structured predicate so NULL rows of the
        referenced columns can't match (their STORED word is 0, which a
        bare ``col == 0`` would otherwise select)."""
        nn = tuple(c for c in cols_ if self.schema.col_nullable(c))
        if not nn:
            return pred

        def wrapped(cols, base=pred, nn=nn):
            m = base(cols)
            for c in nn:
                m = m & ~cols.nulls[c]
            return m
        return wrapped

    def _set_structured(self, *, eq=None, rng=None, members=None) -> None:
        """Install exactly one structured filter (the others clear; a
        stale residual from a previous filter generation must never
        survive into the new one's index recheck)."""
        self._eq = eq
        self._range = rng
        self._in = members
        self._residual = None
        # the structured setters replace the WHOLE filter — any prior
        # opaque where() is gone, so the query is shippable again
        self._pred_trees = []
        self._opaque_pred = False

    def where_eq(self, col: int, value) -> "Query":
        """Structured equality filter: ``col == value``.  Unlike the
        opaque :meth:`where` lambda, the planner can SEE this one — when
        a fresh sorted index sidecar exists for *col* (built by
        :func:`..scan.index.build_index`), a :meth:`select` runs as an
        INDEX SCAN touching only matching pages; every other terminal
        (and a missing/stale index) falls back to the filtered seqscan,
        the way the reference's planner hook transparently swaps access
        paths (`pgsql/nvme_strom.c:1642-1667`).

        The literal is normalized to the COLUMN dtype up front so both
        access paths agree: a float literal against a float32 column
        compares as float32 (``0.1`` matches stored ``float32(0.1)``),
        and a non-integral literal against an integer column matches
        nothing — on the seqscan AND the index.

        **Composite equality**: *col* may be a pair ``(c0, c1)`` with
        *value* a matching pair ``(v0, v1)`` — SQL's
        ``c0 = v0 AND c1 = v1``.  With a fresh composite sidecar
        (``build_index(..., (c0, c1))``) the pair resolves in ONE packed-
        key probe; otherwise it seqscans with the conjunction."""
        if isinstance(col, (tuple, list)):
            if len(col) != 2 or not isinstance(value, (tuple, list)) \
                    or len(value) != 2:
                raise StromError(22, "composite where_eq takes a column "
                                     "PAIR and a value PAIR")
            c0, c1 = int(col[0]), int(col[1])
            for c in (c0, c1):
                if not 0 <= c < self.schema.n_cols:
                    raise StromError(22, f"where_eq column {c} out of range")
            v0 = self._representable(self.schema.col_dtype(c0), value[0])
            v1 = self._representable(self.schema.col_dtype(c1), value[1])
            if v0 is None or v1 is None:
                self._pred = lambda cols: cols[c0] != cols[c0]
                self._set_structured(eq=((c0, c1), None))  # index: empty
            else:
                self._pred = self._null_guard(
                    lambda cols: (cols[c0] == v0) & (cols[c1] == v1),
                    c0, c1)
                self._set_structured(eq=((c0, c1), (v0, v1)))
            return self
        if not 0 <= col < self.schema.n_cols:
            raise StromError(22, f"where_eq column {col} out of range")
        dt = self.schema.col_dtype(col)
        v = self._representable(dt, value)
        if v is None:
            # the literal has no exact representative in the column dtype
            # (non-integral or out-of-range vs int, e.g. 7.5 or 2**40):
            # SQL says no row matches — on BOTH paths, never a wraparound
            self._pred = lambda cols: cols[col] != cols[col]
            self._set_structured(eq=(int(col), None))  # index: empty
        else:
            self._pred = self._null_guard(
                lambda cols: cols[col] == v, col)
            self._set_structured(eq=(int(col), v))
        return self

    def where_in(self, col: int, values) -> "Query":
        """Structured membership filter: ``col IN values`` (SQL IN).
        Planner-visible like :meth:`where_eq`; with a fresh sidecar the
        index resolves every member's positions.  Members with no exact
        representative in the column dtype (7.5 against int32) can match
        no row and simply drop out."""
        if not 0 <= col < self.schema.n_cols:
            raise StromError(22, f"where_in column {col} out of range")
        dt = self.schema.col_dtype(col)
        reps = [self._representable(dt, v) for v in values]
        members = np.unique(np.array([r for r in reps if r is not None],
                                     dt))
        if dt.kind == "f":
            # a NaN member can never equal any row (IEEE; the seqscan's
            # isin agrees) — drop it so the index path cannot disagree
            # either (searchsorted would bracket NaN keys if a sidecar
            # ever carried them, e.g. one built outside build_index)
            members = members[~np.isnan(members)]
        if len(members) == 0:
            # identically False even for NaN rows (x != x alone would
            # select NaN on a float column)
            self._pred = lambda cols: (cols[col] == cols[col]) \
                & (cols[col] != cols[col])
            self._set_structured(members=(int(col), np.zeros(0, dt)))
            return self

        def pred(cols):
            import jax.numpy as jnp
            return jnp.isin(cols[col], members)

        self._pred = self._null_guard(pred, col)
        self._set_structured(members=(int(col), members))
        return self

    @staticmethod
    def _representable(dt: np.dtype, value):
        """The literal as an exact np scalar of *dt*, or None when no
        such value exists (non-integral/out-of-range against an int
        column — astype would silently WRAP, changing which rows match).
        Float columns always cast (the jnp weak-typing semantics the
        seqscan applies)."""
        if dt.kind in "iu":
            f = float(value)
            if not np.isfinite(f) or f != int(f):
                return None
            i = int(value)
            info = np.iinfo(dt)
            if not info.min <= i <= info.max:
                return None
            return dt.type(i)
        return dt.type(float(value))

    def where_range(self, col: int, lo=None, hi=None) -> "Query":
        """Structured range filter: ``lo <= col <= hi`` (either bound may
        be None for open-ended).  Planner-visible like :meth:`where_eq`:
        a fresh sidecar turns a :meth:`select` into an index RANGE scan
        reading only matching pages; everything else seqscans with the
        filter."""
        if not 0 <= col < self.schema.n_cols:
            raise StromError(22, f"where_range column {col} out of range")
        if lo is None and hi is None:
            raise StromError(22, "where_range needs at least one bound")
        dt = self.schema.col_dtype(col)
        # normalize bounds so the index searchsorted and the seqscan
        # predicate agree (and never overflow):
        #  - float column: bounds cast to the column dtype (the seqscan's
        #    weak-typing would compare at float32, so the index must too)
        #  - int column: fractional bounds tighten to the nearest integer
        #    (7.5 means ">= 8" / "<= 7") as exact dt scalars, so the
        #    seqscan (float32 weak typing) and the index searchsorted
        #    (float64) can never disagree at magnitudes > 2^24; bounds
        #    beyond the dtype's range clamp to open / empty instead of
        #    wrapping or raising
        never = False
        if dt.kind == "f":
            nlo = None if lo is None else dt.type(float(lo))
            nhi = None if hi is None else dt.type(float(hi))
        else:
            info = np.iinfo(dt)
            nlo = nhi = None
            if lo is not None:
                if float(lo) > info.max:
                    never = True           # nothing can be >= lo
                else:
                    ilo = int(math.ceil(float(lo)))
                    if ilo > info.min:
                        nlo = dt.type(min(ilo, info.max))
            if hi is not None and not never:
                if float(hi) < info.min:
                    never = True           # nothing can be <= hi
                else:
                    ihi = int(math.floor(float(hi)))
                    if ihi < info.max:
                        nhi = dt.type(max(ihi, info.min))
        if never:
            # an empty range encodes "never": lo > hi on both paths
            nlo, nhi = dt.type(1), dt.type(0)

        def pred(cols):
            m = cols[col] == cols[col] if dt.kind != "f" \
                else ~(cols[col] != cols[col])   # NaN rows never match
            if nlo is not None:
                m = m & (cols[col] >= nlo)
            if nhi is not None:
                m = m & (cols[col] <= nhi)
            return m

        self._pred = self._null_guard(pred, col)
        self._set_structured(rng=(int(col), nlo, nhi))
        return self

    def select(self, cols: Optional[Sequence[int]] = None, *,
               limit: Optional[int] = None, offset: int = 0) -> "Query":
        """Terminal: materialize the selected rows themselves — projected
        column values + global row positions, the face the reference scan
        actually exposes (tuples handed back to the executor,
        `pgsql/nvme_strom.c:941-979`).  ``cols=None`` projects every
        column.  ``limit`` stops the scan early once enough rows are
        gathered; row order is physical arrival order (SQL without ORDER
        BY — use :meth:`order_by`/:meth:`top_k` for ordered heads)."""
        self._require_no_terminal()
        if limit is not None and limit < 0:
            raise StromError(22, "select limit must be >= 0")
        if offset < 0:
            raise StromError(22, "select offset must be >= 0")
        self._op = "select"
        self._terminal_set = True
        self._select = (None if cols is None else [int(c) for c in cols],
                        limit, int(offset))
        return self

    def aggregate(self, cols: Optional[Sequence[int]] = None) -> "Query":
        """Terminal: selected-row count + per-column masked sums."""
        self._require_no_terminal()
        self._op = "aggregate"
        self._terminal_set = True
        self._agg_cols = cols
        return self

    def group_by(self, key_fn: Callable, n_groups: int, *,
                 agg_cols: Optional[Sequence[int]] = None,
                 having: Optional[Callable] = None) -> "Query":
        """Terminal: per-group count/sum/min/max/avg/var/stddev.
        ``key_fn(cols) -> (B, T) int32`` ids in ``[0, n_groups)``.

        ``having(groups) -> (G,) bool`` filters groups AFTER aggregation
        (SQL HAVING): it receives the finished numpy result
        (``count (G,)``, ``sums/sumsqs/mins/maxs/avgs/vars/stds (V, G)``)
        and surviving groups are compressed out, their original ids in
        ``"groups"``."""
        self._require_no_terminal()
        self._op = "group_by"
        self._terminal_set = True
        self._group = (key_fn, int(n_groups), agg_cols, having)
        return self

    def group_by_cols(self, key_cols, *,
                      agg_cols: Optional[Sequence[int]] = None,
                      agg_exprs: Optional[Sequence] = None,
                      having: Optional[Callable] = None,
                      max_groups: int = 1 << 16) -> "Query":
        """Terminal: SQL ``GROUP BY col[, col2]`` over actual column
        VALUES — no key function, no group-count guess.  The group
        kernels take an exact-membership key function over a key table
        (:mod:`.group_keys`).  A single-process scan reads the table
        once: the key set starts from the first page batch's keys and
        grows inside the aggregation scan as batches meet keys it lacks
        (:class:`.group_keys.OnePassGroups`).  A fresh sidecar gives the
        whole key set at zero table I/O instead; worker processes, the
        mesh and the index path discover the keys first by a projection
        scan.  Finding keys is timed as the ``group_discover`` stage.

        Result = :meth:`group_by`'s (count/sums/mins/maxs/avgs/...)
        plus ``key_cols``: one array per key column, aligned with the
        surviving groups — the SELECT-list face SQL gives GROUP BY, in
        key order.  Groups that select no rows are dropped (SQL
        semantics); *having* then filters like :meth:`group_by`'s.  One
        to four integer columns; past *max_groups* distinct keys, column
        aggregates over one or two key columns take the sorted
        aggregation, anything else fails with ENOMEM instead of silently
        truncating.

        *agg_exprs* (SQL's ``SUM(expr)``/``AVG(expr)`` under GROUP BY):
        expression trees in the :meth:`aggregate_exprs` grammar.  The
        whole statement then runs on the grouped expression kernel
        (:func:`..ops.groupby.make_group_efn`), which computes only
        counts and sums: the result is ``count``, ``sums``/``avgs``
        (one array per *agg_cols* column) and ``esums``/``eavgs`` (one
        per expression), each of any dtype; no mins, maxs or variances.
        AVG is sum / count in float64 on the host."""
        self._require_no_terminal()
        cols_ = [int(c) for c in (key_cols if isinstance(
            key_cols, (tuple, list)) else [key_cols])]
        if not 1 <= len(cols_) <= 4:
            raise StromError(22, "group_by_cols takes 1-4 key columns")
        for c in cols_:
            if not 0 <= c < self.schema.n_cols:
                raise StromError(22, f"group_by_cols column {c} out of "
                                     f"range")
            if self.schema.col_dtype(c).kind not in "iu" \
                    or self.schema.col_dtype(c).itemsize != 4:
                raise StromError(22, "group_by_cols keys must be 4-byte "
                                     "integer columns")
            if self.schema.col_nullable(c):
                raise StromError(22, f"group_by_cols: c{c} is nullable "
                                     f"(NULL group keys are outside "
                                     f"this subset)")
        if max_groups < 1:
            raise StromError(22, "max_groups must be >= 1")
        if agg_exprs is not None:
            if not agg_exprs:
                raise StromError(22, "agg_exprs needs >= 1 expression")
            exprs = [("col", int(c)) for c in agg_cols or ()] + \
                list(agg_exprs)
            self._check_expr_trees(exprs)
            # one kernel sum per distinct tree (told apart by repr, so an
            # int literal never merges with its float twin); the index
            # list maps each requested aggregate, columns first, onto it
            keys = [repr(e) for e in exprs]
            uniq = list(dict.fromkeys(keys))
            self._group_exprs = ([exprs[keys.index(k)] for k in uniq],
                                 [uniq.index(k) for k in keys])
        self._op = "group_by"
        self._terminal_set = True
        # key_fn None = unresolved; run() finds the keys
        self._group = (None, 0, agg_cols, None)
        self._group_cols = (cols_, agg_cols, having, int(max_groups))
        return self

    def _resolve_group_keys(self, session, device) -> None:
        """Discover the distinct key set first, then install the derived
        key function, the group count and the composed HAVING into
        ``self._group`` (the path of workers, the mesh and the index)."""
        self._install_group_keys(self._discover_group_keys(session,
                                                           device))

    def _discover_group_keys(self, session, device) -> np.ndarray:
        """Discovery half of :meth:`_resolve_group_keys`: the sorted
        distinct key set (packed uint64 for pairs, (g, N) lex rows for
        3-4 keys) from a fresh sidecar at zero table I/O, else a
        streamed projection scan.  Raises :class:`GroupSpill` past
        ``max_groups``.  Timed as the ``group_discover`` stage, whose
        scan's ``vfs_read`` and ``staging_retire`` stages nest in it."""
        from ..stats import stats
        with stats.stage("group_discover"):
            return self._discover_group_keys_staged(session, device)

    def _group_key_sidecar(self) -> Optional[str]:
        """The fresh sorted sidecar whose uniques are the GROUP BY key
        set (one column, or a composite pair), else None."""
        cols_ = self._group_cols[0]
        if not isinstance(self.source, str) or len(cols_) > 2:
            return None
        from .index import index_path_for, probe_index
        want = cols_[0] if len(cols_) == 1 else tuple(cols_)
        ip = index_path_for(self.source, want)
        try:
            ok = probe_index(ip, self.source, expect_col=want,
                             allow_prefix=False)
        except Exception:   # raced away: the scan discovers
            return None
        return ip if ok else None

    def _group_onepass(self, plan: QueryPlan) -> bool:
        """Whether this run finds the keys inside the aggregation scan:
        a local scan of the table (not the index path), with no fresh
        sidecar to give the keys at zero table I/O."""
        return (plan.mode == "local" and plan.kernel != "invalid"
                and plan.access_path in ("vfs", "direct")
                and self._group_key_sidecar() is None)

    def _discover_group_keys_staged(self, session, device) -> np.ndarray:
        from .index import pack_pair
        cols_, _agg, _hv, max_groups = self._group_cols
        dts = [self.schema.col_dtype(c) for c in cols_]
        discovered = None
        ip = self._group_key_sidecar()
        if ip is not None:
            # fresh sidecar shortcut: the distinct keys are the sorted
            # sidecar's uniques — zero table I/O.  Composite (c0, c1)
            # sidecars serve PAIR grouping the same way (their packed
            # uint64 keys use the same pack_pair ordering discovery
            # derives by scanning)
            from .index import open_index
            try:
                discovered = np.unique(
                    open_index(ip, table_path=self.source).keys)
            except Exception:   # raced away: fall to the scan
                discovered = None
        if discovered is not None and len(discovered) > max_groups:
            raise GroupSpill(len(discovered))
        if discovered is None:
            gather, _f, _d = self._make_gather_fn(cols_,
                                                  want_positions=False)
            nk = len(cols_)
            if nk <= 2:
                merged = np.zeros(0, np.uint64 if nk == 2 else dts[0])
            else:   # N-column keys: (k, N) row array, lexicographic
                merged = np.zeros((0, nk), np.int64)

            def collect(pages_dev):
                nonlocal merged
                out = gather(pages_dev)
                m = np.asarray(out["mask"]).astype(bool)
                vs = [np.asarray(out[f"f{i}"])[m]
                      for i in range(nk)]
                if nk == 1:
                    merged = np.union1d(merged, np.unique(vs[0]))
                elif nk == 2:
                    merged = np.union1d(merged, np.unique(
                        pack_pair(vs[0], vs[1], dts[0], dts[1])))
                else:
                    u = np.unique(np.stack(
                        [v.astype(np.int64) for v in vs], 1), axis=0)
                    merged = np.unique(
                        np.concatenate([merged, u]), axis=0)
                if len(merged) > max_groups:
                    raise GroupSpill(len(merged))
                return {}

            self._stream_collect(self._explain_inner(), collect, device,
                                 session)
            discovered = merged
        return discovered

    def _group_having(self):
        """The value-keyed GROUP BY's HAVING: groups that select no row
        dropped, then the caller's *having*."""
        user = self._group_cols[2]

        def hv(res):
            m = np.asarray(res["count"]) > 0
            if user is not None:
                m = m & np.asarray(user(res)).astype(bool)
            return m
        return hv

    def _install_group_keys(self, discovered: np.ndarray) -> None:
        """Installation half of :meth:`_resolve_group_keys`: the exact-
        membership key function over the (already discovered, possibly
        worker-shipped) sorted key set, with group id = the key's rank
        and the sentinel after the last, and the composed HAVING, into
        ``self._group``."""
        from .index import unpack_second
        cols_, _agg, _hv, max_groups = self._group_cols
        dts = [self.schema.col_dtype(c) for c in cols_]
        self._group_discovered = discovered   # worker spec ships this
        if len(cols_) == 1:
            kcols = [discovered.astype(dts[0])]
        elif len(cols_) == 2:
            hi = (discovered >> np.uint64(32)).astype(np.int64)
            kcols = [(hi - (1 << 31) if dts[0] == np.dtype(np.int32)
                      else hi).astype(dts[0]),
                     unpack_second(discovered, dts[1])]
        else:                                    # (g, N) lex-sorted rows
            kcols = [discovered[:, j].astype(dts[j])
                     for j in range(len(cols_))]
        keys = GroupKeySet(dts, max_groups)
        keys.add(kcols)
        g = len(keys)
        tabs, ids = keys.table(sentinel=g)

        def key_fn(cols, tabs=tabs, ids=ids):
            return lookup_group_ids(cols, cols_, tabs, ids, g)

        self._set_group_keys(keys, key_fn, g + 1)

    def _set_group_keys(self, keys: GroupKeySet, key_fn, n_groups: int):
        """Install a found key set: the kernels' group ids are its ids,
        results list its groups in key order (:meth:`_finalize` takes
        them, dropping the sentinel and unused ids), and ``key_cols``
        decodes them."""
        order = keys.key_order()
        self._group_take = order
        sorted_cols = [c[order] for c in keys.cols]
        self._gk_decode = lambda gids: [c[gids] for c in sorted_cols]
        self._group = (key_fn, n_groups, self._group_cols[1],
                       self._group_having())

    def top_k(self, col: int, k: int, *, largest: bool = True) -> "Query":
        """Terminal: k best values of *col* + their global row positions."""
        self._require_no_terminal()
        if 0 <= int(col) < self.schema.n_cols:
            if self.schema.col_nullable(int(col)):
                raise StromError(22, f"top_k over the nullable c{col} "
                                     f"is outside this subset (no NULL "
                                     f"ordering)")
            if self.schema.col_dtype(int(col)).itemsize != 4:
                raise StromError(22, f"top_k supports 4-byte columns "
                                     f"(c{col} is 8-byte)")
        self._op = "top_k"
        self._terminal_set = True
        self._topk = (int(col), int(k), largest)
        return self

    def order_by(self, col, *, descending: bool = False,
                 limit: Optional[int] = None, offset: int = 0) -> "Query":
        """Terminal: the full ordering over selected rows — sorted primary
        column values + their global row positions.  *col* may be a
        sequence of column indices (ORDER BY c_a, c_b, ...): later
        columns break ties of earlier ones; ``descending`` applies to the
        whole ordering.  ``limit``/``offset`` slice the sorted output
        (ORDER BY ... LIMIT n OFFSET m; for a small head :meth:`top_k`
        streams without materializing the whole order).  With a mesh,
        runs the distributed sample sort (single key column only); device
        *b* ends up owning the *b*-th key range — the
        ``per_device_count`` info key always describes that full
        pre-slice distribution, not the sliced arrays."""
        self._require_no_terminal()
        if limit is not None and limit < 0:
            raise StromError(22, "order_by limit must be >= 0")
        if offset < 0:
            raise StromError(22, "order_by offset must be >= 0")
        cols = [int(col)] if isinstance(col, (int, np.integer)) \
            else [int(c) for c in col]
        if not cols:
            raise StromError(22, "order_by needs at least one column")
        self._op = "order_by"
        self._terminal_set = True
        self._order = (cols, descending, limit, int(offset))
        return self

    def quantiles(self, col: int, qs: Sequence[float]) -> "Query":
        """Terminal: exact quantiles of *col* over selected rows (nearest-
        rank on the true sorted order — percentile/median without
        materializing the ordering for the caller).  With a mesh, rides
        the distributed sample sort: only the per-device bucket holding
        each rank is touched, using the bucket count distribution."""
        self._require_no_terminal()
        qs = [float(q) for q in qs]
        if not qs:
            raise StromError(22, "quantiles needs at least one q")
        for q in qs:
            if not 0.0 <= q <= 1.0:
                raise StromError(22, f"quantile {q} outside [0, 1]")
        self._op = "quantiles"
        self._terminal_set = True
        self._order = ([int(col)], False, None, 0)  # reuses the sort shape
        self._quantiles = qs
        return self

    def count_distinct(self, col: int) -> "Query":
        """Terminal: exact COUNT(DISTINCT col) of selected rows — the
        distributed sort + per-bucket run count under a mesh, a local
        unique count otherwise (each float NaN counts as distinct on
        both paths)."""
        self._require_no_terminal()
        self._op = "count_distinct"
        self._terminal_set = True
        # reuses the order_by gather shape
        self._order = ([int(col)], False, None, 0)
        return self

    def join(self, probe_col: int, build_keys: np.ndarray,
             build_values: np.ndarray, *, materialize: bool = False,
             limit: Optional[int] = None, offset: int = 0,
             how: str = "inner") -> "Query":
        """Terminal: join against a host-side dimension table.

        ``how`` — ``"inner"`` (default), ``"left"`` (every selected
        probe row; unpartnered rows carry payload 0 and a False
        ``matched`` NULL indicator), ``"semi"`` (EXISTS — partnered rows,
        build payload not exposed), or ``"anti"`` (NOT EXISTS — rows
        without a partner).  Every strategy (broadcast, Grace local
        passes, mesh partitioned, index-served) serves every face.

        Default: fold aggregates over emitted rows — ``matched``/
        ``sums``, plus ``payload_sum`` (inner/left) and ``null_count``
        (left).  ``materialize=True`` returns the rows themselves —
        ``{"positions", "keys", "count"}`` plus ``payload`` (inner/left)
        and ``matched`` (left) — with ``limit``/``offset`` slicing like
        :meth:`select` (the early DMA cut-off included)."""
        from ..ops.join import check_join_how
        self._require_no_terminal()
        try:
            check_join_how(how)
        except ValueError as e:
            raise StromError(22, str(e)) from None
        if 0 <= int(probe_col) < self.schema.n_cols \
                and self.schema.col_nullable(int(probe_col)):
            raise StromError(22, f"join probe column c{probe_col} is "
                                 f"nullable (NULL keys never match; "
                                 f"outside this subset)")
        if limit is not None and limit < 0:
            raise StromError(22, "join limit must be >= 0")
        if offset < 0:
            raise StromError(22, "join offset must be >= 0")
        if not materialize and (limit is not None or offset):
            # silently aggregating the whole table under a "limit" would
            # be a lie; row slicing only means something for rows
            raise StromError(22, "join limit/offset require "
                                 "materialize=True")
        self._op = "join"
        self._terminal_set = True
        self._join = (int(probe_col), build_keys, build_values,
                      materialize, limit, int(offset))
        self._join_how = how
        return self

    def join_table(self, probe_col: int, build_table, build_schema,
                   key_col: int, value_col: int, *,
                   materialize: bool = False,
                   limit: Optional[int] = None, offset: int = 0,
                   how: str = "inner") -> "Query":
        """Terminal: join (``how`` as in :meth:`join`) whose build side
        is an ON-DISK heap
        table instead of host arrays (the bounded-build face, VERDICT
        r3 #8).  A build table that broadcasts (fits
        ``config join_broadcast_max``) is loaded with one projection
        scan and then behaves exactly like :meth:`join`.  A larger one
        is NEVER fully materialized on the host: the mesh path streams
        it into hash partitions in Grace passes
        (:func:`..parallel.pjoin.partition_build_sharded_from_table`);
        the local path streams one partition per probe pass — host RAM
        stays bounded to one partition plus a scan batch either way."""
        if isinstance(build_table, os.PathLike):
            build_table = str(build_table)
        # validate BEFORE claiming the terminal slot: a rejected call
        # must leave the query reusable
        for c in (key_col, value_col):
            if not 0 <= int(c) < build_schema.n_cols:
                raise StromError(22, f"join_table column {c} out of range")
        if build_schema.col_dtype(int(key_col)) != np.dtype(np.int32):
            raise StromError(22, "join_table key column must be int32")
        if build_schema.col_dtype(int(value_col)).kind not in "iuf":
            raise StromError(22, "join_table value column must be "
                                 "int32/uint32/float32")
        # header check up front: a missing file, a non-heap file, or a
        # schema whose column count disagrees with what the pages carry
        # must fail HERE with a clear error, not surface later as a raw
        # OSError or silently garbled keys
        from .heap import validate_heap_header
        try:
            validate_heap_header(build_table, build_schema)
        except (OSError, ValueError) as e:
            raise StromError(getattr(e, "errno", None) or 22,
                             f"join_table build table: {e}") from e
        self.join(probe_col, None, None, materialize=materialize,
                  limit=limit, offset=offset, how=how)
        self._join_src = (build_table, build_schema, int(key_col),
                          int(value_col))
        return self

    def aggregate_exprs(self, exprs) -> "Query":
        """Terminal: selected-row count + masked sums of EXPRESSIONS
        over fact columns — SQL's ``SUM(c1*c2)`` / ``AVG(c0+5)`` face.
        *exprs* are picklable trees in the :mod:`.sql` expression
        grammar (``("col", c) | ("lit", v) | ("neg", e) |
        ("bin", op, l, r)``); each evaluates per row on device (int32
        arithmetic wraps at the storage width, float math runs at
        float32) and sums under the scan mask.  The reference's scan
        gets this for free from the executor above it
        (pgsql/nvme_strom.c:941-979); here the expressions fuse INTO the
        scan kernel.  Result: ``{"count", "esums": [scalar per expr]}``.
        """
        self._require_no_terminal()
        exprs = list(exprs)
        if not exprs:
            raise StromError(22, "aggregate_exprs needs >= 1 expression")
        self._check_expr_trees(exprs)
        self._op = "aggregate"
        self._terminal_set = True
        self._agg_exprs = exprs
        return self

    def _check_expr_trees(self, exprs) -> None:
        """EINVAL for a tree outside the expression subset or reading a
        nullable column: a NULL operand makes the whole expression NULL,
        and the fused kernels have no per-row NULL propagation, so they
        refuse instead of summing stored zeros."""
        from .sql import _expr_info
        for e in exprs:
            _dt, cs = _expr_info(e, self.schema)
            for c in cs:
                if self.schema.col_nullable(c):
                    raise StromError(22, f"SQL: expression aggregates "
                                         f"over the nullable c{c} are "
                                         f"outside this subset (NULL "
                                         f"propagation)")

    def star_join(self, joins, *, materialize: bool = False,
                  fact_cols: Optional[Sequence[int]] = None,
                  exprs: Optional[Sequence] = None,
                  limit: Optional[int] = None, offset: int = 0) -> "Query":
        """Terminal: probe SEVERAL broadcast dimension tables in ONE
        scan pass — the star-schema query shape the reference gets from
        the executor above its scan (`pgsql/nvme_strom.c:941-979`
        composes any joins over the handed-up tuples).

        *joins* — a sequence of dicts, one per dimension::

            {"probe_col": int,          # fact column carrying the key
             "table": path, "schema": HeapSchema,   # on-disk dim table
             "key_col": int,            # int32 unique-key column
             "value_col": int | None,   # payload column (None: no
                                        #  payload face — semi/anti)
             "how": "inner"|"left"|"semi"|"anti"}

        Every dimension must fit ``config join_broadcast_max`` (each is
        loaded once and probed as a sorted broadcast table); a larger
        build refuses with EINVAL — join it singly (the partitioned
        path) and CTAS the result instead.

        Default face: additive aggregates — ``count`` (rows passing all
        dims + the filter), ``sums`` (every fact column), ``pay_sums``
        (per-dim payload over partnered emitted rows), ``null_counts``
        (per-dim unpartnered emitted rows — the LEFT NULL face), and
        ``esums`` for optional expression trees (*exprs*, the
        :meth:`aggregate_exprs` grammar).  ``materialize=True`` returns
        the rows: requested *fact_cols*, per-dim payload + partner mask,
        positions, with ``limit``/``offset`` slicing like
        :meth:`select`."""
        from ..config import config as _cfg
        from ..ops.join import check_join_how
        from .heap import validate_heap_header
        self._require_no_terminal()
        joins = [dict(j) for j in joins]
        if len(joins) < 1:
            raise StromError(22, "star_join needs >= 1 dimension")
        cap = int(_cfg.get("join_broadcast_max"))
        for j in joins:
            try:
                check_join_how(j.get("how", "inner"))
            except ValueError as e:
                raise StromError(22, str(e)) from None
            j.setdefault("how", "inner")
            pc = int(j["probe_col"])
            if not 0 <= pc < self.schema.n_cols:
                raise StromError(22, f"star_join probe column {pc} out "
                                     f"of range")
            if self.schema.col_dtype(pc) != np.dtype(np.int32) \
                    or self.schema.col_nullable(pc):
                raise StromError(22, "star_join probe columns must be "
                                     "non-nullable int32")
            bs = j["schema"]
            if isinstance(j["table"], os.PathLike):
                j["table"] = str(j["table"])
            kc, vc = int(j["key_col"]), j["value_col"]
            if not 0 <= kc < bs.n_cols:
                raise StromError(22, f"star_join key column {kc} out of "
                                     f"range")
            if bs.col_dtype(kc) != np.dtype(np.int32):
                raise StromError(22, "star_join key columns must be "
                                     "int32")
            if vc is not None:
                vc = int(vc)
                if not 0 <= vc < bs.n_cols:
                    raise StromError(22, f"star_join value column {vc} "
                                         f"out of range")
                if bs.col_dtype(vc).kind not in "iuf":
                    raise StromError(22, "star_join value columns must "
                                         "be int32/uint32/float32")
                if j["how"] in ("semi", "anti"):
                    raise StromError(22, f"star_join: {j['how']} "
                                         f"dimensions expose no payload "
                                         f"(EXISTS semantics)")
                j["value_col"] = vc
            try:
                validate_heap_header(j["table"], bs)
            except (OSError, ValueError) as e:
                raise StromError(getattr(e, "errno", None) or 22,
                                 f"star_join build table: {e}") from e
            rows = (os.path.getsize(j["table"]) // PAGE_SIZE) \
                * bs.tuples_per_page
            if rows * 8 > cap:
                raise StromError(22, f"star_join: dimension "
                                     f"{j['table']} (~{rows} rows) is "
                                     f"above join_broadcast_max — join "
                                     f"it singly (the partitioned path) "
                                     f"and CTAS the result")
        if exprs:
            from .sql import _expr_info
            for e in exprs:
                _dt, cs = _expr_info(e, self.schema)
                for c in cs:
                    if self.schema.col_nullable(c):
                        raise StromError(22, f"SQL: expression "
                                             f"aggregates over the "
                                             f"nullable c{c} are "
                                             f"outside this subset "
                                             f"(NULL propagation)")
        if materialize:
            if limit is not None and limit < 0:
                raise StromError(22, "star_join limit must be >= 0")
            if offset < 0:
                raise StromError(22, "star_join offset must be >= 0")
            fact_cols = [int(c) for c in (fact_cols or [])]
            for c in fact_cols:
                if not 0 <= c < self.schema.n_cols:
                    raise StromError(22, f"star_join fact column {c} "
                                         f"out of range")
        elif limit is not None or offset:
            raise StromError(22, "star_join limit/offset require "
                                 "materialize=True")
        self._op = "star"
        self._terminal_set = True
        self._star = {"joins": joins, "materialize": bool(materialize),
                      "fact_cols": list(fact_cols or []),
                      "exprs": list(exprs or []), "limit": limit,
                      "offset": int(offset)}
        self._star_resolved = None
        return self

    def _resolve_star_builds(self, session, device) -> None:
        """Load every dimension (one projection scan each) into the
        sorted host-array form the star kernels capture; idempotent."""
        from ..ops.join import _sorted_build
        if getattr(self, "_star_resolved", None) is not None:
            return
        resolved = []
        for j in self._star["joins"]:
            bs, kc, vc = j["schema"], j["key_col"], j["value_col"]
            cols = [kc] if vc is None or vc == kc else [kc, vc]
            out = Query(j["table"], bs).select(cols).run(session=session,
                                                         device=device)
            bk = np.asarray(out[f"col{kc}"], np.int32)
            bv = None if vc is None else np.asarray(
                out[f"col{vc}"], bs.col_dtype(vc))
            try:
                keys, vals = _sorted_build(
                    bk, bk if bv is None else bv, self.schema,
                    j["probe_col"])
            except ValueError as e:
                raise StromError(22, f"star_join {j['table']}: {e}") \
                    from None
            resolved.append((j["probe_col"], keys,
                             None if bv is None else vals, j["how"]))
        self._star_resolved = resolved

    def _star_expr_parts(self):
        """(expr_fns, expr_zeros, expr_accs) for the star/expr kernels."""
        from ..ops.groupby import acc_dtypes
        from .sql import _eval_expr, _expr_info
        fns, zeros, accs = [], [], []
        if self._op == "star":
            trees = self._star["exprs"]
        elif self._op == "group_by":
            trees = self._group_exprs[0]
        else:
            trees = self._agg_exprs
        for e in trees:
            dt, _cols = _expr_info(e, self.schema)
            fns.append(lambda cols, e=e: _eval_expr(e, cols))
            zeros.append(dt.type(0))
            accs.append(acc_dtypes(dt)[0])
        return fns, zeros, accs

    def _run_star_rows(self, plan: QueryPlan, device, session) -> dict:
        """Star row face: stream the scan, probe every dimension per
        batch, hand the emitted rows back (fact cols + per-dim payload/
        partner + positions)."""
        from ..ops.join import make_star_rows_fn
        st = self._star
        pred = self._pred
        run = make_star_rows_fn(
            self.schema, self._star_resolved,
            predicate=(lambda cols: pred(cols)) if pred else None,
            fact_cols=st["fact_cols"])
        fields = [f"c{c}" for c in st["fact_cols"]]
        dtypes = [self.schema.col_dtype(c) for c in st["fact_cols"]]
        for i, (pc, _k, vals, how) in enumerate(self._star_resolved):
            if vals is not None:
                fields.append(f"pay{i}")
                dtypes.append(vals.dtype)
            fields.append(f"m{i}")
            dtypes.append(np.dtype(bool))
        fields.append("positions")
        dtypes.append(self._pos_dtype())
        arrs = self._collect_rows(plan, run, "hit", fields, dtypes,
                                  device, session, limit=st["limit"],
                                  offset=st["offset"])
        out = dict(zip(fields, arrs))
        out["count"] = np.int64(len(out["positions"]))
        return out

    def _require_no_terminal(self) -> None:
        if self._terminal_set:
            raise StromError(22, "one terminal operator per query "
                                 "(it is one scan node)")

    # -- planning -----------------------------------------------------------
    def _source_facts(self):
        if isinstance(self.source, str):
            path = self.source
            size = os.path.getsize(path)
        elif isinstance(self.source, (list, tuple)):
            path = self.source[0]
            size = sum(os.path.getsize(p) for p in self.source)
        else:  # live Source object
            path = getattr(self.source, "path", None)
            size = self.source.size
        return path, size

    def _open_owned(self):
        """(live Source, owned?) — multi-file sets open as RAID-0 stripes
        with the query's stripe geometry."""
        from ..engine import open_source
        if hasattr(self.source, "size"):
            return self.source, False
        if isinstance(self.source, (list, tuple)):
            return open_source(self.source,
                               stripe_chunk_size=self._stripe_chunk), True
        return open_source(self.source), True

    def _kernel_choice(self, mode: str):
        import jax

        if self._group_exprs is not None:
            return "xla", (f"grouped expression aggregates: group_efn "
                           f"sums {len(self._group_exprs[0])} argument(s) "
                           f"per group under the scan mask (XLA, masked "
                           f"per-group reductions)")
        # operator validity is mode-independent — check BEFORE any mode
        # early-return so mesh plans surface 'invalid' too
        if self._op == "group_by":
            from ..ops.groupby import _check_agg_cols
            try:
                _check_agg_cols(self.schema, self._group[2])
            except ValueError as e:
                # EXPLAIN must show the problem, not raise; run() refuses
                return "invalid", str(e)
        if self._op == "aggregate" and self._agg_cols is not None:
            bad = [c for c in self._agg_cols
                   if not 0 <= c < self.schema.n_cols]
            if bad:   # both access paths must refuse identically
                return "invalid", (f"aggregate column {bad[0]} out of "
                                   f"range (schema has "
                                   f"{self.schema.n_cols})")
        if self._op == "star":
            n = len(self._star["joins"])
            face = "row materialization" if self._star["materialize"] \
                else "additive aggregate"
            return "xla", (f"star join: {n} broadcast dimension"
                           f"{'s' if n != 1 else ''} probed per batch "
                           f"(sorted searchsorted probes fused in one "
                           f"kernel), {face} face")
        if self._op == "aggregate" and self._agg_exprs is not None:
            return "xla", (f"{len(self._agg_exprs)} expression "
                           f"aggregate(s) fuse into the scan kernel "
                           f"(XLA elementwise + masked sum)")
        if self._op == "top_k" \
                and not 0 <= self._topk[0] < self.schema.n_cols:
            return "invalid", (f"top_k column {self._topk[0]} out of "
                               f"range (schema has {self.schema.n_cols})")
        if self._op in ("order_by", "quantiles", "count_distinct"):
            for c in self._order[0]:
                try:
                    self._check_sortable_col(c, self._op)
                except StromError as e:
                    return "invalid", str(e)
        if self._op == "select":
            bad = [c for c in (self._select[0] or [])
                   if not 0 <= c < self.schema.n_cols]
            if bad:   # EXPLAIN must show the problem, not raise
                return "invalid", (f"select column {bad[0]} out of range "
                                   f"(schema has {self.schema.n_cols})")
            return "xla", ("row materialization: decode + mask-compress "
                           "gather, rows return to the host like tuples "
                           "to the executor" +
                           ("; gather runs on a local device (no mesh "
                            "reduction in a materialization)"
                            if mode == "mesh" else ""))
        on_tpu = jax.default_backend() == "tpu"
        if mode == "mesh":
            return "xla", "mesh mode: XLA partitions the reduction and " \
                          "inserts collectives (pallas does not auto-shard)"
        if self.schema.has_wide or any(self.schema.nullable or ()):
            # the Mosaic kernels decode the 4-byte non-null layout;
            # wide (int64/float64) regions and validity bitmaps decode
            # on the XLA path (round 5)
            return "xla", ("wide/nullable page layout decodes on the "
                           "XLA path (the pallas kernels serve the "
                           "4-byte non-null layout)")
        if self._op == "aggregate":
            if on_tpu:
                return "pallas", "single-pass SMEM-accumulator kernel"
            return "xla", "non-TPU backend: interpret-mode pallas would " \
                          "be pure overhead"
        if self._op == "group_by":
            _, g, agg, _hv = self._group
            if self._group_cols is not None:
                # value-keyed GROUP BY: the derived key function closes
                # over the DISCOVERED key table (a device array), and
                # pallas_call rejects captured array constants — found
                # live on TPU driving `--sql ... GROUP BY c0` (round 5)
                return "xla", ("value-keyed GROUP BY: the discovered "
                               "key table is a captured array (Mosaic "
                               "kernels take arrays as inputs only); "
                               "XLA serves the key-table lookup")
            if jax.config.jax_enable_x64:
                # acc_dtypes widens sums/sumsqs to i64/f64 under x64 —
                # dtypes Mosaic cannot hold in SMEM on real hardware
                return "xla", "x64 accumulators (i64/f64) exceed the " \
                              "pallas kernel's SMEM dtype support"
            if g > _PALLAS_MAX_GROUPS:
                return "xla", f"G={g} exceeds the pallas unroll bound"
            if not on_tpu:
                return "xla", "non-TPU backend"
            from ..ops.groupby import _check_agg_cols as _cac
            from ..ops.groupby import groupby_kernel_auto
            # per-device routing decision (device_figures, keyed by
            # device_kind), crossover at speedup 1.0
            gk, gwhy = groupby_kernel_auto(_cac(self.schema, agg)[1].kind)
            if gk == "xla":
                return "xla", gwhy
            return "pallas", f"G={g} within the static-unroll bound " \
                             f"({_PALLAS_MAX_GROUPS}); {gwhy}"
        if self._op in ("order_by", "count_distinct", "quantiles"):
            return "xla", ("distributed sample sort (splitter election + "
                           "all_to_all)" if mode == "mesh"
                           else "single-device lax sort")
        return "xla", f"{self._op} runs on lax.top_k/searchsorted (XLA)"

    def _resolve_join_build(self, session, device) -> None:
        """Load a broadcast-sized on-disk build side (one projection
        scan) into the host-array form the broadcast paths consume;
        idempotent across repeated run() calls."""
        bt, bs, kc, vc = self._join_src
        out = Query(bt, bs).select([kc, vc]).run(session=session,
                                                 device=device)
        pc, _bk, _bv, mat, lim, off = self._join
        self._join = (pc, np.asarray(out[f"col{kc}"], np.int32),
                      np.asarray(out[f"col{vc}"],
                                 bs.col_dtype(vc)), mat, lim, off)
        self._join_src = None

    def _join_strategy(self) -> Optional[tuple]:
        """(strategy, n_parts) for a join terminal: "broadcast" while the
        build side (keys+values bytes) fits ``config join_broadcast_max``
        per device; above it, "partitioned" with the part count that
        bounds resident build memory to the cap — hash-repartition both
        sides, sorted-probe per partition, degrade instead of OOM."""
        if self._join is None:
            return None
        from ..config import config
        if self._join_src is not None:
            # on-disk build: estimate keys+values bytes from the row
            # count (8 bytes/row — two int32 columns)
            bt, bs, _kc, _vc = self._join_src
            rows = (os.path.getsize(bt) // PAGE_SIZE) * bs.tuples_per_page
            nbytes = rows * 8
        else:
            bk, bv = self._join[1], self._join[2]
            nbytes = (np.asarray(bk).nbytes + np.asarray(bv).nbytes)
        cap = int(config.get("join_broadcast_max"))
        if nbytes <= cap:
            return ("broadcast", 1)
        return ("partitioned", max(2, -(-nbytes // cap)))

    def _index_col(self) -> Optional[int]:
        """The column a structured (eq/range/in) filter targets."""
        for f in (self._eq, self._range, self._in):
            if f is not None:
                return f[0]
        return None

    def _eq_order_combo_path(self) -> Optional[str]:
        """Composite sidecar path serving ``WHERE c0 = v ORDER BY c1``
        (single-column structured equality + single-column order_by over
        a DIFFERENT integer column), or None."""
        if (self._op != "order_by" or self._eq is None
                or self._residual is not None
                or isinstance(self._eq[0], (tuple, list))
                or not isinstance(self.source, str)):
            # a residual where() disqualifies the span shortcut: the
            # prefix span is read straight off the sidecar with no row
            # recheck, so it would silently ignore the predicate
            return None
        oc = self._order[0]
        if len(oc) != 1:
            return None
        ce, c1 = int(self._eq[0]), int(oc[0])
        if ce == c1:
            return None
        for c in (ce, c1):
            if not 0 <= c < self.schema.n_cols \
                    or self.schema.col_dtype(c).kind not in "iu":
                return None
        from .index import index_path_for
        return index_path_for(self.source, (ce, c1))

    def _order_key(self):
        """(order columns, sidecar key) for the op's ordered terminal —
        THE single derivation explain() and run() both use, so the
        EXPLAIN promise and run()'s acceptance check cannot drift."""
        ocols = [self._topk[0]] if self._op == "top_k" else self._order[0]
        okey = ocols[0] if len(ocols) == 1 else tuple(ocols[:2])
        return ocols, okey

    def _order_index_path(self) -> Optional[str]:
        """Sidecar path that can serve this ordered terminal directly:
        unfiltered local ``order_by`` (the sorted order IS the index
        order), ``top_k`` (the k best keys are the sidecar's head/tail),
        ``quantiles`` (nearest-rank reads of the sorted keys), or
        ``count_distinct`` (adjacent-diff over the sorted keys) —
        single integer column, or the two integer columns of a composite
        sidecar for order_by.  None when no index could apply."""
        if (self._op not in ("order_by", "quantiles", "count_distinct",
                             "top_k")
                or self._pred is not None
                or not isinstance(self.source, str)):
            return None
        cols, _okey = self._order_key()
        want = (1, 2) if self._op == "order_by" else (1,)
        if len(cols) not in want:
            return None
        for c in cols:
            if not 0 <= c < self.schema.n_cols \
                    or self.schema.col_dtype(c).kind not in "iu":
                # float sidecars strip NaN keys (index.py build), so they
                # cannot reproduce the full row set an ORDER BY owes —
                # index presence must never change query results
                return None
        from .index import index_path_for
        key = cols[0] if len(cols) == 1 else (cols[0], cols[1])
        return index_path_for(self.source, key)

    def _index_path_candidates(self) -> List[str]:
        """Sidecars that could serve the structured filter, preferred
        first: the filter column's own, then — for single-column filters
        — any composite sidecar whose FIRST column is the filter column
        (the SQL leftmost-prefix rule; its packed keys hold the filter
        column's range contiguously).  The directory glob runs once per
        Query (memoized): freshness is re-probed per use anyway, and the
        planner path must stay I/O-cheap."""
        col = self._index_col()
        if col is None or not isinstance(self.source, str):
            return []
        from .index import index_path_for
        out = [index_path_for(self.source, col)]
        if not isinstance(col, (tuple, list)):
            cached = getattr(self, "_prefix_cands", None)
            if cached is None:
                import glob as _glob
                import re as _re
                # escape the table path (metacharacter paths must not
                # become character classes) and accept ONLY the exact
                # .idx<c0>_<c1> shape — never .tmp litter or lookalikes
                pat = _glob.escape(self.source) + f".idx{int(col)}_*"
                rx = _re.compile(
                    _re.escape(self.source) + rf"\.idx{int(col)}_\d+$")
                cached = sorted(p for p in _glob.glob(pat)
                                if rx.fullmatch(p))
                self._prefix_cands = cached
            out += cached
        return out

    def _replan_scan(self, plan: QueryPlan) -> QueryPlan:
        """An index promised at EXPLAIN raced away before run(): choose
        the SCAN access path afresh (falling into vfs unconditionally
        would demote large tables off the direct DMA path)."""
        path, size = self._source_facts()
        return dataclasses.replace(
            plan, access_path="direct"
            if path is not None and should_use_direct_scan(
                path, table_size=size) else "vfs")

    def _index_fresh_for_eq(self) -> bool:
        """Header-only planner probe (no key/position load — EXPLAIN
        stays I/O-cheap); missing/stale/corrupt all mean False.  Any
        candidate (own sidecar or a composite leftmost-prefix match)
        counts — validated against the HEADER's column field, so EXPLAIN
        never claims an index path run() would refuse."""
        from .index import probe_index
        col = self._index_col()
        return any(probe_index(p, self.source, expect_col=col)
                   for p in self._index_path_candidates())

    def _index_for_eq(self):
        """A FRESH sorted-index sidecar serving the structured filter, or
        None (missing/stale/corrupt all mean seqscan fallback, silently —
        the planner never fails a query over an optional accelerator).
        Candidates in preference order: the filter column's own sidecar,
        then composite ones usable via the leftmost-prefix rule."""
        from .index import open_index
        col = self._index_col()
        for ipath in self._index_path_candidates():
            try:
                idx = open_index(ipath, table_path=self.source)
            except Exception:  # corrupt sidecars included, not just Strom/OS
                continue
            # the header is authoritative, not the filename: a sidecar
            # built for other columns (index_path= override) must never
            # serve this filter
            want = tuple(col) if isinstance(col, (tuple, list)) else col
            if idx.col == want or (idx.composite
                                   and not isinstance(want, tuple)
                                   and idx.col[0] == want):
                return idx
        return None

    def explain(self, *, mesh=None) -> QueryPlan:
        plan = self._explain_inner(mesh=mesh)
        if self._workers >= 2 and mesh is None:
            from .planner import _parallel_divisor
            plan = dataclasses.replace(
                plan, workers=self._workers,
                reason=plan.reason +
                f"; parallel: {self._workers} worker processes claim "
                f"chunks from ONE shared cursor (per-worker Sessions, "
                f"partials fold on the leader; cost divisor "
                f"{_parallel_divisor(self._workers):.1f}); the workers "
                f"compute on the host CPU, not the accelerator")
        if self._group_cols is not None:
            side = self._group_key_sidecar()
            if side is not None:
                how = (f"distinct keys discovered first from the fresh "
                       f"sidecar {os.path.basename(side)} at zero table "
                       f"I/O")
            elif self._group_onepass(plan) and not plan.workers:
                how = ("keys grow inside the aggregation scan (the first "
                       "page batch's keys, then each batch's misses, "
                       "aggregated again over only the added keys)")
            else:
                how = ("distinct keys discovered first by a projection "
                       "scan of the table")
            plan = dataclasses.replace(
                plan, reason=plan.reason +
                f"; value-keyed GROUP BY: {how}, empty groups dropped")
        js = self._join_strategy()
        if js is not None:
            strat, n_parts = js
            label = "broadcast" if strat == "broadcast" else \
                f"partitioned({n_parts})"
            how = ("build side replicated per device"
                   if strat == "broadcast" else
                   (f"build side above join_broadcast_max: hash-"
                    f"repartitioned over the mesh dp axis, all_to_all "
                    f"row exchange, local sorted-probe"
                    if mesh is not None else
                    f"build side above join_broadcast_max: {n_parts} "
                    f"hash partitions probed as sequential passes "
                    f"(Grace join), resident build bounded to the cap"))
            if self._join_src is not None and strat == "partitioned":
                how += ("; build side STREAMED from the on-disk table "
                        "in partition passes (host RAM bounded by "
                        "join_build_host_max)")
            plan = dataclasses.replace(
                plan, join_strategy=label,
                reason=plan.reason + f"; join type {self._join_how}"
                       f"; join strategy {label}: {how}")
        return plan

    def _explain_inner(self, *, mesh=None) -> QueryPlan:
        path, size = self._source_facts()
        n_pages = size // PAGE_SIZE
        t = self.schema.tuples_per_page
        direct = path is not None and should_use_direct_scan(
            path, table_size=size)
        mode = "mesh" if mesh is not None else "local"
        kernel, why = self._kernel_choice(mode)
        nw = self._workers if self._workers >= 2 else 0
        cd = cost_direct_scan(n_pages, n_pages * t, workers=nw)
        cv = cost_vfs_scan(n_pages, n_pages * t, workers=nw)
        if mode == "local" and kernel != "invalid":
            comb = self._eq_order_combo_path()
            if comb is not None and self._eq[1] is not None:
                from .index import probe_index
                if probe_index(comb, self.source,
                               expect_col=(int(self._eq[0]),
                                           int(self._order[0][0]))):
                    ce, _v = self._eq
                    oc = self._order[0][0]
                    return QueryPlan(
                        operator=self._op, access_path="index",
                        kernel=kernel, mode=mode, n_pages=n_pages,
                        cost_direct=cd.total, cost_vfs=cv.total,
                        reason=f"fresh composite index on col({ce}, "
                               f"{oc}): WHERE col{ce} = ... ORDER BY "
                               f"col{oc} is ONE pinned-prefix span of "
                               f"the sidecar (keys within the prefix "
                               f"are already in col{oc} order) — no "
                               f"sort, no table I/O; " + why)
            oip = self._order_index_path()
            if oip is not None:
                from .index import probe_index
                ocols, okey = self._order_key()
                # exact header match, no prefix: these terminals read
                # the KEYS as values, so a composite sidecar can only
                # serve the exact pair ordering
                if probe_index(oip, self.source, expect_col=okey,
                               allow_prefix=False):
                    cols_ = ocols
                    what = {
                        "order_by": "the sorted order IS the index "
                                    "order — positions read from the "
                                    "sidecar, no sort, and LIMIT reads "
                                    "only the head",
                        "quantiles": "nearest-rank reads of the sorted "
                                     "sidecar keys — no table I/O at all",
                        "count_distinct": "adjacent-diff over the sorted "
                                          "sidecar keys — no table I/O "
                                          "at all",
                        "top_k": "the k best keys are the sidecar's "
                                 "head/tail — no scan, no table I/O",
                    }[self._op]
                    return QueryPlan(
                        operator=self._op, access_path="index",
                        kernel=kernel, mode=mode, n_pages=n_pages,
                        cost_direct=cd.total, cost_vfs=cv.total,
                        reason=f"fresh index on col{cols_}: {what}; "
                               + why)
        if (self._op in ("select", "aggregate", "top_k", "quantiles",
                         "count_distinct", "group_by", "join")
                and mode == "local"
                and kernel != "invalid" and self._index_fresh_for_eq()):
            if self._eq is not None:
                c, v = self._eq
                cond = f"equality col{c} == {v!r}"
            elif self._in is not None:
                c, members = self._in
                cond = f"membership col{c} IN ({len(members)} values)"
            else:
                c, lo, hi = self._range
                cond = f"range {lo!r} <= col{c} <= {hi!r}"
            recheck = ("" if self._residual is None else
                       " + residual filter RECHECKED on index-resolved "
                       "rows (Index Cond + Filter)")
            return QueryPlan(
                operator=self._op, access_path="index", kernel=kernel,
                mode=mode, n_pages=n_pages, cost_direct=cd.total,
                cost_vfs=cv.total,
                reason=f"fresh index on col{c}: {cond} resolves "
                       f"positions from the sidecar and reads only "
                       f"matching pages{recheck}; " + why)
        if direct:
            reason = ("table above the direct-scan threshold and backing "
                      "eligible; " + why)
        else:
            info = capability_cache.probe(path) if path else None
            if info is not None and not info.supported:
                reason = "source not direct-load capable (CHECK_FILE); " + why
            else:
                reason = "table below the direct-scan threshold " \
                         "(page cache wins for small tables); " + why
        # cache-aware planning (ISSUE 9): report the residency tier's
        # expected hit ratio for this table — at 1.0 the scan is served
        # entirely from pinned slabs and skips engine submission
        from ..tiering import extent_space
        ratio = 0.0
        hbm_ratio = 0.0
        if extent_space.lookup_active and size:
            if isinstance(self.source, (list, tuple)):
                cpaths = list(self.source)
            elif path is not None:
                cpaths = [path]
            else:
                cpaths = []
            # unified residency surface (ISSUE 20): one dict of
            # per-tier expected hit fractions — the engine consults
            # HBM FIRST, so its share surfaces separately; those
            # chunks cost one device->dest memcpy, not even a
            # host-slab touch
            fr = extent_space.resident_fraction(cpaths, size)
            ratio = fr.get("ram", 0.0)
            hbm_ratio = fr.get("hbm", 0.0)
        if hbm_ratio > 0:
            reason += (f"; hbm tier holds ~{hbm_ratio:.0%} of the table "
                       f"(device hits, checked before the host tier)")
        if ratio >= 1.0:
            reason += ("; fully cache-resident: served from the "
                       "residency tier, engine submission skipped")
        elif ratio > 0:
            reason += (f"; residency tier holds ~{ratio:.0%} of the "
                       f"table (memcpy hits, no mincore probe)")
        # compute pushdown (ISSUE 14): a fresh packed sidecar re-plans
        # the scan over compressed extents; the per-column host/chip
        # decision and the wire-byte prediction surface here so EXPLAIN
        # shows exactly what will cross the transport
        pd = ""
        if mode == "local" and kernel != "invalid":
            probe = self._pushdown_probe()
            if probe is not None:
                dec, _meta = probe
                pd = dec.mode
                reason += "; " + dec.explain()
        return QueryPlan(operator=self._op,
                         access_path="direct" if direct else "vfs",
                         kernel=kernel, mode=mode, n_pages=n_pages,
                         cost_direct=cd.total, cost_vfs=cv.total,
                         reason=reason,
                         cache_hit_ratio=round(ratio, 4),
                         hbm_hit_ratio=round(hbm_ratio, 4),
                         pushdown=pd)

    # -- compute builders ---------------------------------------------------
    def _build_fn(self, kernel: str):
        """Returns (fn(pages)->dict, combine or None)."""
        pred = self._pred
        if self._op == "star":
            from ..ops.join import make_star_fn
            fns, zeros, accs = self._star_expr_parts()
            run = make_star_fn(
                self.schema, self._star_resolved,
                predicate=(lambda cols: pred(cols)) if pred else None,
                expr_fns=fns, expr_zeros=zeros, expr_accs=accs)
            return (lambda pages: run(pages)), None
        if self._op == "aggregate" and self._agg_exprs is not None:
            import jax
            import jax.numpy as jnp

            from ..ops.filter_xla import decode_pages
            fns, zeros, accs = self._star_expr_parts()

            @jax.jit
            def efn(pages):
                cols, valid = decode_pages(pages, self.schema)
                sel = valid if pred is None else valid & pred(cols)
                return {"count": jnp.sum(sel.astype(jnp.int32)),
                        "esums": [jnp.sum(jnp.where(sel, f(cols), z),
                                          dtype=a)
                                  for f, z, a in zip(fns, zeros, accs)]}
            return efn, None
        if self._op == "aggregate":
            import jax.numpy as jnp

            # no predicate = every valid row.  NOT cols[0]==cols[0]: that
            # is False for float NaN and would silently drop NaN rows
            all_rows = lambda cols: jnp.ones(cols[0].shape, bool)
            if kernel == "pallas":
                from ..ops.filter_pallas import make_filter_fn_pallas
                p = (lambda cols, th: pred(cols)) if pred is not None \
                    else (lambda cols, th: all_rows(cols))
                run = make_filter_fn_pallas(self.schema, p)
                fn = lambda pages: run(pages, np.int32(0))
            else:
                from ..ops.filter_xla import make_filter_fn
                p = pred if pred is not None else all_rows
                fn = make_filter_fn(self.schema, p)
            if self._agg_cols is not None:
                keep = list(self._agg_cols)
                inner = fn

                def project(o, keep=keep):
                    out = {"count": o["count"],
                           "sums": [o["sums"][c] for c in keep]}
                    if "nncounts" in o:   # NULL-aware denominators
                        out["nncounts"] = [o["nncounts"][c]
                                           for c in keep]
                    return out
                fn = lambda pages: project(inner(pages))
            return fn, None
        if self._op == "group_by":
            key_fn, g, _agg, _having = self._group
            return self._group_kernel(key_fn, g, kernel)
        if self._op == "top_k":
            from ..ops.topk import make_topk_fn
            col, k, largest = self._topk
            run = make_topk_fn(self.schema, col, k, largest=largest,
                               predicate=(lambda cols: pred(cols))
                               if pred else None)
            return (lambda pages: run(pages)), run.combine
        # join
        from ..ops.join import make_join_fn
        probe_col, bk, bv = self._join[:3]
        run = make_join_fn(self.schema, probe_col, bk, bv,
                           predicate=(lambda cols: pred(cols))
                           if pred else None, how=self._join_how)
        return (lambda pages: run(pages)), None

    def _group_kernel(self, key_fn, n_groups: int, kernel: str):
        """``(fn(pages, *params), combine)`` of the GROUP BY kernel over
        *n_groups* groups: the grouped expression kernel when the
        statement sums expressions, else the column kernel.  *params*
        reach ``key_fn(cols, *params)`` (the one-pass key table)."""
        pred = self._pred
        if self._group_exprs is not None:
            from ..ops.groupby import make_group_efn
            fns, zeros, accs = self._star_expr_parts()
            return make_group_efn(self.schema, key_fn, n_groups, fns,
                                  zeros, accs, predicate=pred), None
        from ..ops.groupby import combine_groupby
        agg = self._group[2]
        if kernel == "pallas" and self._group_cols is not None:
            # an explicit kernel="pallas" override must refuse
            # cleanly, not die inside pallas_call tracing
            raise StromError(22, "value-keyed GROUP BY cannot run "
                                 "on the pallas kernel (the "
                                 "discovered key table is a "
                                 "captured array); use kernel="
                                 "'xla' or 'auto'")
        if kernel == "pallas":
            from ..ops.groupby_pallas import make_groupby_fn_pallas
            run = make_groupby_fn_pallas(
                self.schema, lambda cols: key_fn(cols), n_groups,
                agg_cols=agg,
                predicate=(lambda cols: pred(cols)) if pred else None)
            return (lambda pages: run(pages)), combine_groupby
        from ..ops.groupby import make_groupby_fn
        run = make_groupby_fn(
            self.schema, lambda cols, *p: key_fn(cols, *p), n_groups,
            agg_cols=agg,
            predicate=(lambda cols, *_: pred(cols)) if pred else None)
        return run, combine_groupby

    def _group_overflow(self, e: GroupSpill) -> None:
        """Past ``max_groups``: return when the sorted aggregation serves
        the statement (column aggregates over 1-2 key columns), else
        fail with ENOMEM."""
        if self._group_exprs is not None:
            raise StromError(12, f"group_by_cols: {e.seen} distinct keys "
                                 f"exceed max_groups; grouped expression "
                                 f"aggregates have no sorted-aggregation "
                                 f"path") from None
        if len(self._group_cols[0]) > 2:
            raise StromError(12, f"group_by_cols: more than "
                                 f"{self._group_cols[3]} distinct keys "
                                 f"(raise max_groups, or use group_by "
                                 f"with a key function)") from None

    def _run_group_onepass(self, plan: QueryPlan, device, session,
                           kernel: str) -> dict:
        """Value-keyed GROUP BY in one scan of the table: the group
        kernel over a key table that grows inside the scan
        (:class:`.group_keys.OnePassGroups`); the loop dispatches per
        batch (the kernel holds host state, so it cannot coalesce)."""
        import jax

        from ..ops.filter_xla import decode_pages
        from ..ops.groupby import combine_groupby
        from .group_keys import OnePassGroups
        cols_, _agg, _hv, max_groups = self._group_cols
        pred = self._pred

        def key_fn(cols, tabs, ids):
            return lookup_group_ids(cols, cols_, tabs, ids, ids.shape[0])

        @jax.jit
        def gather_misses(pages, tabs, ids):
            dcols, valid = decode_pages(pages, self.schema)
            if pred is not None:
                valid = valid & pred(dcols)
            miss = key_fn(dcols, tabs, ids) == ids.shape[0]
            out = {"mask": (valid & miss).reshape(-1)}
            for j, c in enumerate(cols_):
                out[f"f{j}"] = dcols[c].reshape(-1)
            return out

        keys = GroupKeySet([self.schema.col_dtype(c) for c in cols_],
                           max_groups)
        run = OnePassGroups(
            keys, lambda cap: self._group_kernel(key_fn, cap + 1,
                                                 kernel)[0],
            gather_misses,
            None if self._group_exprs is not None else combine_groupby)
        self._stream_collect(plan, run, device, session)
        out = run.finish()
        self._set_group_keys(keys, None, len(keys))
        return self._finalize(out) if out else {}

    # -- compute pushdown (ISSUE 14) ----------------------------------------
    def _pushdown_need_cols(self):
        """Columns the packed scan must expand: the aggregate projection
        when no predicate can read other columns, else all (an opaque
        ``where()`` lambda may touch any column)."""
        if self._pred is None and self._agg_cols is not None:
            return tuple(self._agg_cols)
        return None

    def _pushdown_probe(self):
        """(PushdownDecision, PackedMeta) when a fresh packed sidecar can
        serve this query, else None.

        Structural eligibility mirrors what the fused decode kernels
        implement: plain aggregate (no expression sums), 4-byte non-null
        layout, serial local scan over one table file.  Freshness is the
        sidecar's size+mtime stamp (the scan/index.py contract), so any
        table write silently retires the packed plan."""
        if self._op != "aggregate" or self._agg_exprs is not None:
            return None
        if not isinstance(self.source, str) or self._workers >= 2:
            return None
        if self.schema.has_wide or any(self.schema.nullable or ()):
            return None
        from .colpack import probe_packed
        meta = probe_packed(self.source)
        if meta is None:
            return None
        from .planner import decide_pushdown
        return decide_pushdown(meta, self._pushdown_need_cols()), meta

    def _run_pushdown(self, dec, meta, device, session,
                      kernel: str = "auto") -> dict:
        """Aggregate over the packed sidecar instead of the heap table.

        ``chip``: the ``.cpk`` pages stream SSD -> landing buffer ->
        device UNEXPANDED and the fused decode->filter->project kernel
        expands them in VMEM — the h2d link (the measured ceiling) only
        ever carries wire bytes.  ``host``: the SSD is the ceiling
        instead, so packed bytes leave the disk, expand to heap pages on
        the host, and the ordinary XLA filter kernel consumes them.
        Integer aggregates are byte-identical to the unpacked scan on
        both legs (same accumulator dtypes, same masked-sum shape)."""
        import time as _time

        import jax

        from ..engine import open_source
        from ..stats import stats
        from ..trace import recorder
        need = self._pushdown_need_cols()
        scale = meta.logical_bytes / max(meta.packed_bytes, 1)
        src = open_source(meta.path)
        # residency-tier identity: packed extents are a DIFFERENT
        # representation of the table, so the cache key carries a repr
        # tag + the encode generation — a re-encoded sidecar can never
        # alias a stale cached extent, and capacity accounting can
        # credit the tier with the LOGICAL bytes each packed slab serves
        src.cache_key_extra = ("#repr=cpk", f"#gen={meta.table_mtime_ns}")
        src.logical_scale = scale
        t0 = _time.monotonic_ns()
        try:
            if dec.mode == "chip":
                use_pallas = kernel == "pallas" or (
                    kernel == "auto" and jax.default_backend() == "tpu")
                if use_pallas:
                    from ..ops.decode_pallas import \
                        make_decode_filter_fn_pallas
                    run = make_decode_filter_fn_pallas(
                        meta, self.schema, self._pred, need_cols=need)
                else:
                    from ..ops.decode_xla import make_decode_filter_fn_xla
                    run = make_decode_filter_fn_xla(
                        meta, self._pred, need_cols=need)

                # counted OUTSIDE the jitted decode (a traced stats.add
                # would fire once at trace time, not per batch) — so no
                # dispatch coalescing on this path
                def fn(pages):
                    stats.add("nr_pushdown_decode_chip")
                    stats.add("bytes_wire_saved",
                              int(pages.shape[0] * PAGE_SIZE
                                  * (scale - 1.0)))
                    return run(pages)

                from .executor import TableScanner
                with TableScanner(src, self.schema, session=session) as sc:
                    out = sc.scan_filter(fn, device=device)
                    self._last_scan_h2d_depth = getattr(
                        sc, "last_h2d_depth", 0)
            else:   # host expansion (SSD-bound)
                from .colpack import decode_pages_numpy
                from .executor import fold_results
                from .heap import build_pages
                fn, _combine = self._build_fn("xla")
                dev = device or jax.local_devices()[0]
                n_pages = src.size // PAGE_SIZE
                acc = None
                for p0 in range(0, n_pages, BATCH_PAGES):
                    n = min(BATCH_PAGES, n_pages - p0)
                    raw = bytearray(n * PAGE_SIZE)
                    src.read_buffered(p0 * PAGE_SIZE, memoryview(raw))
                    packed = np.frombuffer(raw, np.uint8).reshape(
                        n, PAGE_SIZE)
                    cols, nr = decode_pages_numpy(packed, meta)
                    stats.add("nr_pushdown_decode_host")
                    stats.add("bytes_wire_saved",
                              int(n * PAGE_SIZE * (scale - 1.0)))
                    if nr == 0:
                        continue
                    pages = build_pages(cols, self.schema)
                    acc = fold_results(
                        acc, fn(jax.device_put(pages, dev)), None)
                out = jax.tree.map(np.asarray, acc) if acc else {}
        finally:
            src.close()
            recorder.span("pushdown_decode", t0, _time.monotonic_ns(),
                          length=meta.packed_bytes,
                          args={"mode": dec.mode,
                                "wire_bytes": dec.wire_bytes,
                                "logical_bytes": dec.logical_bytes})
        if dec.mode == "chip" and out and self._agg_cols is not None:
            # the fused kernel returns every schema column's sum slot
            # (un-needed ones as zeros); project like _build_fn does
            out = {"count": out["count"],
                   "sums": [out["sums"][c] for c in self._agg_cols]}
        return self._finalize(out)

    # -- execution ----------------------------------------------------------
    def run(self, *, mesh=None, device=None, kernel: str = "auto",
            batch_pages: Optional[int] = None, session=None,
            analyze: bool = False, workers: Optional[int] = None) -> dict:
        """Execute the planned scan and return numpy results.

        ``kernel`` overrides the planner's pallas/XLA choice ("auto" |
        "pallas" | "xla").  With *mesh*, batches stream sharded over the
        mesh's ``dp`` axis and XLA inserts the reduction collectives.
        ``workers=N`` (or ``Query(..., workers=N)``) runs the scan as N
        worker PROCESSES sharing one atomic chunk cursor — the Gather
        analog (`pgsql/nvme_strom.c:582-595,1057-1112`); each worker
        scans with its own Session and the partial results fold on the
        leader.  ``analyze=True`` attaches an ``"_analyze"`` key —
        elapsed wall time plus the engine's stage counters for this run
        (the EXPLAIN ANALYZE face of the STAT_INFO registry,
        kmod/nvme_strom.c:2056-2103)."""
        if analyze:
            import time as _time

            from ..stats import stats as _stats

            def _fold(sess):
                # a caller-supplied session keeps its native-engine
                # counters until stat_info/close; fold them so both
                # snapshots see this run's I/O (not some later window's)
                if sess is not None and getattr(sess, "_native", None) \
                        is not None:
                    sess._fold_native_stats()

            _fold(session)
            before = _stats.snapshot(reset_max=False).counters
            # per-run attribution: an index-served run must report 0, not
            # a previous scan's depth
            self._last_scan_h2d_depth = 0
            t0 = _time.monotonic()
            out = self.run(mesh=mesh, device=device, kernel=kernel,
                           batch_pages=batch_pages, session=session,
                           workers=workers)
            dt = _time.monotonic() - t0
            _fold(session)
            after = _stats.snapshot(reset_max=False).counters
            d = {k: after.get(k, 0) - before.get(k, 0)
                 for k in ("total_dma_length", "nr_submit_dma",
                           "nr_ioctl_memcpy_wait", "nr_wrong_wakeup",
                           "nr_enter_dma", "nr_kernel_dispatch")}
            nsub = max(d["nr_submit_dma"], 1)
            out["_analyze"] = {
                "elapsed_s": round(dt, 6),
                "bytes_direct": int(d["total_dma_length"]),
                "requests": int(d["nr_submit_dma"]),
                "avg_dma_bytes": int(d["total_dma_length"] // nsub),
                "waits": int(d["nr_ioctl_memcpy_wait"]),
                "submit_syscalls": int(d["nr_enter_dma"]),
                # jitted kernel calls this run issued: coalescing makes
                # this ~batches/K on streamed kernel paths
                "kernel_dispatches": int(d["nr_kernel_dispatch"]),
                # per-RUN value from this run's scanner (the registry
                # gauge is process-lifetime and would misattribute a
                # previous scan's pipelining to an index-served query)
                "h2d_depth_reached": int(
                    getattr(self, "_last_scan_h2d_depth", 0)),
                "scan_GBps": round(d["total_dma_length"] / dt / (1 << 30), 3)
                if dt > 0 else None,
            }
            return out
        nw = self._workers if workers is None else int(workers)
        if nw >= 2 and mesh is None:
            return self._run_workers(nw, session=session, device=device)
        if self._group_cols is not None and self._group[0] is None:
            # value-keyed GROUP BY: a local scan grows the key set inside
            # the aggregation pass; otherwise the keys come first (fresh
            # sidecar, or a discovery scan) and the run is a normal
            # group_by.  Past max_groups the sorted-aggregation path takes
            # over (the one-hot kernels' footprint grows with the groups)
            try:
                plan = self._explain_inner(mesh=mesh)
                if self._group_onepass(plan):
                    return self._run_group_onepass(
                        plan, device, session,
                        plan.kernel if kernel == "auto" else kernel)
                self._resolve_group_keys(session, device)
            except GroupSpill as e:
                self._group_overflow(e)
                return self._run_groupby_sorted(device, session)
        plan = self.explain(mesh=mesh)
        if plan.kernel == "invalid":
            raise StromError(22, f"query not executable: {plan.reason}")
        if self._op == "join" and self._join_src is not None \
                and self._join_strategy()[0] == "broadcast":
            # broadcast-sized on-disk build: one projection scan loads
            # it, then every downstream join path (incl. indexed) sees
            # plain host arrays
            self._resolve_join_build(session, device)
        if self._op == "star":
            self._resolve_star_builds(session, device)
            if self._star["materialize"]:
                return self._run_star_rows(plan, device, session)
        if plan.access_path == "index" and self._op == "order_by" \
                and self._eq is not None:
            comb = self._eq_order_combo_path()
            idx = None
            if comb is not None:
                from .index import open_index
                try:
                    cand = open_index(comb, table_path=self.source)
                    ce, oc = int(self._eq[0]), int(self._order[0][0])
                    if cand.composite and cand.col == (ce, oc):
                        idx = cand
                except Exception:   # raced away: fall to the sort path
                    idx = None
            if idx is not None:
                return self._run_order_by_prefix(idx)
            plan = self._replan_scan(plan)
        if plan.access_path == "index" and self._op in (
                "order_by", "quantiles", "count_distinct", "top_k") \
                and self._index_col() is None:
            oip = self._order_index_path()
            idx = None
            if oip is not None:
                from .index import open_index
                try:
                    idx = open_index(oip, table_path=self.source)
                except Exception:   # raced away: fall to the sort path
                    idx = None
            if idx is not None:
                # header authoritative (same contract as the probe):
                # these terminals read keys as VALUES, exact match only
                _ocols, okey = self._order_key()
                if idx.col != okey:
                    idx = None
            if idx is not None:
                if self._op == "order_by":
                    return self._run_order_by_indexed(idx, device, session)
                if self._op == "quantiles":
                    return self._run_quantiles_sidecar(idx)
                if self._op == "top_k":
                    return self._run_topk_sidecar(idx)
                return self._run_count_distinct_sidecar(idx)
            plan = self._replan_scan(plan)
        if plan.access_path == "index":
            idx = self._index_for_eq()
            # explicit per-op dispatch: an op added to the planner's
            # index-capable list but not here must fall to the (always
            # correct) scan path, never to another op's result shape
            runner = {"select": self._run_select_indexed,
                      "top_k": self._run_topk_indexed,
                      "quantiles": self._run_column_indexed,
                      "count_distinct": self._run_column_indexed,
                      "aggregate": self._run_aggregate_indexed,
                      "group_by": self._run_groupby_indexed,
                      "join": self._run_join_indexed,
                      }.get(self._op)
            if self._agg_exprs is not None or \
                    self._group_exprs is not None:
                # expression sums have no host emulation (the fused
                # kernel IS the implementation); scan instead of
                # returning the wrong result shape
                runner = None
            if (self._op == "join" and self._join_src is not None
                    and self._join_strategy()[0] == "partitioned"):
                # index-served joins probe the build host-side; a
                # partitioned-sized ON-DISK build must keep join_table's
                # bounded-RAM contract, so it takes the scan path's
                # streamed Grace passes instead of resolving here
                runner = None
            if idx is not None and runner is not None:
                return runner(idx, device, session)
            plan = self._replan_scan(plan)
        if self._op == "select":
            return self._run_select(plan, device, session)
        if self._op == "join":
            js = self._join_strategy()
            if js is not None and js[0] == "partitioned":
                return self._run_join_partitioned(plan, mesh, device,
                                                  session, js[1],
                                                  batch_pages)
            if self._join[3]:   # materialize=True
                return self._run_join_rows(plan, device, session)
        if self._op == "order_by":
            return self._run_order_by(plan, mesh, device, session)
        if self._op == "count_distinct":
            return self._run_count_distinct(plan, mesh, device, session)
        if self._op == "quantiles":
            return self._run_quantiles(plan, mesh, device, session)
        if plan.pushdown in ("chip", "host") and mesh is None \
                and self._op == "aggregate":
            # packed-sidecar scan: re-probe (the sidecar may have been
            # retired between EXPLAIN and now) and fall through to the
            # heap path when it raced away
            probe = self._pushdown_probe()
            if probe is not None and probe[0].mode in ("chip", "host"):
                return self._run_pushdown(probe[0], probe[1], device,
                                          session, kernel)
        chosen = plan.kernel if kernel == "auto" else kernel
        fn, combine = self._build_fn(chosen)
        if mesh is not None:
            from ..parallel.stream import distributed_scan_filter
            from .executor import fold_results
            src, own = self._open_owned()
            try:
                bp, covered = _mesh_steps(src.size // PAGE_SIZE,
                                          mesh.shape["dp"], batch_pages)
                acc = None
                if covered:
                    acc = distributed_scan_filter(src, mesh, fn,
                                                  batch_pages=bp,
                                                  combine=combine,
                                                  session=session) or None
                # the stream drops any partial final step (it cannot fill
                # every shard evenly); the buffered batch loop scans the
                # tail on one local device, so mesh results cover every
                # page, like the local path does
                tail = self._vfs_scan(fn, combine, device,
                                      first_page=covered, src=src)
                if tail:
                    acc = fold_results(acc, tail, combine)
            finally:
                if own:
                    src.close()
            return self._finalize(acc) if acc is not None else {}
        if plan.access_path == "direct":
            from ..config import config as _cfg
            from .executor import TableScanner
            src, own = self._open_owned()
            try:
                with TableScanner(src, self.schema,
                                  session=session) as sc:
                    # kernel paths are jit-safe end to end (jitted page
                    # kernels, jnp combines) — coalesce their dispatches
                    out = sc.scan_filter(
                        fn, device=device, combine=combine,
                        dispatch_coalesce=int(
                            _cfg.get("scan_dispatch_batch")))
                    self._last_scan_h2d_depth = getattr(
                        sc, "last_h2d_depth", 0)
                    return self._finalize(out)
            finally:
                if own:
                    src.close()
        return self._finalize(self._vfs_scan(fn, combine, device))

    def _finalize(self, out: dict) -> dict:
        """Post-aggregation decoration for group_by: derived ``avgs``
        (sum/count), ``vars``/``stds`` (population variance via
        E[x²]−E[x]², NaN for empty groups) and the HAVING filter — applied
        AFTER the cross-batch/cross-device fold, which is what gives it
        SQL's post-aggregation semantics."""
        if self._op != "group_by" or not out:
            return out
        take = getattr(self, "_group_take", None)
        if take is not None:    # value-keyed: real groups, in key order
            out = {k: ([np.asarray(a)[..., take] for a in v]
                       if isinstance(v, list) else np.asarray(v)[..., take])
                   for k, v in out.items()}
        if self._group_exprs is not None:
            return self._finalize_groups(self._finalize_exprs(out))
        count = np.asarray(out["count"])
        sums = np.asarray(out["sums"])
        # AVG/VAR denominators: per-column non-NULL counts when the
        # kernel emitted them (nullable aggregate columns), else the
        # group row count — an all-NULL group's average is NaN (SQL
        # NULL), exactly like an empty group's
        nn = np.asarray(out["nncounts"]) if "nncounts" in out else None
        base = nn if nn is not None else count
        with np.errstate(divide="ignore", invalid="ignore"):
            denom = np.maximum(base, 1)
            avgs = np.where(base > 0, sums / denom, np.nan)
        res = {"count": count, "sums": sums,
               "mins": np.asarray(out["mins"]),
               "maxs": np.asarray(out["maxs"]), "avgs": avgs}
        if nn is not None:
            res["nncounts"] = nn
            if (nn == 0).any():
                # all-NULL groups: SQL says MIN/MAX/SUM are NULL, not
                # the kernel's ±INT_MAX / 0 accumulator identities —
                # surface NULL as NaN at the result edge (the same face
                # avgs already wears), converting to float only when an
                # all-NULL group actually exists
                void = nn == 0
                for k in ("sums", "mins", "maxs"):
                    res[k] = np.where(void, np.nan,
                                      res[k].astype(np.float64))
        if "sumsqs" in out:
            sumsqs = np.asarray(out["sumsqs"], dtype=np.float64)
            with np.errstate(divide="ignore", invalid="ignore"):
                # clamp: E[x^2]-E[x]^2 can dip epsilon-negative in floats
                vars_ = np.maximum(
                    np.where(base > 0, sumsqs / denom - np.square(avgs),
                             np.nan), 0.0)
            res["sumsqs"] = sumsqs
            res["vars"] = vars_
            res["stds"] = np.sqrt(vars_)
        return self._finalize_groups(res)

    def _finalize_exprs(self, out: dict) -> dict:
        """The grouped expression kernel's per-group sums onto the
        requested aggregates: ``sums``/``avgs`` per column, then
        ``esums``/``eavgs`` per expression (AVG = sum / count in
        float64, NaN for an empty group, as :meth:`_finalize` does)."""
        count = np.asarray(out["count"])
        sums = [np.asarray(s) for s in out["esums"]]
        with np.errstate(divide="ignore", invalid="ignore"):
            denom = np.maximum(count, 1)
            avgs = [np.where(count > 0, s / denom, np.nan) for s in sums]
        idx = self._group_exprs[1]
        nv = len(self._group[2] or ())        # the columns come first
        return {"count": count,
                "sums": [sums[i] for i in idx[:nv]],
                "avgs": [avgs[i] for i in idx[:nv]],
                "esums": [sums[i] for i in idx[nv:]],
                "eavgs": [avgs[i] for i in idx[nv:]]}

    def _finalize_groups(self, res: dict) -> dict:
        """HAVING (empty groups dropped first, for value-keyed GROUP BY)
        over the finished per-group arrays (an entry may be a list of
        them), then each surviving group's id and key values."""
        having = self._group[3]
        if having is None:
            return res
        count = res["count"]
        mask = np.asarray(having(res)).astype(bool)
        if mask.shape != count.shape:
            raise StromError(22, f"having must return a ({len(count)},) "
                                 f"bool mask, got shape {mask.shape}")
        res = {k: ([a[mask] for a in v] if isinstance(v, list) else
                   v[mask] if v.ndim == 1 else v[..., mask])
               for k, v in res.items()}
        res["groups"] = np.flatnonzero(mask).astype(np.int32)
        if self._group_cols is not None and \
                getattr(self, "_gk_decode", None) is not None:
            # the SELECT-list face of GROUP BY: actual key values per
            # surviving group (group_by_cols contract)
            res["key_cols"] = self._gk_decode(res["groups"])
        return res

    # -- sorted (spill) GROUP BY -------------------------------------------
    _SPILL_HARD_MAX = 1 << 24   # truly-unbounded guard (ENOMEM past this)

    def _sorted_group_ctx(self):
        """Shared setup for the sorted-aggregation GROUP BY (serial and
        worker halves): ``(key_cols, agg_idx, packer, accumulator)``."""
        from ..ops.groupby import _check_agg_cols, acc_dtypes
        from .index import pack_pair
        cols_, agg, _user_having, _mg = self._group_cols
        agg_idx, agg_dt = _check_agg_cols(self.schema, agg)
        for c in agg_idx:
            if self.schema.col_nullable(c):
                raise StromError(22, f"group_by_cols: c{c} is nullable "
                                     f"and the key set exceeded "
                                     f"max_groups — high-cardinality "
                                     f"GROUP BY over nullable "
                                     f"aggregates is outside this "
                                     f"subset")
        acc_np, sq_np, lo, hi = acc_dtypes(agg_dt)
        dts = [self.schema.col_dtype(c) for c in cols_]
        if len(cols_) == 1:
            packer = lambda ks: ks[0]
        else:
            packer = lambda ks: pack_pair(ks[0], ks[1], dts[0], dts[1])
        acc = _SortedGroupAcc(len(agg_idx), acc_np, sq_np, lo, hi,
                              self._SPILL_HARD_MAX)
        return cols_, agg_idx, packer, acc

    def _sorted_group_scan(self, acc, cols_, agg_idx, packer, device,
                           session, *, scanner=None) -> None:
        """Stream the scan through the sorted accumulator: gather key +
        aggregate columns, pack keys, sort-reduce per batch, merge."""
        gather, _f, _d = self._make_gather_fn(list(cols_) + list(agg_idx),
                                              want_positions=False)
        nk = len(cols_)

        def collect(pages_dev):
            out = gather(pages_dev)
            m = np.asarray(out["mask"]).astype(bool)
            ks = [np.asarray(out[f"f{i}"])[m] for i in range(nk)]
            vals = np.stack([np.asarray(out[f"f{nk + j}"])[m]
                             for j in range(len(agg_idx))])
            acc.add_batch(packer(ks), vals)
            return {}

        if scanner is not None:
            scanner.scan_filter(collect, device=device)
        else:
            self._stream_collect(self._explain_inner(), collect, device,
                                 session)

    def _sorted_group_result(self, acc) -> dict:
        """Fold the accumulator state into the group_by result contract
        (same faces as the one-hot kernels + ``key_cols``), via
        :meth:`_finalize` so HAVING/avgs/vars compose identically."""
        from .index import unpack_second
        cols_, agg, _user_having, _mg = self._group_cols
        dts = [self.schema.col_dtype(c) for c in cols_]
        st = acc.state()
        keys = st.pop("keys")
        self._group = (None, len(keys), agg, self._group_having())
        self._group_take = None
        if len(cols_) == 1:
            self._gk_decode = lambda gids, keys=keys: [
                keys.astype(dts[0])[gids]]
        else:
            hi_w = (keys >> np.uint64(32))
            if dts[0] == np.dtype(np.int32):
                k0 = (hi_w.astype(np.int64) - (1 << 31)).astype(np.int32)
            else:
                k0 = hi_w.astype(np.uint32)
            k1 = unpack_second(keys, dts[1])
            self._gk_decode = lambda gids, k0=k0, k1=k1: [k0[gids],
                                                          k1[gids]]
        return self._finalize(st)

    def _run_groupby_sorted(self, device, session) -> dict:
        """GROUP BY past the one-hot budget (``max_groups``): sort-then-
        segment-reduce — each batch's selected rows sort by packed key
        and ``reduceat`` into per-key partials, merged into a running
        sorted state whose footprint is O(distinct keys), not
        O(rows x groups) like the one-hot contraction.  The SQL executor
        the reference sits under switches to sort-aggregation for
        high-cardinality keys the same way.  Local host path (the mesh
        one-hot path keeps its own budget); result contract identical to
        the kernel path."""
        cols_, agg_idx, packer, acc = self._sorted_group_ctx()
        self._sorted_group_scan(acc, cols_, agg_idx, packer, device,
                                session)
        return self._sorted_group_result(acc)

    # -- parallel worker processes (the Gather analog) ----------------------
    _WORKER_OPS = ("aggregate", "group_by", "top_k", "select", "star")

    def _worker_spec(self, discovered=None) -> dict:
        """Picklable reconstruction recipe for worker processes: the
        structured filter, SQL predicate trees, terminal, and (for
        value-keyed GROUP BY) the leader-discovered key set."""
        import jax

        from ..config import config as _cfg
        spec = {
            "source": self.source,
            "schema": (self.schema.n_cols, self.schema.visibility,
                       self.schema.dtypes, self.schema.nullable),
            "chunk_size": int(_cfg.get("chunk_size")),
            # leader-side runtime state workers must mirror: the config
            # snapshot (join_broadcast_max, scan knobs, ...) and the
            # x64 flag (acc_dtypes widens int sums under x64 — a worker
            # accumulating at a different width would fold silently
            # different partials)
            "config": _cfg.snapshot(),
            "x64": bool(jax.config.jax_enable_x64),
            "eq": self._eq, "rng": self._range, "in": self._in,
            "trees": list(self._pred_trees),
            "op": self._op,
            "agg_cols": (None if self._agg_cols is None
                         else list(self._agg_cols)),
            "agg_exprs": self._agg_exprs,
            "select": self._select,
            "topk": self._topk,
        }
        if self._op == "star":
            spec["star"] = self._star
        if self._op == "group_by":
            cols_, agg, _hv, max_groups = self._group_cols
            spec["group"] = (list(cols_), None if agg is None
                             else list(agg), int(max_groups))
            spec["discovered"] = discovered
        return spec

    @classmethod
    def _from_worker_spec(cls, spec: dict) -> "Query":
        """Rebuild the leader's query inside a worker process from the
        picklable spec (inverse of :meth:`_worker_spec`)."""
        n_cols, vis, dts, nullable = spec["schema"]
        schema = HeapSchema(n_cols=n_cols, visibility=vis, dtypes=dts,
                            nullable=nullable)
        q = cls(spec["source"], schema)
        if spec["eq"] is not None:
            col, v = spec["eq"]
            if v is None:    # no representable literal: matches nothing
                c0 = int(col[0]) if isinstance(col, (tuple, list)) \
                    else int(col)
                q._pred = lambda cols: cols[c0] != cols[c0]
                q._set_structured(eq=(col, None))
            elif isinstance(col, (tuple, list)):
                q.where_eq(tuple(col), tuple(v))
            else:
                q.where_eq(col, v)
        elif spec["rng"] is not None:
            c, lo, hi = spec["rng"]
            q.where_range(c, lo, hi)
        elif spec["in"] is not None:
            c, members = spec["in"]
            q.where_in(c, members)
        from .sql import _tree_mask
        for t in spec["trees"]:
            q.where(lambda cols, t=t: _tree_mask(t, cols), _tree=t)
        op = spec["op"]
        if op == "aggregate":
            if spec.get("agg_exprs"):
                q.aggregate_exprs(spec["agg_exprs"])
            else:
                q.aggregate(spec["agg_cols"])
        elif op == "star":
            st = spec["star"]
            q.star_join(st["joins"], exprs=st["exprs"])
        elif op == "top_k":
            tc, tk, tl = spec["topk"]
            q.top_k(tc, tk, largest=tl)
        elif op == "select":
            cols, limit, offset = spec["select"]
            # offset applies on the LEADER (rows split across workers);
            # each worker gathers up to offset+limit and the leader
            # slices the concatenation
            stop = None if limit is None else limit + offset
            q.select(cols, limit=stop, offset=0)
        elif op in ("group_by", "group_sorted"):
            cols_, agg, max_groups = spec["group"]
            q.group_by_cols(cols_, agg_cols=agg, max_groups=max_groups)
            if op == "group_by":
                q._install_group_keys(spec["discovered"])
            else:    # spill: workers sort-aggregate, no key table
                q._op = "group_sorted"
        else:
            raise StromError(22, f"worker spec op {op!r}")
        return q

    def _run_worker_partial(self, spec: dict, cursor) -> dict:
        """Worker-side execution: scan chunks claimed from the SHARED
        cursor with this process's own Session and return the picklable
        partial (raw accumulator — the leader folds and finalizes).
        ``scan_s`` rides along: the worker's own scan wall time, net of
        process spawn/jit, so the leader can report how the scan work
        actually divided."""
        import time as _time

        from .executor import TableScanner
        t0 = _time.monotonic()
        out = self._worker_partial_inner(spec, cursor, TableScanner)
        out["scan_s"] = _time.monotonic() - t0
        return out

    def _worker_partial_inner(self, spec: dict, cursor,
                              TableScanner) -> dict:
        with TableScanner(self.source, self.schema, cursor=cursor,
                          chunk_size=spec["chunk_size"],
                          numa_bind=False) as sc:
            if self._op == "group_sorted":
                cols_, agg_idx, packer, acc = self._sorted_group_ctx()
                self._sorted_group_scan(acc, cols_, agg_idx, packer,
                                        None, None, scanner=sc)
                return {"sorted": acc.state()}
            if self._op in ("aggregate", "group_by", "top_k", "star"):
                if self._op == "star":
                    # each worker loads the (broadcast-sized) dims once
                    self._resolve_star_builds(None, None)
                fn, combine = self._build_fn("xla")
                return {"acc": sc.scan_filter(fn, combine=combine)}
            # select: the shared row-collection machinery, fed from
            # THIS scanner (the spec already folded offset into stop)
            cols, stop, _off = self._select
            if cols is None:
                cols = list(range(self.schema.n_cols))
            gather, fields, dtypes = self._make_gather_fn(cols)
            arrs = self._collect_rows(None, gather, "mask", fields,
                                      dtypes, None, None, limit=stop,
                                      offset=0, scanner=sc)
            return {"rows": arrs}

    def _run_workers(self, n_workers: int, *, session=None,
                     device=None) -> dict:
        """Leader side of the parallel scan: validate the query is
        worker-shippable, resolve GROUP BY keys once (workers must share
        one key space), fan out via :func:`.parallel.run_query_workers`,
        and fold the partials exactly like the batch fold."""
        from .executor import fold_results
        from .parallel import run_query_workers
        if not isinstance(self.source, str):
            raise StromError(22, "workers: parallel scan takes a single "
                                 "on-disk table path (striped sets scan "
                                 "serially or via a mesh)")
        # plan validation BEFORE spawning: a query the serial path
        # refuses with a clean StromError must refuse identically here,
        # not crash inside N worker processes
        plan = self.explain()
        if plan.kernel == "invalid":
            raise StromError(22, f"query not executable: {plan.reason}")
        if self._join is not None or self._join_src is not None:
            raise StromError(22, "workers: JOIN is not worker-servable "
                                 "yet (use the mesh partitioned join)")
        if self._group_exprs is not None:
            raise StromError(22, "workers: grouped expression aggregates "
                                 "are not worker-servable (run "
                                 "serially)")
        if self._opaque_pred:
            raise StromError(22, "workers: an opaque where() lambda "
                                 "cannot ship to worker processes — use "
                                 "where_eq/where_range/where_in or the "
                                 "SQL facade (predicate trees travel)")
        spill = False
        discovered = None
        if self._op == "group_by":
            if self._group_cols is None:
                raise StromError(22, "workers: group_by needs "
                                     "group_by_cols (key-function "
                                     "closures cannot ship)")
            if self._group[0] is None:
                try:
                    discovered = self._discover_group_keys(session,
                                                           device)
                    self._install_group_keys(discovered)
                except GroupSpill as e:
                    self._group_overflow(e)
                    spill = True
            else:
                discovered = getattr(self, "_group_discovered", None)
                if discovered is None:
                    raise StromError(22, "workers: group keys resolved "
                                         "without a shippable key set")
        elif self._op == "star" and self._star["materialize"]:
            raise StromError(22, "workers: the star row face is not "
                                 "worker-servable (aggregate face "
                                 "only)")
        elif self._op not in self._WORKER_OPS:
            raise StromError(22, f"workers: terminal {self._op!r} is "
                                 f"not worker-servable "
                                 f"({'/'.join(self._WORKER_OPS)})")
        spec = self._worker_spec(discovered)
        if spill:
            spec["op"] = "group_sorted"
        partials = run_query_workers(spec, n_workers)
        winfo = {"n": n_workers,
                 "scan_s": [round(p.pop("scan_s", 0.0), 6)
                            for p in partials]}

        def _tag(out: dict) -> dict:
            # per-worker scan seconds (net of spawn/jit) — the Gather
            # observability face; assemblers drop it like "_analyze"
            if isinstance(out, dict) and out:
                out["_workers"] = winfo
            return out
        if spill:
            _c, _a, _p, acc = self._sorted_group_ctx()
            for p in partials:
                acc.merge_state(p["sorted"])
            return _tag(self._sorted_group_result(acc))
        if self._op == "select":
            cols, limit, offset = self._select
            if cols is None:
                cols = list(range(self.schema.n_cols))
            _g, fields, dtypes = self._make_gather_fn(cols)
            rows = [p["rows"] for p in partials]
            arrs = [np.concatenate([r[i] for r in rows])
                    if rows else np.zeros(0, dtypes[i])
                    for i in range(len(fields))]
            stop = None if limit is None else offset + limit
            arrs = [a[offset:stop] for a in arrs]
            named = dict(zip(fields, arrs))
            out = {f"col{c}": named[f"f{i}"]
                   for i, c in enumerate(cols)}
            for i, c in enumerate(cols):
                if f"n{i}" in named:
                    out[f"null{c}"] = named[f"n{i}"]
            out["positions"] = named["pos"]
            out["count"] = np.int64(len(out["positions"]))
            return _tag(out)
        accs = [p["acc"] for p in partials if p["acc"]]
        if not accs:
            # empty table: no worker claimed a chunk, so no partial
            # accumulator exists.  Synthesize the terminal's normal
            # zero-row result (count=0, zero sums/nncounts, empty
            # groups) by running its kernel over one all-zero page —
            # n_tuples=0 decodes to zero valid rows, so the shapes,
            # dtypes and keys match a real scan exactly; a bare {}
            # crashed every consumer that indexed the result
            import jax
            from .heap import PAGE_SIZE
            if self._op == "star":
                self._resolve_star_builds(None, None)
            fn0, _combine0 = self._build_fn("xla")
            acc0 = fn0(np.zeros((1, PAGE_SIZE), np.uint8))
            return _tag(self._finalize(jax.tree.map(np.asarray, acc0)))
        if self._op == "group_by":
            from ..ops.groupby import combine_groupby
            combine = combine_groupby
        elif self._op == "top_k":
            _fn, combine = self._build_fn("xla")
        else:
            combine = None
        folded = None
        for a in accs:
            folded = fold_results(folded, a, combine)
        import jax
        return _tag(self._finalize(jax.tree.map(np.asarray, folded)))

    def _check_sortable_col(self, col: int, opname: str) -> np.dtype:
        if not 0 <= col < self.schema.n_cols:
            raise StromError(22, f"{opname} column {col} out of range")
        dt = self.schema.col_dtype(col)
        if dt not in (np.dtype(np.int32), np.dtype(np.uint32),
                      np.dtype(np.float32)):
            raise StromError(22, f"{opname} supports int32/uint32/"
                                 f"float32 columns (got {dt})")
        if self.schema.col_nullable(col):
            raise StromError(22, f"{opname} over the nullable c{col} is "
                                 f"outside this subset (no NULL "
                                 f"ordering)")
        return dt

    @staticmethod
    def _pos_dtype():
        import jax
        return np.int64 if jax.config.jax_enable_x64 else np.int32

    def _make_gather_fn(self, cols: Sequence[int],
                        want_positions: bool = True):
        """Jitted per-batch gather of projected columns (+ global
        positions) with the query predicate folded in.  Returns
        ``(batch_fn, field_names, empty_dtypes)`` for
        :meth:`_collect_rows`; field ``f<i>`` is ``cols[i]``, positions
        (if requested) are last."""
        import jax

        from ..ops.filter_xla import decode_pages, global_row_positions
        pred = self._pred
        cols = list(cols)

        @jax.jit
        def gather(pages):
            dcols, valid = decode_pages(pages, self.schema)
            if pred is not None:
                valid = valid & pred(dcols)
            out = {"mask": valid.reshape(-1)}
            for i, c in enumerate(cols):
                out[f"f{i}"] = dcols[c].reshape(-1)
                if c in dcols.nulls:   # NULL masks ride along (round 5)
                    out[f"n{i}"] = dcols.nulls[c].reshape(-1)
            if want_positions:   # distinct never reads them — skip the
                out["pos"] = global_row_positions(   # decode + D2H
                    pages, self.schema).reshape(-1)
            return out

        fields = [f"f{i}" for i in range(len(cols))]
        dtypes = [self.schema.col_dtype(c) for c in cols]
        for i, c in enumerate(cols):
            if self.schema.col_nullable(c):
                fields.append(f"n{i}")
                dtypes.append(np.dtype(bool))
        if want_positions:
            fields.append("pos")
            dtypes.append(self._pos_dtype())
        return gather, fields, dtypes

    def _collect_rows(self, plan: Optional[QueryPlan], batch_fn,
                      mask_key: str,
                      fields: Sequence[str], empty_dtypes, device,
                      session, *, limit: Optional[int] = None,
                      offset: int = 0, scanner=None) -> List[np.ndarray]:
        """Shared row-materialization engine (SELECT and the join's row
        face): stream batches, compress rows by ``batch_fn``'s *mask_key*
        output host-side (one concat at the end — a fold-style growing
        device concat would copy the accumulator once per batch), stop
        issuing I/O once ``offset+limit`` rows are gathered, and slice.
        Returns one array per field."""
        stop = None if limit is None else offset + limit
        chunks = []
        gathered = 0

        def collect(pages_dev):
            nonlocal gathered
            out = batch_fn(pages_dev)
            mask = np.asarray(out[mask_key]).astype(bool)
            chunks.append([np.asarray(out[f])[mask] for f in fields])
            gathered += int(mask.sum())
            if stop is not None and gathered >= stop:
                raise _ScanLimitReached
            return {}   # nothing to fold

        self._stream_collect(plan, collect, device, session,
                             scanner=scanner)
        if chunks:
            arrs = [np.concatenate([c[i] for c in chunks])
                    for i in range(len(fields))]
        else:
            arrs = [np.zeros(0, dt) for dt in empty_dtypes]
        return [a[offset:stop] for a in arrs]

    def _stream_collect(self, plan: Optional[QueryPlan], collect, device,
                        session, *, scanner=None) -> None:
        """Stream the planned access path through a host-side collector
        (shared by the SELECT gather and the materializing join); a
        :class:`_ScanLimitReached` from *collect* stops the scan.  A
        caller-supplied *scanner* (the worker path's shared-cursor
        TableScanner) replaces plan-driven source opening."""
        try:
            if scanner is not None:
                scanner.scan_filter(collect, device=device)
            elif plan.access_path == "direct":
                from .executor import TableScanner
                src, own = self._open_owned()
                try:
                    with TableScanner(src, self.schema,
                                      session=session) as sc:
                        sc.scan_filter(collect, device=device)
                        self._last_scan_h2d_depth = getattr(
                            sc, "last_h2d_depth", 0)
                finally:
                    if own:
                        src.close()
            else:
                self._vfs_scan(collect, None, device)
        except _ScanLimitReached:
            pass

    def fetch(self, positions, cols: Optional[Sequence[int]] = None, *,
              session=None, device=None,
              max_batch_pages: int = 4096) -> dict:
        """Point lookup by global row position — the index-access face
        the seqscan-only reference lacks: ONLY the pages containing
        *positions* are read (8KB page grid; the engine's merge planner
        consolidates contiguous pages into ``dma_max`` requests,
        `kmod/nvme_strom.c:1473-1505`), decoded on device, and the
        requested rows gathered in caller order.

        Returns ``{"col<i>": values, "valid": mask}`` — ``valid`` is
        False for rows whose slot is past the page's tuple count or
        marked invisible.  Duplicate and unsorted positions are fine.
        Not a terminal: usable on any Query (e.g. feed ``top_k``
        positions back to fetch the full rows)."""
        import jax

        from ..engine import read_chunk_ids
        if cols is None:
            cols = list(range(self.schema.n_cols))
        for c in cols:
            if not 0 <= c < self.schema.n_cols:
                raise StromError(22, f"fetch column {c} out of range")
        pos = np.asarray(positions, np.int64).reshape(-1)
        t = self.schema.tuples_per_page
        src, own = self._open_owned()
        try:
            n_pages = src.size // PAGE_SIZE
            if len(pos) and (pos.min() < 0 or pos.max() >= n_pages * t):
                raise StromError(34, f"position outside the table "
                                     f"({n_pages * t} rows)")
            if not len(pos):
                out = {f"col{c}": np.zeros(0, self.schema.col_dtype(c))
                       for c in cols}
                out["valid"] = np.zeros(0, bool)
                return out
            uniq = np.unique(pos // t)          # pages to touch, sorted
            dev = device or jax.local_devices()[0]
            gather = _fetch_gather_fn(self.schema, tuple(cols))

            from ..engine import Session as _S
            own_sess = session is None
            sess = session or _S()
            parts = []
            try:
                for b0 in range(0, len(uniq), max_batch_pages):
                    batch_pages = uniq[b0:b0 + max_batch_pages]
                    handle, buf = sess.alloc_dma_buffer(
                        len(batch_pages) * PAGE_SIZE)
                    try:
                        raw = read_chunk_ids(sess, src, batch_pages,
                                             PAGE_SIZE, handle, buf.view())
                        parts.append(np.array(raw).reshape(-1, PAGE_SIZE))
                    finally:
                        sess.unmap_buffer(handle)
                        buf.close()
            finally:
                if own_sess:
                    sess.close()
            pages = np.concatenate(parts) if len(parts) > 1 else parts[0]
            page_idx = np.searchsorted(uniq, pos // t).astype(np.int32)
            slot = (pos % t).astype(np.int32)
            out = gather(jax.device_put(pages, dev),
                         jax.device_put(page_idx, dev),
                         jax.device_put(slot, dev))
            return {k: np.asarray(v) for k, v in out.items()}
        finally:
            if own:
                src.close()

    def _run_select_indexed(self, idx, device, session) -> dict:
        """INDEX SCAN select: positions from the sidecar, then only the
        matching pages are read (``fetch``'s merge-planned lookups).
        Same result contract as :meth:`_run_select`; row order is index
        order (ascending key, build order within duplicates)."""
        cols, limit, offset = self._select
        if cols is None:
            cols = list(range(self.schema.n_cols))
        pos = self._index_positions(idx, session, device)
        # index rows were valid at build time and the table is stamped
        # unchanged; keep the defensive mask anyway — applied BEFORE the
        # offset/limit window, matching the seqscan's filter-then-slice
        # ordering (_collect_rows), so a hypothetical invalid row can only
        # shrink the candidate set, never shift the window.  The early
        # cut-off limit promises is preserved by fetching in batches and
        # stopping once offset+limit VALID rows are in hand (the batched
        # fetch-with-early-stop discipline, not fetch-everything).
        need = None if limit is None else offset + limit
        got_cols: dict = {f"col{c}": [] for c in cols}
        got_pos: list = []
        n_valid = 0
        step = max(1, len(pos)) if need is None else max(need, 1024)
        for b0 in range(0, len(pos), step):
            batch = pos[b0:b0 + step]
            out = self.fetch(batch, cols=cols, session=session,
                             device=device)
            keep = out.pop("valid")
            for c in cols:
                got_cols[f"col{c}"].append(out[f"col{c}"][keep])
            got_pos.append(batch[keep])
            n_valid += int(keep.sum())
            if need is not None and n_valid >= need:
                break
        end = None if limit is None else offset + limit
        res = {k: np.concatenate(v)[offset:end] if v else
               np.zeros(0, self.schema.col_dtype(int(k[3:])))
               for k, v in got_cols.items()}
        res["positions"] = (np.concatenate(got_pos)[offset:end]
                            if got_pos else np.zeros(0, np.int64))
        res["count"] = np.int64(len(res["positions"]))
        return res

    def _index_positions(self, idx, session=None,
                         device=None) -> np.ndarray:
        """Positions matching the structured filter via the sidecar —
        then RECHECKED against any residual :meth:`where` predicate
        (the PG Index Cond + Filter shape): the candidate rows' columns
        are fetched once (on the caller's session/device) and the
        residual mask applied, so every index runner downstream sees
        only fully-qualified rows."""
        pos = self._index_positions_cond(idx)
        if self._residual is None or len(pos) == 0:
            return pos
        pos = np.asarray(pos, np.int64)
        cols_all = list(range(self.schema.n_cols))
        # batched recheck: host memory stays bounded to one batch of
        # candidate rows however large the index cond's result is
        keep_parts = []
        batch = 1 << 16
        for b0 in range(0, len(pos), batch):
            pb = pos[b0:b0 + batch]
            out = self.fetch(pb, cols=cols_all, session=session,
                             device=device)
            colsd = _HostCols(
                {c: np.asarray(out[f"col{c}"]) for c in cols_all},
                nulls={c: np.asarray(out[f"null{c}"]).astype(bool)
                       for c in cols_all if f"null{c}" in out})
            mask = np.asarray(self._residual(colsd)) \
                .astype(bool).reshape(-1)
            # an invisible row's decoded values are garbage: never let
            # the residual resurrect one (downstream keeps would drop it
            # anyway; COUNT-style runners trust the position list)
            keep_parts.append(
                pb[mask & np.asarray(out["valid"]).astype(bool)])
        return np.concatenate(keep_parts)

    def _index_positions_cond(self, idx) -> np.ndarray:
        """The structured (index-cond) half of :meth:`_index_positions`."""
        prefix = idx.composite and not isinstance(self._index_col(),
                                                  (tuple, list))
        if self._eq is not None:
            # value None = the normalized literal can match no row (e.g.
            # 7.5 against an int column) — the seqscan's empty answer
            if self._eq[1] is None:
                return np.zeros(0, np.int64)
            if prefix:   # c0-only equality over a (c0, c1) sidecar
                v = self._eq[1]
                return idx.prefix_range(v, v)
            # composite pair and single value both arrive as ONE probe;
            # SortedIndex.lookup handles the packing when composite
            return idx.lookup([self._eq[1]])
        if self._in is not None:
            if prefix:
                parts = [idx.prefix_range(m, m) for m in self._in[1]]
                return np.concatenate(parts) if parts \
                    else np.zeros(0, np.int64)
            return idx.lookup(self._in[1])
        _c, lo, hi = self._range
        if prefix:
            return idx.prefix_range(lo, hi)
        return idx.range(lo, hi)

    @staticmethod
    def _nearest_ranks(qs, n: int):
        """Nearest-rank indices into a sorted order of *n* elements."""
        return [min(n - 1, max(0, int(np.ceil(q * n)) - 1)) for q in qs]

    def _run_column_indexed(self, idx, device, session) -> dict:
        """quantiles / count_distinct over index-resolved rows (p99
        WHERE key = X): only matching pages are read; the math is the
        local path's exactly."""
        col = self._order[0][0]
        self._check_sortable_col(col, self._op)
        pos = self._index_positions(idx, session, device)
        out = self.fetch(pos, cols=[col], session=session, device=device)
        vals = out[f"col{col}"][np.asarray(out["valid"]).astype(bool)]
        if self._op == "count_distinct":
            return {"distinct": np.int32(len(
                np.unique(vals, equal_nan=False)))}
        qs = self._quantiles
        n = len(vals)
        if n == 0:
            return {"quantiles": np.full(len(qs), np.nan, np.float64),
                    "n": np.int64(0)}
        svals = np.sort(vals)
        return {"quantiles": svals[self._nearest_ranks(qs, n)],
                "n": np.int64(n)}

    def _run_groupby_indexed(self, idx, device, session) -> dict:
        """GROUP BY over index-resolved rows (GROUP BY x WHERE key = v):
        only matching pages are read; per-group accumulation follows the
        kernel contract — count int32, integer sums EXACT in the shared
        accumulator dtype (ufunc.at, never float bincount), float sums/
        sumsqs equal up to summation order (sequential here, tree-reduced
        on device), min/max sentinels for empty groups — and the shared
        :meth:`_finalize` adds avgs/vars/HAVING on top."""
        from ..ops.groupby import _check_agg_cols, acc_dtypes
        key_fn, g, agg, _having = self._group
        cols_idx, agg_dt = _check_agg_cols(self.schema, agg)
        pos = self._index_positions(idx, session, device)
        # key_fn is an opaque lambda over ALL columns: fetch every column
        out = self.fetch(pos, session=session, device=device)
        keep = np.asarray(out["valid"]).astype(bool)
        cols = [np.asarray(out[f"col{c}"])[keep].reshape(1, -1)
                for c in range(self.schema.n_cols)]
        keys = np.asarray(key_fn(cols)).reshape(-1).astype(np.int64)
        sel = (keys >= 0) & (keys < g)
        keys = keys[sel]
        acc_t, sq_t, lo, hi = acc_dtypes(agg_dt)
        count = np.bincount(keys, minlength=g).astype(np.int32)
        V = len(cols_idx)
        sums = np.zeros((V, g), acc_t)
        sumsqs = np.zeros((V, g), sq_t)
        mins = np.full((V, g), hi, agg_dt)
        maxs = np.full((V, g), lo, agg_dt)
        any_null = any(self.schema.col_nullable(c)
                       for c in range(self.schema.n_cols))
        nncounts = np.zeros((V, g), np.int32)
        for vi, ci in enumerate(cols_idx):
            v = cols[ci].reshape(-1)[sel]
            # NULL exclusion mirrors the kernel: NULL rows add nothing
            # to sums and never touch min/max/sumsq (review finding:
            # the host emulation absorbed the stored zeros)
            if f"null{ci}" in out:
                nv = ~np.asarray(out[f"null{ci}"])[keep] \
                    .reshape(-1)[sel]
            else:
                nv = np.ones(len(v), bool)
            vv, kk = v[nv], keys[nv]
            np.add.at(sums[vi], kk, vv.astype(acc_t))
            np.add.at(sumsqs[vi], kk, vv.astype(sq_t) * vv.astype(sq_t))
            np.minimum.at(mins[vi], kk, vv)
            np.maximum.at(maxs[vi], kk, vv)
            np.add.at(nncounts[vi], kk, 1)
        res = {"count": count, "sums": sums, "sumsqs": sumsqs,
               "mins": mins, "maxs": maxs}
        if any_null:
            res["nncounts"] = nncounts
        return self._finalize(res)

    def _run_join_indexed(self, idx, device, session) -> dict:
        """Join over index-resolved rows (JOIN ... WHERE key = v): only
        matching fact pages are read; the probe is the same sorted-
        searchsorted discipline as the page kernel, and the aggregate
        face reproduces its accumulation dtypes via ``acc_dtypes``."""
        from ..ops.groupby import acc_dtypes
        from ..ops.join import _sorted_build
        if self._join_src is not None:
            # only broadcast-sized on-disk builds reach this runner (the
            # dispatch routes partitioned-sized ones to the scan path's
            # streamed passes); resolving here is therefore bounded
            self._resolve_join_build(session, device)
        probe_col, bk, bv, materialize, limit, offset = self._join
        how = self._join_how
        # the kernel path's exact build-side validation + sort (host
        # arrays; the probe column is int32 by that validation)
        keys, vals = _sorted_build(bk, bv, self.schema, probe_col)
        pos_all = np.sort(self._index_positions(idx, session, device))

        def probe_host(probe):
            if len(keys) == 0:
                return (np.zeros(len(probe), bool),
                        np.zeros(len(probe), np.int32))
            i = np.clip(np.searchsorted(keys, probe), 0, len(keys) - 1)
            return keys[i] == probe, vals[i]

        def emit_of(hit):
            # THE kernel emit derivation (ops.join._emit_mask works on
            # numpy operands too); rows here are already selected, so
            # sel = all-ones
            from ..ops.join import _emit_mask
            return np.asarray(_emit_mask(how, np.ones_like(hit), hit))

        if materialize:
            # batched fetch of ONLY the probe column, stopping once
            # offset+limit emitted rows are found (the early DMA cut-off
            # the seqscan face has)
            end = None if limit is None else offset + limit
            parts, got = [], 0
            batch = 65536
            for b0 in range(0, len(pos_all), batch):
                pb = pos_all[b0:b0 + batch]
                out = self.fetch(pb, cols=[probe_col], session=session,
                                 device=device)
                keep = np.asarray(out["valid"]).astype(bool)
                probe = np.asarray(out[f"col{probe_col}"])[keep]
                pb = pb[keep]
                hit, pay = probe_host(probe)
                emit = emit_of(hit)
                parts.append((pb[emit], probe[emit],
                              np.where(hit, pay, 0)[emit], hit[emit]))
                got += int(emit.sum())
                if end is not None and got >= end:
                    break
            if parts:
                pos_c = np.concatenate([p[0] for p in parts])
                key_c = np.concatenate([p[1] for p in parts])
                pay_c = np.concatenate([p[2] for p in parts])
                hit_c = np.concatenate([p[3] for p in parts])
            else:
                pos_c = np.zeros(0, np.int64)
                key_c = np.zeros(0, np.int32)
                pay_c = np.zeros(0, self._join_value_dtype())
                hit_c = np.zeros(0, bool)
            sl = slice(offset, end)
            return self._join_rows_result(
                how, pos_c[sl].astype(self._pos_dtype()),
                key_c[sl].astype(np.int32),
                pay_c[sl].astype(self._join_value_dtype()),
                hit_c[sl])
        # aggregate face: emitted count + per-column sums over EVERY
        # fact column (the kernel's run.sum_cols set, each in its
        # acc_dtypes accumulator — the GROUP BY convention) + the
        # per-how extras (payload_sum inner/left, null_count left)
        cols = list(range(self.schema.n_cols))
        out = self.fetch(pos_all, cols=cols, session=session,
                         device=device)
        keep = np.asarray(out["valid"]).astype(bool)
        probe = np.asarray(out[f"col{probe_col}"])[keep]
        hit, pay = probe_host(probe)
        emit = emit_of(hit)
        sums = [np.sum(np.asarray(out[f"col{c}"])[keep][emit],
                       dtype=acc_dtypes(self.schema.col_dtype(c))[0])
                for c in cols]
        res = {"matched": np.int32(int(emit.sum())),
               "sums": sums}
        if how in ("inner", "left"):
            res["payload_sum"] = np.sum(
                pay[hit], dtype=acc_dtypes(self._join_value_dtype())[0])
        if how == "left":
            res["null_count"] = np.int32(int((emit & ~hit).sum()))
        return res

    def _run_aggregate_indexed(self, idx, device, session) -> dict:
        """COUNT/SUM over index-resolved rows — the most common index
        query shape: only matching pages are read, and the sums
        reproduce the kernel path's accumulation dtypes exactly (column
        dtype for floats; 4-byte int accumulate without x64, 8-byte
        with — the same wrap semantics the MXU contraction has)."""
        from ..ops.groupby import acc_dtypes
        agg_cols = list(self._agg_cols) if self._agg_cols is not None \
            else list(range(self.schema.n_cols))
        pos = self._index_positions(idx, session, device)
        out = self.fetch(pos, cols=agg_cols, session=session,
                         device=device)
        keep = np.asarray(out["valid"]).astype(bool)
        any_null = any(self.schema.col_nullable(c)
                       for c in range(self.schema.n_cols))
        sums, nncounts = [], []
        for c in agg_cols:
            v = out[f"col{c}"][keep]
            acc = acc_dtypes(self.schema.col_dtype(c))[0]
            # stored NULL words are zero, so plain sums already skip
            # them; the DENOMINATORS must not (COUNT(c)/AVG(c))
            sums.append(np.sum(v, dtype=acc))
            if f"null{c}" in out:
                nncounts.append(np.int32(int(
                    (keep & ~np.asarray(out[f"null{c}"])).sum())))
            else:
                nncounts.append(np.int32(int(keep.sum())))
        res = {"count": np.int32(int(keep.sum())), "sums": sums}
        if any_null:    # key present iff the kernel path would emit it
            res["nncounts"] = nncounts
        return res

    def _run_topk_indexed(self, idx, device, session) -> dict:
        """top_k over index-resolved rows: fetch only matching pages,
        then rank through the SAME kernel ranking (``ops.topk.rank_topk``)
        the page path uses — one implementation, so the two access paths
        cannot drift on tie-breaking, NaN ranking, or the sentinel
        squash.  Candidates are pre-sorted by ascending position so
        first-occurrence tie-breaking means lowest position, exactly the
        scan-order contract."""
        import jax.numpy as jnp

        from ..ops.topk import rank_topk
        col, k, largest = self._topk
        dt = self.schema.col_dtype(col)
        pos = np.sort(self._index_positions(idx, session, device))
        out = self.fetch(pos, cols=[col], session=session, device=device)
        keep = np.asarray(out["valid"]).astype(bool)
        vals = out[f"col{col}"][keep]
        pos = pos[keep].astype(self._pos_dtype())
        v, p = rank_topk(jnp.asarray(vals), jnp.asarray(pos), k, dt,
                         largest)
        return {"values": np.asarray(v), "positions": np.asarray(p)}

    def _run_select(self, plan: QueryPlan, device, session) -> dict:
        """SELECT: stream the scan and hand the matching rows back —
        ``{"col<i>": values, "positions": rows, "count": n}``.  Mesh mode
        gathers on a local device (materialization has no reduction for
        the mesh to partition)."""
        cols, limit, offset = self._select
        if cols is None:
            cols = list(range(self.schema.n_cols))
        # out-of-range columns already surfaced by explain() as an
        # invalid plan; run() refused before reaching here
        gather, fields, dtypes = self._make_gather_fn(cols)
        arrs = self._collect_rows(plan, gather, "mask", fields, dtypes,
                                  device, session, limit=limit,
                                  offset=offset)
        named = dict(zip(fields, arrs))
        out = {f"col{c}": named[f"f{i}"] for i, c in enumerate(cols)}
        for i, c in enumerate(cols):
            if f"n{i}" in named:    # True = NULL (round 5)
                out[f"null{c}"] = named[f"n{i}"]
        out["positions"] = named["pos"]
        out["count"] = np.int64(len(out["positions"]))
        return out

    def _run_join_rows(self, plan: QueryPlan, device, session) -> dict:
        """SELECT-with-JOIN: stream the scan, probe the broadcast build
        table per batch, and hand the emitted rows back —
        ``{"positions", "keys", "count"}`` plus ``payload`` (inner/left)
        and ``matched`` (left)."""
        from ..ops.join import make_join_rows_fn
        probe_col, bk, bv, _mat, limit, offset = self._join
        how = self._join_how
        pred = self._pred
        run = make_join_rows_fn(
            self.schema, probe_col, bk, bv,
            predicate=(lambda cols: pred(cols)) if pred else None,
            how=how)
        fields, dtypes = self._join_row_fields(how)
        arrs = self._collect_rows(
            plan, run, "hit", fields, dtypes,
            device, session, limit=limit, offset=offset)
        return self._join_rows_result(how, *arrs)

    def _join_value_dtype(self) -> np.dtype:
        """The build payload's dtype (int32/uint32/float32)."""
        from ..ops.join import _value_dtype
        if self._join_src is not None:
            _bt, bs, _kc, vc = self._join_src
            return bs.col_dtype(vc)
        bv = self._join[2]
        return _value_dtype(bv) if bv is not None else np.dtype(np.int32)

    def _join_row_fields(self, how: str):
        """Kernel output fields the row face collects under *how* —
        faces that drop a column (semi/anti: payload+partner; inner:
        partner) never D2H-transfer or concatenate it."""
        fields = ["positions", "key"]
        dtypes = [self._pos_dtype(), np.int32]
        if how in ("inner", "left"):
            fields.append("payload")
            dtypes.append(self._join_value_dtype())
        if how == "left":
            fields.append("partner")
            dtypes.append(np.bool_)
        return fields, dtypes

    def _join_rows_result(self, how: str, poss, keyv, payl=None,
                          partner=None) -> dict:
        """One row-face result contract for every join strategy: the
        per-*how* key set (payload only where the face exposes the build
        side; the left face's ``matched`` NULL indicator)."""
        out = {"positions": poss, "keys": keyv,
               "count": np.int64(len(poss))}
        if how in ("inner", "left"):
            out["payload"] = payl
        if how == "left":
            out["matched"] = np.asarray(partner).astype(bool)
        return out

    @staticmethod
    def _sidecar_descending_perm(ka: np.ndarray, lo_i: int,
                                 hi_i: int) -> np.ndarray:
        """[lo_i, hi_i) of the STABLE descending permutation of an
        ascending-sorted key array: key groups reverse, rows WITHIN an
        equal-key group keep ascending (physical) order — matching the
        seqscan's stable lexsort over negated keys (a plain array
        reversal would flip duplicate groups internally and make index
        presence change the answer)."""
        n = len(ka)
        starts = np.flatnonzero(
            np.concatenate(([True], ka[1:] != ka[:-1])))
        group_ends = np.append(starts[1:], n)
        if hi_i <= 4096:
            # small head: walk key groups from the tail, stop once
            # offset+limit rows are in hand — honoring the plan's
            # "reads only the head" without an O(n log n) sort
            parts = []
            got = 0
            for gi in range(len(starts) - 1, -1, -1):
                parts.append(np.arange(starts[gi], group_ends[gi]))
                got += group_ends[gi] - starts[gi]
                if got >= hi_i:
                    break
            return np.concatenate(parts)[lo_i:hi_i]
        # large/unbounded output: one vectorized stable argsort over the
        # group ids beats a Python walk of every group
        g = np.cumsum(np.concatenate(
            ([0], (ka[1:] != ka[:-1]).astype(np.int64))))
        return np.argsort(-g, kind="stable")[lo_i:hi_i]

    def _run_topk_sidecar(self, idx) -> dict:
        """Unfiltered top_k over an indexed integer column: the k best
        keys are the sidecar's head (smallest) or stable-descending tail
        (largest) — no scan.  Candidates then pass through the SAME
        ``rank_topk`` as every other access path, so padding (worst
        sentinel, position -1) and the sentinel squash cannot drift."""
        import jax.numpy as jnp

        from ..ops.topk import rank_topk
        col, k, largest = self._topk
        dt = self.schema.col_dtype(col)
        n = len(idx.keys)
        take = min(k, n)
        if largest:
            perm = self._sidecar_descending_perm(idx.keys, 0, take)
            vals, pos = idx.keys[perm], idx.positions[perm]
        else:
            vals, pos = idx.keys[:take], idx.positions[:take]
        v, p = rank_topk(jnp.asarray(np.ascontiguousarray(vals)),
                         jnp.asarray(np.ascontiguousarray(pos)
                                     .astype(self._pos_dtype())),
                         k, dt, largest)
        return {"values": np.asarray(v), "positions": np.asarray(p)}

    def _run_quantiles_sidecar(self, idx) -> dict:
        """Unfiltered exact quantiles with ZERO table I/O: the sidecar's
        sorted keys ARE the order, nearest-rank picks read straight from
        it (integer columns only — float sidecars strip NaN)."""
        qs = self._quantiles
        n = len(idx.keys)
        if n == 0:
            return {"quantiles": np.full(len(qs), np.nan, np.float64),
                    "n": np.int64(0)}
        ranks = self._nearest_ranks(qs, n)
        return {"quantiles": np.ascontiguousarray(idx.keys[ranks]),
                "n": np.int64(n)}

    def _run_count_distinct_sidecar(self, idx) -> dict:
        """Unfiltered COUNT(DISTINCT) with ZERO table I/O: adjacent-diff
        over the sidecar's sorted keys."""
        k = idx.keys
        d = 0 if len(k) == 0 else int((k[1:] != k[:-1]).sum()) + 1
        return {"distinct": np.int32(d)}

    def _run_order_by_prefix(self, idx) -> dict:
        """``WHERE c0 = v ORDER BY c1`` from a composite (c0, c1)
        sidecar: the matching rows are ONE contiguous sidecar span,
        already sorted by c1 (packed-key low word) — no sort, no table
        I/O; values unpack straight from the keys."""
        from .index import unpack_second
        _ce, v = self._eq
        _cols, descending, limit, offset = self._order
        a, b = idx.prefix_span(v) if v is not None else (0, 0)
        span_keys = idx.keys[a:b]
        span_pos = idx.positions[a:b]
        n = b - a
        end = n if limit is None else min(n, offset + limit)
        lo_i, hi_i = min(offset, n), min(end, n)
        if descending:
            # the group walk needs the whole span's key order
            vals1 = unpack_second(span_keys, idx.key_dtypes[1])
            perm = self._sidecar_descending_perm(vals1, lo_i, hi_i)
            pos = span_pos[perm]
            vals = vals1[perm]
        else:
            # LIMIT touches only the head: slice BEFORE unpacking
            pos = span_pos[lo_i:hi_i]
            vals = unpack_second(span_keys[lo_i:hi_i], idx.key_dtypes[1])
        return {"values": np.ascontiguousarray(vals),
                "positions": np.ascontiguousarray(pos)
                .astype(self._pos_dtype())}

    def _run_order_by_indexed(self, idx, device, session) -> dict:
        """ORDER BY served from a fresh sidecar: the index order IS the
        answer — no sort, no full-column gather; a LIMIT touches only the
        head of the sidecar (and, for composite keys, only the head's
        pages).  Result contract matches :meth:`_run_order_by` local mode
        (``values`` = primary column, ``positions``); duplicate ordering
        is the build's physical order, same as the stable seqscan sort."""
        cols, descending, limit, offset = self._order
        self._check_sortable_col(cols[0], "order_by")
        n = len(idx.positions)
        end = n if limit is None else min(n, offset + limit)
        lo_i, hi_i = min(offset, n), min(end, n)
        if descending:
            perm = self._sidecar_descending_perm(idx.keys, lo_i, hi_i)
            pos = idx.positions[perm]
            keys = idx.keys[perm]
        else:
            pos = idx.positions[lo_i:hi_i]
            keys = idx.keys[lo_i:hi_i]
        pos = np.ascontiguousarray(pos)
        if not idx.composite:
            return {"values": np.ascontiguousarray(keys),
                    "positions": pos.astype(self._pos_dtype())}
        # composite sidecar: keys are packed pairs — fetch the primary
        # column's values for the (already sliced) head only
        out = self.fetch(pos, cols=[cols[0]], session=session,
                         device=device)
        keep = np.asarray(out["valid"]).astype(bool)
        return {"values": out[f"col{cols[0]}"][keep],
                "positions": pos[keep].astype(self._pos_dtype())}

    def _run_join_partitioned(self, plan: QueryPlan, mesh, device,
                              session, n_parts: int,
                              batch_pages: Optional[int] = None) -> dict:
        """Partitioned hash join — the build side is too large to
        broadcast (EXPLAIN's ``join_strategy``).

        Mesh: one scan; the build lives hash-sharded 1/dp per device and
        every batch all_to_all-routes rows to their key's owner
        (:mod:`..parallel.pjoin`).  Local: Grace-style sequential passes,
        one hash partition of the build resident at a time (n_parts
        scans, build memory bounded by ``join_broadcast_max``).  Results
        add across partitions because every build key lives in exactly
        one — and, for the left/anti faces, because each pass restricts
        itself to the probe rows its partition OWNS (an unpartnered row
        must be emitted by exactly one pass, not every pass).
        Materialized row order is per-partition arrival order —
        unspecified, like SQL without ORDER BY; parity with broadcast is
        set-equality."""
        probe_col, bk, bv, materialize, limit, offset = self._join
        how = self._join_how
        pred = self._pred
        from .executor import fold_results
        if mesh is not None and materialize:
            return self._run_join_partitioned_mesh_rows(
                mesh, session, device, batch_pages, probe_col, bk, bv,
                limit, offset)
        if mesh is not None and not materialize:
            from ..parallel.pjoin import make_partitioned_join_step
            step = make_partitioned_join_step(
                mesh, self.schema, probe_col, bk, bv,
                predicate=(lambda cols: pred(cols)) if pred else None,
                build_parts=self._streamed_build_parts(mesh, session,
                                                       device),
                how=how)
            src, own = self._open_owned()
            try:
                acc = None
                for pages in self._mesh_page_batches(src, mesh,
                                                     batch_pages, session):
                    acc = fold_results(acc, step(pages), None)
                import jax as _jax
                return {} if acc is None else \
                    _jax.tree.map(np.asarray, acc)
            finally:
                if own:
                    src.close()
        # local: Grace sequential passes (both faces)
        from ..ops.join import (hash_split_build, make_join_fn,
                                make_join_rows_fn)
        if self._join_src is not None:
            # on-disk build side: stream ONE partition per pass (hash
            # predicate pushdown) — host RAM bounded to a partition, and
            # a LIMIT early-exit below never even scans the build rows
            # of the partitions it skips
            parts = self._streamed_build_partitions(n_parts, session,
                                                    device)
        else:
            parts = hash_split_build(bk, bv, n_parts)
        if materialize:
            # LIMIT early-exit across Grace passes (VERDICT r3 #3): each
            # partition scan stops issuing I/O at its remaining row
            # budget, and partitions past the budget are never scanned
            # at all — matching the broadcast row face's early DMA
            # cut-off.  Row order is per-partition arrival order
            # (unspecified, like SQL without ORDER BY), so taking the
            # first offset+limit rows in partition order is a valid
            # instance of the contract.
            stop = None if limit is None else offset + limit
            fields, dtypes = self._join_row_fields(how)
            cols_acc = [[] for _ in fields]
            gathered = 0
            own_needed = how in ("left", "anti")
            for p, (pk, pv) in enumerate(parts):
                remaining = None if stop is None else stop - gathered
                if remaining is not None and remaining <= 0:
                    break
                run = make_join_rows_fn(
                    self.schema, probe_col, pk, pv,
                    predicate=(lambda cols: pred(cols)) if pred else None,
                    how=how,
                    owner_part=(n_parts, p) if own_needed else None)
                got = self._collect_rows(
                    plan, run, "hit", fields, dtypes,
                    device, session, limit=remaining)
                gathered += len(got[0])
                for acc, a in zip(cols_acc, got):
                    acc.append(a)
            end = None if limit is None else offset + limit
            if cols_acc[0]:
                arrs = [np.concatenate(a)[offset:end] for a in cols_acc]
            else:   # limit=0 breaks before any partition scans
                arrs = [np.zeros(0, dt) for dt in dtypes]
            return self._join_rows_result(how, *arrs)
        acc = None
        own_needed = how in ("left", "anti")
        for p, (pk, pv) in enumerate(parts):
            run = make_join_fn(
                self.schema, probe_col, pk, pv,
                predicate=(lambda cols: pred(cols)) if pred else None,
                how=how, owner_part=(n_parts, p) if own_needed else None)
            fn = lambda pages, run=run: run(pages)
            if plan.access_path == "direct":
                from ..config import config as _cfg
                from .executor import TableScanner
                src, own = self._open_owned()
                try:
                    with TableScanner(src, self.schema,
                                      session=session) as sc:
                        out = sc.scan_filter(
                            fn, device=device,
                            dispatch_coalesce=int(
                                _cfg.get("scan_dispatch_batch")))
                        self._last_scan_h2d_depth = getattr(
                            sc, "last_h2d_depth", 0)
                finally:
                    if own:
                        src.close()
            else:
                out = self._vfs_scan(fn, None, device)
            acc = fold_results(acc, out, None)
        import jax as _jax
        # per-leaf: the heterogeneous sums list keeps its acc dtypes
        return {} if acc is None else _jax.tree.map(np.asarray, acc)

    def _mesh_page_batches(self, src, mesh, batch_pages, session):
        """Yield dp-divisible page batches covering EVERY page of *src*:
        the double-buffered sharded stream for the batch-aligned body,
        then zero-padded host reads for the tail (zero pages decode as
        no valid tuples, so the shard_map'ed step covers them too).
        One implementation of the batch-rounding + tail discipline,
        shared by the partitioned join's aggregate and row faces."""
        from ..parallel.stream import ShardedBatchStream
        n_shards = mesh.shape["dp"]
        n_pages = src.size // PAGE_SIZE
        bp, covered = _mesh_steps(n_pages, n_shards, batch_pages)
        if covered:
            with ShardedBatchStream(src, mesh, batch_pages=bp,
                                    session=session) as stream:
                for _first, arr in stream:
                    yield arr
        tail_batch = max(BATCH_PAGES, n_shards)
        for p0 in range(covered, n_pages, tail_batch):
            npg = min(tail_batch, n_pages - p0)
            raw = bytearray(npg * PAGE_SIZE)
            src.read_buffered(p0 * PAGE_SIZE, memoryview(raw))
            pages = np.frombuffer(raw, np.uint8).reshape(-1, PAGE_SIZE)
            padn = (-npg) % n_shards
            if padn:
                pages = np.concatenate(
                    [pages, np.zeros((padn, PAGE_SIZE), np.uint8)])
            yield pages

    def _streamed_build_parts(self, mesh, session, device):
        """Mesh build parts for an on-disk build side (None when the
        build is host arrays): partition-sized Grace passes bounded by
        ``config join_build_host_max``."""
        if self._join_src is None:
            return None
        from ..parallel.pjoin import partition_build_sharded_from_table
        bt, bs, kc, vc = self._join_src
        return partition_build_sharded_from_table(
            bt, bs, kc, vc, mesh, session=session, device=device)

    def _streamed_build_partitions(self, n_parts: int, session, device):
        """Yield the local Grace passes' (keys, values) partitions from
        the on-disk build side.  Under ``join_build_host_max`` the table
        loads with ONE projection scan and partitions in memory (the
        same budget fast path as the mesh builder); above it, one
        hash-predicate scan per partition, host RAM bounded to a
        partition — with a size+mtime stamp re-checked between passes so
        a build table mutated mid-query fails (EIO) instead of silently
        double-counting keys that moved partitions."""
        import jax.numpy as jnp

        from ..config import config
        from ..ops.join import hash_split_build, key_hash32
        bt, bs, kc, vc = self._join_src
        if os.path.getsize(bt) <= int(config.get("join_build_host_max")):
            out = Query(bt, bs).select([kc, vc]).run(session=session,
                                                     device=device)
            yield from hash_split_build(
                np.asarray(out[f"col{kc}"], np.int32),
                np.asarray(out[f"col{vc}"], bs.col_dtype(vc)), n_parts)
            return

        def owner(cols):
            return (key_hash32(cols[kc]) % jnp.uint32(n_parts)) \
                .astype(jnp.int32)

        def stamp():
            st = os.stat(bt)
            return int(st.st_size), int(st.st_mtime_ns)

        s0 = stamp()
        for p in range(n_parts):
            part = Query(bt, bs) \
                .where(lambda cols, p=p: owner(cols) == p) \
                .select([kc, vc]).run(session=session, device=device)
            if stamp() != s0:
                raise StromError(5, f"build table {bt} changed between "
                                    f"partition passes")
            yield (np.asarray(part[f"col{kc}"], np.int32),
                   np.asarray(part[f"col{vc}"], bs.col_dtype(vc)))

    def _run_join_partitioned_mesh_rows(self, mesh, session, device,
                                        batch_pages,
                                        probe_col, bk, bv,
                                        limit: Optional[int],
                                        offset: int) -> dict:
        """Mesh partitioned join, row face (VERDICT r3 #3): the build
        lives hash-sharded 1/dp per device, every batch all_to_all-routes
        rows (key + position words) to their owner, and each owner's
        per-row outcomes come back for host-side compression — same
        result contract as the broadcast row face, with the same LIMIT
        early-exit (the stream stops issuing SSD DMA once offset+limit
        emitted rows are in hand)."""
        from ..parallel.pjoin import (combine_pos_words,
                                      make_partitioned_join_rows_step)
        how = self._join_how
        pred = self._pred
        step = make_partitioned_join_rows_step(
            mesh, self.schema, probe_col, bk, bv,
            predicate=(lambda cols: pred(cols)) if pred else None,
            build_parts=self._streamed_build_parts(mesh, session,
                                                   device),
            how=how)
        stop = None if limit is None else offset + limit
        chunks: List[tuple] = []
        gathered = 0

        fields, dtypes = self._join_row_fields(how)
        # positions arrive as exchange words; the remaining fields come
        # straight off the step's per-how output set
        tail_fields = fields[1:]

        def take(out) -> bool:
            nonlocal gathered
            emit = np.asarray(out["hit"]).astype(bool)
            lo = np.asarray(out["pos_lo"])[emit]
            hi = np.asarray(out["pos_hi"])[emit]
            chunks.append(
                (combine_pos_words(lo, hi, self._pos_dtype()),)
                + tuple(np.asarray(out[f])[emit] for f in tail_fields))
            gathered += int(emit.sum())
            return stop is not None and gathered >= stop
        src, own = self._open_owned()
        try:
            # LIMIT early-exit: the break closes the generator, which
            # shuts the sharded stream down and stops issuing SSD DMA
            for pages in self._mesh_page_batches(src, mesh, batch_pages,
                                                 session):
                if take(step(pages)):
                    break
        finally:
            if own:
                src.close()
        if chunks:
            arrs = [np.concatenate([c[i] for c in chunks])[offset:stop]
                    for i in range(len(fields))]
        else:
            arrs = [np.zeros(0, dt) for dt in dtypes]
        return self._join_rows_result(how, *arrs)

    @staticmethod
    def _mesh_sort_loop(mesh, factory, *arrays):
        """Shared capacity-resize loop of the distributed sort family:
        start at 2.5x balance slack over perfectly uniform buckets,
        double and rerun whenever skewed keys overflow a bucket.
        ``factory(devices, capacity) -> run``; returns ``(out, dp)``."""
        sort_devices = list(mesh.devices.reshape(-1))
        dp = len(sort_devices)
        n = len(arrays[0])
        capacity = max(64, -(-n * 5 // (2 * dp * dp)))
        while True:
            run = factory(sort_devices, capacity)
            out = run(*arrays)
            if int(out["n_dropped"]) == 0:
                return out, dp
            capacity *= 2

    def _run_quantiles(self, plan: QueryPlan, mesh, device,
                       session) -> dict:
        """Exact nearest-rank quantiles: gather the column, sort (locally
        or via the distributed sample sort), and read one value per rank
        from the bucket distribution — ``{"quantiles", "n"}``."""
        col = self._order[0][0]
        dt = self._check_sortable_col(col, "quantiles")
        gather, fields, dtypes = self._make_gather_fn(
            [col], want_positions=False)
        (vals,) = self._collect_rows(plan, gather, "mask", fields,
                                     dtypes, device, session)
        qs = self._quantiles
        n = len(vals)
        if n == 0:
            return {"quantiles": np.full(len(qs), np.nan, np.float64),
                    "n": np.int64(0)}
        # nearest-rank: index = ceil(q*n) - 1, clamped into the order
        ranks = self._nearest_ranks(qs, n)
        if mesh is None:
            svals = np.sort(vals)
            return {"quantiles": svals[ranks], "n": np.int64(n)}
        from ..parallel.sort import make_distributed_sort
        out, _dp = self._mesh_sort_loop(
            mesh,
            lambda devs, cap: make_distributed_sort(
                devs, capacity=cap, dtype=dt, with_payload=False)[0],
            vals)
        counts = np.asarray(out["count"])
        cum = np.cumsum(counts)
        picked = []
        for r in ranks:
            b = int(np.searchsorted(cum, r + 1))
            within = r - (int(cum[b - 1]) if b else 0)
            # fetch only the bucket row holding the rank, not the whole
            # (dp, dp*capacity) sorted array (the docstring's contract)
            picked.append(np.asarray(out["values"][b])[within])
        return {"quantiles": np.array(picked, dt), "n": np.int64(n)}

    def _run_count_distinct(self, plan: QueryPlan, mesh, device,
                            session) -> dict:
        """Exact COUNT(DISTINCT col): gathered values dedupe via the
        distributed sort + ppermute boundary count under a mesh, or a
        host unique count locally."""
        col = self._order[0][0]
        dt = self._check_sortable_col(col, "count_distinct")
        gather, fields, dtypes = self._make_gather_fn(
            [col], want_positions=False)
        (vals,) = self._collect_rows(plan, gather, "mask", fields,
                                     dtypes, device, session)
        if mesh is None:
            # equal_nan=False: each NaN is its own value (IEEE !=), the
            # same semantics the mesh kernel's adjacent-diff implements
            return {"distinct": np.int32(len(
                np.unique(vals, equal_nan=False)))}
        from ..parallel.sort import make_distributed_distinct
        out, _dp = self._mesh_sort_loop(
            mesh,
            lambda devs, cap: make_distributed_distinct(
                devs, capacity=cap, dtype=dt)[0],
            vals)
        return {"distinct": np.int32(out["distinct"])}

    def _run_order_by(self, plan: QueryPlan, mesh, device, session) -> dict:
        """ORDER BY: gather (values, global positions, validity) through
        the planned access path, then sort — distributed sample sort on a
        mesh, one-device lax sort locally.  Returns the flat global order
        ``{"values", "positions"}`` (+ ``per_device_count``/``n_dropped``
        info keys in mesh mode).

        The gather phase runs on one local device even in mesh mode (the
        sort collectives are the distributed piece); for multi-host
        gather-side sharding, stream via ``load_pages_sharded`` and feed
        :func:`..parallel.sort.make_distributed_sort` directly."""
        cols, descending, limit, offset = self._order
        end = None if limit is None else offset + limit
        if mesh is not None and len(cols) > 1:
            raise StromError(
                95,  # EOPNOTSUPP
                "mesh order_by sorts one key column (the slab exchange "
                "carries a single key); sort multi-column orderings "
                "locally, or pre-combine the keys into one column")
        dts = [self._check_sortable_col(c, "order_by") for c in cols]
        dt = dts[0]
        gather, fields, dtypes = self._make_gather_fn(cols)
        arrs = self._collect_rows(plan, gather, "mask", fields, dtypes,
                                  device, session)
        keys, poss = arrs[:-1], arrs[-1]
        # positions normalize to int32 on the mesh path (slab payload
        # width); keep the empty case's dtype consistent with that
        pos_np_t = np.int32 if mesh is not None else self._pos_dtype()
        vals = keys[0]
        if len(vals) == 0:   # empty source or nothing selected
            out = {"values": vals, "positions": poss.astype(pos_np_t)}
            if mesh is not None:   # keep the mesh contract's info keys
                out["per_device_count"] = np.zeros(
                    int(np.prod(list(mesh.shape.values()))), np.int32)
                out["n_dropped"] = np.int32(0)
            return out

        if mesh is None:
            # np.lexsort: LAST key is primary and the sort is stable, so
            # reversed keys give ORDER BY cols[0], cols[1], ...
            def sort_key(k):
                if not descending:
                    return k
                return -k if k.dtype.kind == "f" else ~k
            order = np.lexsort(tuple(sort_key(k)
                                     for k in reversed(keys)))[offset:end]
            return {"values": vals[order], "positions": poss[order]}

        from ..parallel.sort import make_distributed_sort
        n = len(vals)
        if poss.dtype != np.int32:
            # slab payloads are int32; past 2^31 rows a cast would wrap
            # row identity silently — refuse instead
            if n and int(poss.max()) > (1 << 31) - 1:
                raise StromError(
                    34, "mesh order_by row positions exceed int32; "
                    "tables past 2^31 rows need the local sort path")
            poss = poss.astype(np.int32)
        # the sort flattens the caller's (sp, dp) mesh into its own 1-D
        # dp axis — the concat below must walk ALL its buckets, not the
        # caller mesh's dp size
        out, dp = self._mesh_sort_loop(
            mesh,
            lambda devs, cap: make_distributed_sort(
                devs, capacity=cap, dtype=dt, descending=descending)[0],
            vals, poss)
        counts = np.asarray(out["count"])
        v = np.concatenate([np.asarray(out["values"])[b][:counts[b]]
                            for b in range(dp)])
        p = np.concatenate([np.asarray(out["payload"])[b][:counts[b]]
                            for b in range(dp)])
        return {"values": v[offset:end], "positions": p[offset:end],
                "per_device_count": counts, "n_dropped": np.int32(0)}

    def _vfs_scan(self, fn, combine, device, *, first_page: int = 0,
                  src=None) -> dict:
        """Buffered fallback below the planner threshold (the conventional
        path the reference leaves small tables on).  Reads through the
        Source abstraction, so multi-file stripe sets and live Source
        objects scan identically to the direct path.  The 8 MiB batches
        are read ahead on a reader thread into ``h2d_depth_max + 1``
        reused buffers (:class:`.read_ring.ReadAheadRing`), joined before
        an owned source closes.  The mesh path scans its tail here: the
        pages from *first_page* on, of its already open *src*."""
        import jax

        from ..config import config as _cfg
        from ..hbm.staging import safe_device_put
        from ..stats import stats
        from .executor import fold_results
        from .read_ring import ReadAheadRing
        dev = device or jax.local_devices()[0]
        src, own = (src, False) if src is not None else self._open_owned()
        try:
            acc = None
            with ReadAheadRing(src, BATCH_PAGES,
                               int(_cfg.get("h2d_depth_max")) + 1,
                               first_page=first_page) as ring:
                for slot, pages in ring:
                    with stats.stage("staging_retire"):
                        # safe_device_put: the CPU backend would alias
                        # the slot, which the reader refills
                        dev_pages = safe_device_put(pages, dev)
                        ring.recycle(slot, dev_pages)
                        out = fn(dev_pages)
                    stats.add("nr_kernel_dispatch")
                    acc = fold_results(acc, out, combine)
        finally:
            if own:
                src.close()
        if acc is None:
            return {}
        # per-leaf: the heterogeneous sums list keeps its acc dtypes
        return jax.tree.map(np.asarray, acc)
