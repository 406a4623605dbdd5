"""The value-keyed GROUP BY's key set, its exact-membership key function,
and the one-pass scan kernel that grows the set as the scan meets keys.

A :class:`GroupKeySet` gives each distinct key tuple a stable group id,
in the order keys arrive: ids never move as the set grows, so per-group
device accumulators never need remapping.  On the device the set is a key
table, passed to the kernels as an argument: each key column's values,
padded to a power-of-four capacity, beside the id of each row.  :func:`lookup_group_ids` compares a row's key with every table
row: a selected row whose key the table does not hold gets the sentinel
id, which the kernels count like any group, so the sentinel's row count
is the batch's miss count.

:class:`OnePassGroups` is the stateful per-query kernel of a single-
process scan: it starts the set from the first batch's keys, reads each
batch's miss count one batch behind, and for a batch that missed keys
adds them and runs the kernel again on that batch over only those keys.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import numpy as np

from .executor import fold_results
from .index import _to_u32_order

__all__ = ["GroupKeySet", "GroupSpill", "OnePassGroups",
           "lookup_group_ids"]


class GroupSpill(Exception):
    """The key set passed ``max_groups``.  Private control flow: the
    runner reroutes to the sorted aggregation where that serves the shape
    (1-2 key columns, column aggregates), and fails with ENOMEM
    otherwise."""

    def __init__(self, seen: int):
        self.seen = seen
        super().__init__(f"group key set passed {seen} distinct keys")


def _pack(vals: Sequence[np.ndarray], dts) -> np.ndarray:
    """One sortable host key per row, ordered like the key tuples: uint64
    for 1-2 columns, raw big-endian bytes for 3-4."""
    us = [_to_u32_order(np.asarray(v), dt) for v, dt in zip(vals, dts)]
    if len(us) == 1:
        return us[0]
    if len(us) == 2:
        return (us[0] << np.uint64(32)) | us[1]
    rows = np.ascontiguousarray(np.stack(us, 1).astype(">u4"))
    return rows.view(f"V{4 * len(us)}").reshape(-1)


def _capacity(n: int) -> int:
    """A key table's length for *n* keys: the smallest power of four at
    least *n*, so a set that grows to G keys compiles about log4(G)
    programs of each kernel."""
    c = 1
    while c < n:
        c <<= 2
    return c


def lookup_group_ids(cols, key_cols: Sequence[int], tabs, ids, sentinel):
    """``(B, T) int32`` group ids of the rows' key tuples in a key table.

    ``tabs[j]`` holds key column ``key_cols[j]`` of each table row and
    ``ids`` the group id of each row, every id below *sentinel*; padding
    rows carry *sentinel*.  A row whose key tuple the table does not hold
    gets *sentinel*.  Each row is compared with every table row, which
    keeps to the vector units: the kernels' per-group sums already cost
    rows x groups, and a search would gather from the table, which
    serialises on TPU."""
    import jax.numpy as jnp

    hit = None
    for c, t in zip(key_cols, tabs):
        eq = cols[c][..., None] == t                   # (B, T, rows)
        hit = eq if hit is None else hit & eq
    # keys are distinct, so only a padding row can match beside a key
    return jnp.min(jnp.where(hit, ids, sentinel), axis=-1).astype(jnp.int32)


class GroupKeySet:
    """Distinct key tuples of 4-byte integer columns, each with a stable
    group id in arrival order (keys new in one :meth:`add` take theirs in
    key order).  Past *max_groups* keys :meth:`add` raises
    :class:`GroupSpill`."""

    def __init__(self, dts, max_groups: int):
        self.dts = [np.dtype(d) for d in dts]
        self.max_groups = int(max_groups)
        self.cols = [np.zeros(0, dt) for dt in self.dts]   # by group id
        self._packed = _pack(self.cols, self.dts)          # by group id
        self._sorted = self._packed                        # key order
        self._sorted_ids = np.zeros(0, np.int32)

    def __len__(self) -> int:
        return len(self._packed)

    @property
    def capacity(self) -> int:
        """The whole set's device table length."""
        return _capacity(len(self))

    def add(self, vals: Sequence[np.ndarray]) -> np.ndarray:
        """Add the key tuples of the rows *vals* (one array per key
        column) that the set lacks; returns the ids of the rows' distinct
        keys."""
        vals = [np.asarray(v) for v in vals]
        if len(vals[0]) > 1:     # runs of one key (a clustered table) first
            keep = np.ones(len(vals[0]), bool)
            keep[1:] = False
            for v in vals:
                keep[1:] |= v[1:] != v[:-1]
            vals = [v[keep] for v in vals]
        u, first = np.unique(_pack(vals, self.dts), return_index=True)
        pos = np.searchsorted(self._sorted, u)
        known = pos < len(self)
        known[known] = self._sorted[pos[known]] == u[known]
        n_new = int((~known).sum())
        if len(self) + n_new > self.max_groups:
            raise GroupSpill(len(self) + n_new)
        out = np.empty(len(u), np.int32)
        out[known] = self._sorted_ids[pos[known]]
        out[~known] = np.arange(len(self), len(self) + n_new,
                                dtype=np.int32)
        if n_new:
            fresh = first[~known]
            self.cols = [np.concatenate([c, v[fresh]])
                         for c, v in zip(self.cols, vals)]
            self._packed = np.concatenate([self._packed, u[~known]])
            order = np.argsort(self._packed, kind="stable")
            self._sorted = self._packed[order]
            self._sorted_ids = order.astype(np.int32)
        return out

    def key_order(self) -> np.ndarray:
        """Group ids in key order."""
        return self._sorted_ids

    def table(self, ids: Optional[np.ndarray] = None, *,
              sentinel: Optional[int] = None):
        """A device key table ``(tabs, labels)``, padded to a power of
        four with each column's largest value under the label *sentinel*
        (the table's length by default): the whole set, labelled by group
        id, or, given *ids*, those keys, each labelled by its position in
        *ids*."""
        import jax.numpy as jnp

        rows = np.arange(len(self)) if ids is None else np.asarray(ids)
        labels = rows if ids is None else np.arange(len(rows))
        cap = _capacity(len(rows))
        sentinel = cap if sentinel is None else int(sentinel)
        tabs = []
        for c, dt in zip(self.cols, self.dts):
            t = np.full(cap, np.iinfo(dt).max, dt)
            t[:len(rows)] = c[rows]
            tabs.append(jnp.asarray(t))
        idarr = np.full(cap, sentinel, np.int32)
        idarr[:len(rows)] = labels
        return tuple(tabs), jnp.asarray(idarr)


class OnePassGroups:
    """Stateful per-query kernel for a single-process scan loop: call it
    on each device page batch in turn, then :meth:`finish`.  It returns
    ``{}`` to the loop and folds the group kernel's outputs itself.

    *kernel_for(cap)* builds the jitted group kernel
    ``fn(pages, tabs, ids) -> dict`` over ``cap + 1`` groups (the last
    the sentinel), once per capacity; *gather* is the jitted
    ``gather(pages, tabs, ids) -> {"mask", "f0", ...}`` whose mask selects
    the rows the predicate keeps and the table misses, beside each key
    column.  So the set's growth compiles one program of each per power
    of four.

    The first batch's selected keys start the set (its one host copy).
    Each batch's kernel runs over the whole set, and its sentinel count
    is read one batch later, before the next batch's kernel: when rows
    missed, that batch's missed rows are gathered (ahead of the next
    kernel), the keys the set lacks are added (while the next kernel
    runs), and the batch's kernel runs again over a table of only its
    missed keys, so no row counts twice.  That re-run is sized by those
    keys, not by the whole set (the kernels' cost grows with their
    groups), and :meth:`finish` folds it in by id.  The bootstrap and
    each growth step are timed as the ``group_discover`` stage;
    ``nr_group_key_grow`` counts the steps after the bootstrap that added
    keys, ``nr_group_rerun`` the batches run again."""

    def __init__(self, keys: GroupKeySet, kernel_for: Callable,
                 gather: Callable, combine: Optional[Callable]):
        self.keys = keys
        self._kernel_for, self._gather = kernel_for, gather
        self._kernels: dict = {}
        self._combine = combine
        self._table = None            # (len, tabs, ids) of the whole set
        self._pending = None          # (pages, out, table) one behind
        self._epochs: List[tuple] = []   # (cap, folded outputs)
        self._reruns: List[tuple] = []   # (group ids, re-run outputs)

    def _run(self, pages, table) -> dict:
        tabs, ids = table
        cap = ids.shape[0]
        if cap not in self._kernels:
            self._kernels[cap] = self._kernel_for(cap)
        return self._kernels[cap](pages, tabs, ids)

    def _whole_table(self):
        if self._table is None or self._table[0] != len(self.keys):
            self._table = (len(self.keys),) + self.keys.table()
        return self._table[1:]

    def _fold(self, out: dict) -> None:
        cap = out["count"].shape[-1] - 1
        if self._epochs and self._epochs[-1][0] == cap:
            self._epochs[-1] = (cap, fold_results(self._epochs[-1][1], out,
                                                  self._combine))
        else:
            self._epochs.append((cap, out))

    def _gather_misses(self, pages, table) -> dict:
        from ..stats import stats
        stats.add("nr_kernel_dispatch")
        return self._gather(pages, *table)

    def _add_missed(self, gathered: dict) -> np.ndarray:
        """Add the gathered missed rows' keys the set lacks; returns the
        ids of all their keys."""
        m = np.asarray(gathered["mask"]).astype(bool)
        return self.keys.add([np.asarray(gathered[f"f{j}"])[m]
                              for j in range(len(self.keys.dts))])

    def _misses(self, pending):
        """``(pages, gathered)`` with the miss gather of the pending batch
        dispatched when its kernel missed rows, else None."""
        if pending is None:
            return None
        pages, out, table = pending
        if not int(np.asarray(out["count"])[table[1].shape[0]]):
            return None
        return pages, self._gather_misses(pages, table)

    def _rerun(self, pages, gathered: dict) -> None:
        """Grow the set by a batch's missed keys, and run the batch's
        kernel again over only those keys."""
        from ..stats import stats
        with stats.stage("group_discover"):
            n = len(self.keys)
            missed = self._add_missed(gathered)
            if len(self.keys) > n:
                stats.add("nr_group_key_grow")
            self._reruns.append(
                (missed, self._run(pages, self.keys.table(missed))))
            stats.add("nr_group_rerun")
            stats.add("nr_kernel_dispatch")

    def __call__(self, pages) -> dict:
        from ..stats import stats
        if self._table is None:          # the first batch: bootstrap
            with stats.stage("group_discover"):
                self._add_missed(self._gather_misses(pages,
                                                     self.keys.table()))
        # the previous batch's gather goes first, so the host merges its
        # keys while this batch's kernel runs
        grow = self._misses(self._pending)
        table = self._whole_table()
        out = self._run(pages, table)
        out["count"].copy_to_host_async()      # read at the next batch
        self._fold(out)
        if grow is not None:
            self._rerun(*grow)
        self._pending = (pages, out, table)
        return {}

    def finish(self) -> Optional[dict]:
        """The folded per-group outputs as numpy, over group ids from 0
        up to at least the set's size (the sentinel dropped); None when
        no batch ran."""
        import jax
        grow, self._pending = self._misses(self._pending), None
        if grow is not None:
            self._rerun(*grow)
        # one device_get each: the copies overlap instead of queueing
        epochs = jax.device_get([acc for _, acc in self._epochs])
        reruns = jax.device_get([out for _, out in self._reruns])
        res, have = None, 0
        for (cap, _), acc in zip(self._epochs, epochs):
            acc = jax.tree.map(lambda a: a[..., :cap], acc)
            if res is not None:
                # ids below the earlier epoch's capacity fold; the later
                # epoch alone holds the ids it added
                head = fold_results(
                    res, jax.tree.map(lambda a: a[..., :have], acc),
                    self._combine)
                acc = jax.tree.map(
                    lambda h, a: np.concatenate(
                        [np.asarray(h), a[..., have:]], axis=-1),
                    head, acc)
            res, have = acc, cap
        if self._reruns:
            # a re-run folds into the ids the first runs saw, and alone
            # gives those added after the last first run
            n = max(have, len(self.keys))
            res = jax.tree.map(lambda a: np.concatenate(
                [a, np.zeros(a.shape[:-1] + (n - have,), a.dtype)], -1),
                res)
            seen = np.arange(n) < have
            for (ids, _), out in zip(self._reruns, reruns):
                out = jax.tree.map(lambda a: a[..., :len(ids)], out)
                head = fold_results(jax.tree.map(lambda a: a[..., ids], res),
                                    out, self._combine)
                for r, h, o in zip(jax.tree.leaves(res),
                                   jax.tree.leaves(head),
                                   jax.tree.leaves(out)):
                    r[..., ids] = np.where(seen[ids], np.asarray(h), o)
                seen[ids] = True
        return res
