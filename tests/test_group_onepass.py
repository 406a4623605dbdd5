"""Value-keyed GROUP BY in one pass: the key set grows inside the
aggregation scan (``scan/group_keys.py``).

Each case builds a heap whose batches force a known amount of growth and
checks, on the grouped expression kernel (``make_group_efn``) and on the
column kernel (``make_groupby_fn``), for 1, 2 and 3 key columns: exact
answers against numpy, each 8 MiB batch read once (``vfs_read`` entries),
and the growth counters ``nr_group_key_grow`` / ``nr_group_rerun``."""

import numpy as np
import pytest

from nvme_strom_tpu import config
from nvme_strom_tpu.api import StromError
from nvme_strom_tpu.scan.group_keys import GroupKeySet, lookup_group_ids
from nvme_strom_tpu.scan.heap import HeapSchema, build_heap_file
from nvme_strom_tpu.scan.query import Query
from nvme_strom_tpu.stats import stats

#: keys c0 int32, c1 uint32, c2 int32; c3 the value, c4 > 0 selects; the
#: rest widen a row so an 8 MiB batch holds few of them
SCHEMA = HeapSchema(n_cols=32, visibility=False,
                    dtypes=("int32", "uint32") + ("int32",) * 30)
R = SCHEMA.tuples_per_page * 1024          # rows in one 8 MiB batch
IMAX, UMAX = np.iinfo(np.int32).max, np.iinfo(np.uint32).max


def _tuples(ks):
    """Key id -> (c0, c1, c2), distinct in c0 alone."""
    ks = np.asarray(ks, np.int64)
    return np.stack([ks * 3 - 7, (ks * 5) % 7, -ks], 1)


def _case(name: str, rng):
    """(key tuples per row (n, 3), c4, batches, key grows, reruns,
    max_groups) of one table shape."""
    if name == "last_batch":         # a key first seen in the last batch
        kid = rng.integers(0, 4, 3 * R)
        kid[2 * R + rng.choice(R, 10, replace=False)] = 4
        return _tuples(kid), np.ones(3 * R), 3, 1, 1, 1 << 16
    if name == "sorted":             # every batch brings keys, some span
        kid = (np.arange(4 * R) + R // 4) // (R // 2)
        return _tuples(kid), np.ones(4 * R), 4, 3, 3, 1 << 16
    if name == "pred_late":          # key 4 dropped early, selected later
        kid = rng.integers(0, 4, 3 * R)
        p = rng.choice([-1, 1], 3 * R)
        early, late = rng.choice(R, 50, replace=False), \
            2 * R + rng.choice(R, 50, replace=False)
        kid[early], p[early] = 4, -1
        kid[late], p[late] = 4, 1
        return _tuples(kid), p, 3, 1, 1, 1 << 16
    if name == "empty":              # one page with no rows
        return np.zeros((0, 3), np.int64), np.zeros(0), 1, 0, 0, 1 << 16
    if name == "all_filtered":
        kid = rng.integers(0, 4, 2 * R)
        return _tuples(kid), -np.ones(2 * R), 2, 0, 0, 1 << 16
    if name == "between":
        # known: a negative key, and keys at both dtypes' largest value
        # (the key table's padding value); batch 1 adds (1, 1, 1),
        # between known keys, and (0, 2, 2), whose values are each known
        # in their column but not as a tuple (c0 = 0 alone is known)
        known = np.array([[0, 0, 0], [2, 2, 2], [-5, 3, -9],
                          [IMAX, UMAX, 5]], np.int64)
        new = np.array([[1, 1, 1], [0, 2, 2]], np.int64)
        rows = known[rng.integers(0, 4, 2 * R)]
        at = R + rng.choice(R, 40, replace=False)
        rows[at] = new[np.arange(40) % 2]
        return rows, np.ones(2 * R), 2, 1, 1, 1 << 16
    assert name == "overflow"        # 3 keys, then 6 past max_groups=4
    kid = np.concatenate([rng.integers(0, 3, R), rng.integers(0, 6, R)])
    return _tuples(kid), np.ones(2 * R), 2, None, None, 4


def _heap(tmp_path, rows, p, seed):
    rng = np.random.default_rng(seed)
    n = len(rows)
    cols = [rows[:, 0].astype(np.int32), rows[:, 1].astype(np.uint32),
            rows[:, 2].astype(np.int32),
            rng.integers(-50, 50, n).astype(np.int32),
            np.asarray(p).astype(np.int32)]
    cols += [np.zeros(n, np.int32)] * (SCHEMA.n_cols - len(cols))
    path = str(tmp_path / "g.heap")
    build_heap_file(path, cols, SCHEMA)
    return path, cols


def _want(cols, nk):
    """numpy GROUP BY: keys in key order, count, sum/min/max of c3."""
    sel = cols[4] > 0
    k = np.stack([cols[j][sel].astype(np.int64) for j in range(nk)], 1)
    v = cols[3][sel].astype(np.int64)
    uk, inv = np.unique(k.reshape(-1, nk), axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    g = len(uk)
    mins, maxs = np.full(g, 1 << 40), np.full(g, -(1 << 40))
    np.minimum.at(mins, inv, v)
    np.maximum.at(maxs, inv, v)
    return {"keys": uk, "count": np.bincount(inv, minlength=g),
            "sum": np.bincount(inv, weights=v, minlength=g).astype(np.int64),
            "min": mins, "max": maxs}


def _query(path, nk, kernel, max_groups):
    q = Query(path, SCHEMA).where(lambda cols: cols[4] > 0)
    keys = list(range(nk))
    if kernel == "expr":
        return q.group_by_cols(keys, agg_cols=[3],
                               agg_exprs=[("bin", "*", ("col", 3),
                                           ("lit", 2))],
                               max_groups=max_groups)
    return q.group_by_cols(keys, agg_cols=[3], max_groups=max_groups)


def _check(out, want, nk, kernel):
    g = len(want["count"])
    for j in range(nk):
        assert out["key_cols"][j].dtype == SCHEMA.col_dtype(j)
        np.testing.assert_array_equal(
            out["key_cols"][j].astype(np.int64), want["keys"][:, j])
    np.testing.assert_array_equal(out["count"], want["count"])
    sums = np.asarray(out["sums"]).reshape(1, g)[0]
    np.testing.assert_array_equal(sums.astype(np.int64), want["sum"])
    if kernel == "expr":
        np.testing.assert_array_equal(
            np.asarray(out["esums"][0]).astype(np.int64), 2 * want["sum"])
    else:
        np.testing.assert_array_equal(np.asarray(out["mins"])[0],
                                      want["min"])
        np.testing.assert_array_equal(np.asarray(out["maxs"])[0],
                                      want["max"])


def _counters():
    return dict(stats.snapshot().counters)


CASES = ["last_batch", "sorted", "pred_late", "empty", "all_filtered",
         "between"]


@pytest.mark.parametrize("kernel", ["expr", "cols"])
@pytest.mark.parametrize("nk", [1, 2, 3])
@pytest.mark.parametrize("case", CASES)
def test_group_onepass_exact_reads_each_batch_once(tmp_path, case, nk,
                                                  kernel):
    rng = np.random.default_rng(11)
    rows, p, batches, grow, rerun, mg = _case(case, rng)
    path, cols = _heap(tmp_path, rows, p, seed=12)
    q = _query(path, nk, kernel, mg)
    assert "keys grow inside the aggregation scan" in q.explain().reason
    before = _counters()
    out = q.run()
    d = {k: v - before.get(k, 0) for k, v in _counters().items()}
    _check(out, _want(cols, nk), nk, kernel)
    assert d["nr_vfs_read"] == batches
    assert (d["nr_group_key_grow"], d["nr_group_rerun"]) == (grow, rerun)
    # one bootstrap, one step per batch re-run; each batch's kernel, the
    # bootstrap's gather, and each re-run's gather and kernel dispatch
    assert d["nr_group_discover"] == 1 + rerun
    assert d["nr_kernel_dispatch"] == batches + 1 + 2 * rerun


def test_group_onepass_between_key_takes_no_neighbour(tmp_path):
    """Key 1 lies between the known 0 and 2: its rows form their own
    group, and 0's and 2's counts hold only their own rows."""
    rng = np.random.default_rng(4)
    rows, p, *_ = _case("between", rng)
    path, cols = _heap(tmp_path, rows, p, seed=5)
    out = _query(path, 1, "expr", 1 << 16).run()
    got = dict(zip(out["key_cols"][0].tolist(), out["count"].tolist()))
    assert got[1] == 20 and got[0] == int((cols[0] == 0).sum())
    assert got[2] == int((cols[0] == 2).sum())


@pytest.mark.parametrize("kernel", ["expr", "cols"])
@pytest.mark.parametrize("nk", [1, 2, 3])
def test_group_onepass_overflow_mid_pass(tmp_path, nk, kernel):
    """Growth past max_groups in the second batch: column aggregates over
    1-2 key columns restart on the sorted aggregation (two more reads),
    anything else fails with ENOMEM after the partial pass."""
    rng = np.random.default_rng(7)
    rows, p, batches, _g, _r, mg = _case("overflow", rng)
    path, cols = _heap(tmp_path, rows, p, seed=8)
    before = _counters()
    if kernel == "expr" or nk == 3:
        with pytest.raises(StromError) as e:
            _query(path, nk, kernel, mg).run()
        assert e.value.errno == 12
        reads = batches
    else:
        out = _query(path, nk, kernel, mg).run()
        _check(out, _want(cols, nk), nk, kernel)
        reads = 2 * batches
    d = {k: v - before.get(k, 0) for k, v in _counters().items()}
    assert d["nr_vfs_read"] == reads


def test_group_onepass_direct_path(tmp_path):
    """The direct executor's batches (64 KiB chunks) run the same
    stateful kernel, without dispatch coalescing: exact answers on a
    table sorted by its key, where chunks keep bringing keys."""
    rng = np.random.default_rng(9)
    rows, p, *_ = _case("sorted", rng)
    path, cols = _heap(tmp_path, rows[:R], p[:R], seed=10)
    config.set("debug_no_threshold", True)
    config.set("chunk_size", "64k")
    config.set("buffer_size", "1m")
    q = _query(path, 2, "expr", 1 << 16)
    assert q.explain().access_path == "direct"
    before = _counters()
    out = q.run()
    d = {k: v - before.get(k, 0) for k, v in _counters().items()}
    _check(out, _want(cols, 2), 2, "expr")
    assert d["nr_group_key_grow"] >= 1
    assert d["nr_group_rerun"] >= d["nr_group_key_grow"]


@pytest.mark.parametrize("nk", [1, 2, 3])
def test_lookup_group_ids_is_exact_membership(nk):
    """Ids in arrival order, the key order apart from them; a tuple the
    table lacks, between or beyond its keys, gets the sentinel."""
    dts = [np.dtype(np.int32), np.dtype(np.uint32), np.dtype(np.int32)][:nk]
    ks = GroupKeySet(dts, 16)
    first = np.array([[5, 1, 0], [-3, 2, 2], [IMAX, UMAX, 7]])
    assert ks.add([first[:, j] for j in range(nk)]).tolist() == [0, 1, 2]
    later = np.array([[0, 0, 0], [5, 1, 0]])           # one new, one known
    assert ks.add([later[:, j] for j in range(nk)]).tolist() == [3, 1]
    assert len(ks) == 4 and ks.capacity == 4
    assert ks.key_order().tolist() == [0, 3, 1, 2]
    probe = np.array([[5, 1, 0], [-3, 2, 2], [IMAX, UMAX, 7], [0, 0, 0],
                      [1, 1, 1], [IMAX - 1, 0, 0], [-4, 0, 0],
                      [5, 2, 0] if nk > 1 else [6, 0, 0]])
    cols = {j: probe[:, j].astype(dts[j]).reshape(1, -1) for j in range(nk)}
    tabs, idarr = ks.table()
    got = np.asarray(lookup_group_ids(cols, list(range(nk)), tabs, idarr,
                                      4)).reshape(-1)
    assert got.tolist() == [1, 0, 2, 3, 4, 4, 4, 4]
    # a table of some keys, labelled by their position among them
    sub_tabs, sub_ids = ks.table(np.array([3, 2], np.int32))
    assert sub_ids.shape == (4,)
    sub = np.asarray(lookup_group_ids(cols, list(range(nk)), sub_tabs,
                                      sub_ids, 4)).reshape(-1)
    assert sub.tolist() == [4, 4, 1, 0, 4, 4, 4, 4]
