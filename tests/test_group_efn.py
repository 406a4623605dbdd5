"""The grouped expression-aggregate kernel (``ops.groupby.make_group_efn``)
against a plain per-group loop over the same rows.

Group ids come from a key function, as the value-keyed GROUP BY builds
them: some groups select no row, a sentinel group takes what the key
table does not hold, and ids outside ``[0, G)`` fall into no group."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nvme_strom_tpu.ops.groupby import make_group_efn
from nvme_strom_tpu.scan.heap import HeapSchema, build_pages
from nvme_strom_tpu.scan.sql import _eval_expr, _expr_info

G = 5                 # groups 0..3, then the sentinel 4
#: key value -> group id: no key maps to group 2 (it stays empty), 9 is
#: not in the key table (the sentinel takes it), -3 and 20 map outside
#: [0, G) and fall into no group
KEYS = {0: 0, 1: 1, 3: 3}


def _key_fn(cols):
    k = cols[0]
    g = jnp.where(k == 0, 0, jnp.where(k == 1, 1, jnp.where(k == 3, 3, 4)))
    return jnp.where(k < 0, -1, jnp.where(k >= 20, G, g))


def _key_np(k):
    return np.where(k < 0, -1, np.where(k >= 20, G, np.vectorize(
        lambda v: KEYS.get(int(v), 4))(k)))


@pytest.fixture(params=[True, False], ids=["x64", "x32"])
def x64(request):
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", request.param)
    yield request.param
    jax.config.update("jax_enable_x64", was)


def _table(wide: bool, n: int = 3000, seed: int = 3):
    rng = np.random.default_rng(seed)
    k = rng.choice(np.array([0, 1, 3, 9, -3, 20], np.int32), n)
    a = rng.integers(-1000, 1000, n).astype(np.int32)
    f = rng.standard_normal(n).astype(np.float32)
    cols, dts = [k, a, f], ["int32", "int32", "float32"]
    if wide:
        cols.append(rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64))
        dts.append("int64")
    schema = HeapSchema(n_cols=len(cols), visibility=False,
                        dtypes=tuple(dts))
    return schema, cols


def _trees(wide: bool):
    t = [("col", 1), ("bin", "*", ("col", 1), ("lit", 3)),
         ("bin", "+", ("col", 2), ("lit", 0.5)), ("lit", 2)]
    if wide:
        t.append(("bin", "-", ("col", 3), ("bin", "*", ("col", 1),
                                            ("col", 1))))
    return t


def _kernel(schema, trees, predicate=None):
    from nvme_strom_tpu.ops.groupby import acc_dtypes
    dts = [_expr_info(e, schema)[0] for e in trees]
    return make_group_efn(
        schema, _key_fn, G, [lambda c, e=e: _eval_expr(e, c) for e in trees],
        [dt.type(0) for dt in dts], [acc_dtypes(dt)[0] for dt in dts],
        predicate=predicate)


def _loop(cols, trees, sel):
    """Per group: the row count and each expression summed in Python
    over the group's selected rows (numpy for the row values)."""
    gid = _key_np(cols[0])
    env = {i: c for i, c in enumerate(cols)}

    def ev(e):
        if e[0] == "col":
            return env[e[1]]
        if e[0] == "lit":
            return np.full(len(cols[0]), e[1])
        a, b = ev(e[2]), ev(e[3])
        return {"+": a + b, "-": a - b, "*": a * b}[e[1]]
    count, sums = [], [[] for _ in trees]
    for g in range(G):
        m = sel & (gid == g)
        count.append(int(m.sum()))
        for i, e in enumerate(trees):
            v = ev(e)
            if v.dtype.kind != "f":
                v = v.astype(np.int64)
            sums[i].append(v[m].sum())
    return count, sums


def test_group_efn_matches_a_per_group_loop(x64):
    schema, cols = _table(wide=x64)
    trees = _trees(wide=x64)
    pages = build_pages(cols, schema)
    out = jax.tree.map(np.asarray, _kernel(schema, trees)(pages))
    count, sums = _loop(cols, trees, np.ones(len(cols[0]), bool))
    assert out["count"].tolist() == count
    assert count[2] == 0 and count[4] > 0     # an empty group, the sentinel
    assert sum(count) < len(cols[0])           # -3 and 20 fall into none
    for i, e in enumerate(trees):
        got = out["esums"][i]
        if _expr_info(e, schema)[0].kind == "f":
            np.testing.assert_allclose(got, np.asarray(sums[i]), rtol=1e-4,
                                       atol=1e-2)
        else:
            assert got.tolist() == [int(s) for s in sums[i]], e
            # x64 widens every int sum to int64; without it int32 wraps,
            # and these sums stay within int32
            assert got.dtype == (np.int64 if x64 else np.int32)
    assert out["esums"][3].tolist() == [2 * c for c in count]


def test_group_efn_predicate_and_padding(x64):
    """The predicate and the pages' empty slots select no row; a page
    batch of only padding gives zero counts and sums everywhere."""
    schema, cols = _table(wide=False, n=1500, seed=8)
    trees = _trees(wide=False)
    fn = _kernel(schema, trees, predicate=lambda c: c[1] > 0)
    out = jax.tree.map(np.asarray, fn(build_pages(cols, schema)))
    count, sums = _loop(cols, trees, cols[1] > 0)
    assert out["count"].tolist() == count
    assert out["esums"][0].tolist() == [int(s) for s in sums[0]]
    empty = jax.tree.map(np.asarray,
                         fn(np.zeros((2, 8192), np.uint8)))
    assert not empty["count"].any()
    assert all(not np.asarray(s).any() for s in empty["esums"])
