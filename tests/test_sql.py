"""SQL front-end: the parsed SELECT subset maps exactly onto Query
terminals — every answer is checked against a numpy oracle, and
out-of-subset statements fail loudly (EINVAL naming the construct),
never silently approximate."""

import numpy as np
import pytest

from nvme_strom_tpu.api import StromError
from nvme_strom_tpu.config import config
from nvme_strom_tpu.scan.heap import HeapSchema, build_heap_file
from nvme_strom_tpu.scan.sql import parse_sql, sql_query


@pytest.fixture()
def table(tmp_path):
    rng = np.random.default_rng(42)
    schema = HeapSchema(n_cols=3, visibility=False,
                        dtypes=("int32", "int32", "float32"))
    n = schema.tuples_per_page * 8
    c0 = rng.integers(0, 50, n).astype(np.int32)
    c1 = rng.integers(-100, 100, n).astype(np.int32)
    c2 = rng.standard_normal(n).astype(np.float32)
    path = str(tmp_path / "t.heap")
    build_heap_file(path, [c0, c1, c2], schema)
    config.set("debug_no_threshold", True)
    return path, schema, c0, c1, c2


def test_sql_scalar_aggregates(table):
    path, schema, c0, c1, c2 = table
    out = sql_query("SELECT COUNT(*), SUM(c1), AVG(c1) FROM t "
                    "WHERE c0 < 10", path, schema)
    sel = c0 < 10
    assert out["count(*)"] == int(sel.sum())
    assert out["sum(c1)"] == int(c1[sel].sum())
    assert out["avg(c1)"] == pytest.approx(c1[sel].mean())


def test_sql_where_forms(table):
    """=, BETWEEN, IN promote to structured filters; residual conds
    compose; literal-first comparisons flip."""
    path, schema, c0, c1, c2 = table
    out = sql_query("SELECT COUNT(*) FROM t WHERE c0 = 7", path, schema)
    assert out["count(*)"] == int((c0 == 7).sum())
    out = sql_query("SELECT COUNT(*) FROM t WHERE c0 BETWEEN 10 AND 19 "
                    "AND c1 > 0", path, schema)
    assert out["count(*)"] == int(((c0 >= 10) & (c0 <= 19)
                                   & (c1 > 0)).sum())
    out = sql_query("SELECT COUNT(*) FROM t WHERE c0 IN (1, 2, 3)",
                    path, schema)
    assert out["count(*)"] == int(np.isin(c0, [1, 2, 3]).sum())
    out = sql_query("SELECT COUNT(*) FROM t WHERE 0 < c1", path, schema)
    assert out["count(*)"] == int((c1 > 0).sum())


def test_sql_group_by_with_having(table):
    path, schema, c0, c1, c2 = table
    out = sql_query("SELECT c0, COUNT(*), SUM(c1), MIN(c1) FROM t "
                    "WHERE c1 > 0 GROUP BY c0 "
                    "HAVING COUNT(*) >= 20", path, schema)
    sel = c1 > 0
    keys = [k for k in np.unique(c0[sel])
            if int((sel & (c0 == k)).sum()) >= 20]
    np.testing.assert_array_equal(out["c0"], np.array(keys))
    for i, k in enumerate(keys):
        m = sel & (c0 == k)
        assert out["count(*)"][i] == int(m.sum())
        assert out["sum(c1)"][i] == int(c1[m].sum())
        assert out["min(c1)"][i] == int(c1[m].min())


def test_sql_select_order_limit(table):
    path, schema, c0, c1, c2 = table
    out = sql_query("SELECT c0, c1 FROM t WHERE c0 = 3 LIMIT 5",
                    path, schema)
    assert len(out["c0"]) == min(5, int((c0 == 3).sum()))
    assert (out["c0"] == 3).all()
    np.testing.assert_array_equal(out["c1"], c1[out["positions"]])
    out = sql_query("SELECT c1 FROM t ORDER BY c1 DESC LIMIT 10",
                    path, schema)
    np.testing.assert_array_equal(out["c1"], np.sort(c1)[::-1][:10])


def test_sql_min_max_count_distinct(table):
    path, schema, c0, c1, c2 = table
    assert sql_query("SELECT MAX(c1) FROM t", path, schema)["max(c1)"] \
        == int(c1.max())
    assert sql_query("SELECT MIN(c1) FROM t WHERE c0 = 3", path,
                     schema)["min(c1)"] == int(c1[c0 == 3].min())
    assert sql_query("SELECT COUNT(DISTINCT c0) FROM t", path,
                     schema)["count(distinct c0)"] == \
        len(np.unique(c0))


def test_sql_star_projection(table):
    path, schema, c0, c1, c2 = table
    out = sql_query("SELECT * FROM t WHERE c1 > 95", path, schema)
    sel = c1 > 95
    np.testing.assert_array_equal(np.sort(out["positions"]),
                                  np.flatnonzero(sel))


def test_sql_rides_the_index(table):
    """WHERE c0 = v through SQL plans the index access path once a
    sidecar is fresh — the facade reaches the planner, not around it."""
    from nvme_strom_tpu.scan.index import build_index
    path, schema, c0, c1, c2 = table
    build_index(path, schema, 0)
    q, _ = parse_sql("SELECT COUNT(*), SUM(c1) FROM t WHERE c0 = 7",
                     path, schema)
    assert q.explain().access_path == "index"
    out = sql_query("SELECT COUNT(*), SUM(c1) FROM t WHERE c0 = 7",
                    path, schema)
    assert out["count(*)"] == int((c0 == 7).sum())
    assert out["sum(c1)"] == int(c1[c0 == 7].sum())


def test_sql_mesh_mode(table):
    import jax

    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    path, schema, c0, c1, c2 = table
    mesh = make_scan_mesh(jax.devices())
    out = sql_query("SELECT COUNT(*), SUM(c1) FROM t WHERE c1 > 0",
                    path, schema, mesh=mesh, batch_pages=8)
    assert out["count(*)"] == int((c1 > 0).sum())
    assert out["sum(c1)"] == int(c1[c1 > 0].sum())


def test_sql_rejects_out_of_subset(table):
    path, schema, *_ = table
    bad = [
        ("SELECT c0 FROM t WHERE c0 = 1 OR", "end of statement"),
        # an unterminated group fails the group reading, backtracks to
        # the arithmetic reading (round 5), and reports ITS mismatch
        ("SELECT c0 FROM t WHERE (c0 = 1 OR c1 = 2", "expected ')'"),
        ("SELECT c9 FROM t", "out of range"),
        ("SELECT c0, SUM(c1) FROM t", "GROUP BY"),
        # mixed-dtype aggregation set (int32 SUM + float32 HAVING SUM)
        # hits the kernels' one-dtype contract with its own clear error
        ("SELECT SUM(c1) FROM t GROUP BY c0 HAVING SUM(c2) > 0",
         "dtype"),
        ("SELECT MAX(c1), SUM(c0) FROM t", "cannot combine"),
        ("SELECT SUM(c0) FROM t ORDER BY c1", "requires GROUP BY"),
        ("SELECT c0 FROM t ORDER BY COUNT(*)", "requires GROUP BY"),
        ("SELECT AVG(*) FROM t", "name a column"),
        ("SELECT c0 FROM t; DROP TABLE t", "tokenize"),
        ("SELECT c0 FROM t LIMIT 5 EXTRA", "trailing"),
        ("SELECT SUM(c0) FROM t HAVING COUNT(*) > 1", "GROUP BY"),
    ]
    for sql, needle in bad:
        with pytest.raises(StromError) as ei:
            sql_query(sql, path, schema)
        assert needle.lower() in str(ei.value).lower(), sql


def test_sql_order_by_projection(table):
    """ORDER BY serves OTHER projected columns via point-lookups by
    position, in sorted order."""
    path, schema, c0, c1, c2 = table
    out = sql_query("SELECT c0, c1 FROM t ORDER BY c1 DESC LIMIT 12",
                    path, schema)
    order = np.argsort(-c1, kind="stable")[:12]
    np.testing.assert_array_equal(out["c1"], c1[order])
    # c0 values correspond row-for-row with the sorted c1 rows
    np.testing.assert_array_equal(out["c0"], c0[out["positions"]])


def test_sql_top_n_groups(table):
    """ORDER BY an aggregate + LIMIT on grouped results — SQL's
    top-N-groups — sorts post-aggregation."""
    path, schema, c0, c1, c2 = table
    out = sql_query("SELECT c0, COUNT(*) FROM t GROUP BY c0 "
                    "ORDER BY COUNT(*) DESC LIMIT 5", path, schema)
    keys, counts = np.unique(c0, return_counts=True)
    want = counts[np.argsort(counts, kind="stable")[::-1][:5]]
    np.testing.assert_array_equal(out["count(*)"], want)
    assert len(out["c0"]) == 5
    # ORDER BY an aggregate that is not selected also works
    out = sql_query("SELECT c0 FROM t GROUP BY c0 "
                    "ORDER BY SUM(c1) DESC LIMIT 3", path, schema)
    sums = np.array([c1[c0 == k].sum() for k in keys])
    np.testing.assert_array_equal(
        out["c0"], keys[np.argsort(sums, kind="stable")[::-1][:3]])


def test_sql_having_over_unselected_aggregate(table):
    """HAVING may reference an aggregate absent from the SELECT list
    (legal SQL) — the parser aggregates it internally."""
    path, schema, c0, c1, c2 = table
    out = sql_query("SELECT c0, COUNT(*) FROM t GROUP BY c0 "
                    "HAVING SUM(c1) > 100", path, schema)
    keys = [k for k in np.unique(c0)
            if int(c1[c0 == k].sum()) > 100]
    np.testing.assert_array_equal(out["c0"], np.array(keys))


def test_sql_empty_results(table):
    path, schema, c0, c1, c2 = table
    out = sql_query("SELECT MAX(c1) FROM t WHERE c0 = 999", path, schema)
    assert out["max(c1)"] is None
    out = sql_query("SELECT c0 FROM t WHERE c0 = 999", path, schema)
    assert len(out["c0"]) == 0
    out = sql_query("SELECT c0, COUNT(*) FROM t WHERE c0 = 999 "
                    "GROUP BY c0", path, schema)
    assert len(out["c0"]) == 0


@pytest.fixture()
def joined(tmp_path):
    rng = np.random.default_rng(77)
    fschema = HeapSchema(n_cols=2, visibility=False)
    n = fschema.tuples_per_page * 6
    c0 = rng.integers(-50, 50, n).astype(np.int32)
    c1 = rng.integers(0, 16, n).astype(np.int32)
    fpath = str(tmp_path / "fact.heap")
    build_heap_file(fpath, [c0, c1], fschema)
    keys = np.arange(0, 8, dtype=np.int32)
    vals = (keys * 100).astype(np.int32)
    dschema = HeapSchema(n_cols=2, visibility=False)
    dpath = str(tmp_path / "dim.heap")
    build_heap_file(dpath, [keys, vals], dschema)
    config.set("debug_no_threshold", True)
    return fpath, fschema, c0, c1, dpath, dschema


def test_sql_join_aggregate_faces(joined):
    fpath, fschema, c0, c1, dpath, dschema = joined
    tables = {"d": (dpath, dschema)}
    partner = c1 < 8
    out = sql_query("SELECT COUNT(*), SUM(c0), SUM(d.c1) FROM t "
                    "JOIN d ON c1 = d.c0", fpath, fschema,
                    tables=tables)
    assert out["count(*)"] == int(partner.sum())
    assert out["sum(c0)"] == int(c0[partner].sum())
    assert out["sum(d.c1)"] == int((c1[partner] * 100).sum())
    out = sql_query("SELECT COUNT(*) FROM t ANTI JOIN d ON c1 = d.c0",
                    fpath, fschema, tables=tables)
    assert out["count(*)"] == int((~partner).sum())
    out = sql_query("SELECT COUNT(*), SUM(d.c1) FROM t "
                    "LEFT JOIN d ON c1 = d.c0 WHERE c0 > 0",
                    fpath, fschema, tables=tables)
    sel = c0 > 0
    assert out["count(*)"] == int(sel.sum())
    assert out["sum(d.c1)"] == int((c1[sel & partner] * 100).sum())
    out = sql_query("SELECT COUNT(*) FROM t SEMI JOIN d ON c1 = d.c0",
                    fpath, fschema, tables=tables)
    assert out["count(*)"] == int(partner.sum())


def test_sql_join_row_face(joined):
    fpath, fschema, c0, c1, dpath, dschema = joined
    tables = {"d": (dpath, dschema)}
    partner = c1 < 8
    out = sql_query("SELECT c1, d.c1 FROM t JOIN d ON c1 = d.c0",
                    fpath, fschema, tables=tables)
    order = np.argsort(out["positions"])
    np.testing.assert_array_equal(out["positions"][order],
                                  np.flatnonzero(partner))
    np.testing.assert_array_equal(out["c1"][order], c1[partner])
    np.testing.assert_array_equal(out["d.c1"][order],
                                  c1[partner] * 100)
    # LEFT rows carry the NULL indicator
    out = sql_query("SELECT c1, d.c1 FROM t LEFT JOIN d ON c1 = d.c0 "
                    "LIMIT 20", fpath, fschema, tables=tables)
    assert len(out["c1"]) == 20
    m = out["matched"]
    assert (out["d.c1"][~m] == 0).all()


def test_sql_join_rejections(joined):
    fpath, fschema, c0, c1, dpath, dschema = joined
    tables = {"d": (dpath, dschema)}
    bad = [
        ("SELECT COUNT(*) FROM t JOIN x ON c1 = x.c0", "not bound"),
        ("SELECT COUNT(*) FROM t JOIN d ON c1 = c0", "equate"),
        ("SELECT d.c1 FROM t SEMI JOIN d ON c1 = d.c0", "EXISTS"),
        ("SELECT c0, d.c1 FROM t JOIN d ON c1 = d.c0", "probe column"),
        ("SELECT c1, COUNT(*) FROM t JOIN d ON c1 = d.c0",
         "mixes aggregates"),
        ("SELECT COUNT(*) FROM t JOIN d ON c1 = d.c0 GROUP BY c0",
         "outside this subset"),
        ("SELECT AVG(c0) FROM t JOIN d ON c1 = d.c0",
         "outside this subset"),
        ("SELECT COUNT(*) FROM t LEFT d ON c1 = d.c0", "JOIN"),
    ]
    for sql, needle in bad:
        with pytest.raises(StromError) as ei:
            sql_query(sql, fpath, fschema, tables=tables)
        assert needle.lower() in str(ei.value).lower(), sql


def test_sql_or_and_parentheses(table):
    """OR with SQL precedence (AND binds tighter) and parentheses; a
    top-level AND still promotes its first index-capable leaf with the
    OR tree as the recheck residual."""
    from nvme_strom_tpu.scan.index import build_index
    from nvme_strom_tpu.scan.sql import parse_sql
    path, schema, c0, c1, c2 = table
    out = sql_query("SELECT COUNT(*) FROM t "
                    "WHERE c0 = 7 OR c0 = 9", path, schema)
    assert out["count(*)"] == int(((c0 == 7) | (c0 == 9)).sum())
    # precedence: a OR b AND c == a OR (b AND c)
    out = sql_query("SELECT COUNT(*) FROM t "
                    "WHERE c0 = 7 OR c0 = 9 AND c1 > 0", path, schema)
    assert out["count(*)"] == int(
        ((c0 == 7) | ((c0 == 9) & (c1 > 0))).sum())
    # parentheses override
    out = sql_query("SELECT COUNT(*) FROM t "
                    "WHERE (c0 = 7 OR c0 = 9) AND c1 > 0", path, schema)
    m = ((c0 == 7) | (c0 == 9)) & (c1 > 0)
    assert out["count(*)"] == int(m.sum())
    # index-capable leaf of a top-level AND promotes; OR tree rechecks
    build_index(path, schema, 1)
    q, _ = parse_sql("SELECT COUNT(*) FROM t "
                     "WHERE c1 BETWEEN 0 AND 50 AND "
                     "(c0 = 7 OR c0 = 9)", path, schema)
    plan = q.explain()
    assert plan.access_path == "index" and "RECHECKED" in plan.reason
    out = sql_query("SELECT COUNT(*) FROM t WHERE c1 BETWEEN 0 AND 50 "
                    "AND (c0 = 7 OR c0 = 9)", path, schema)
    assert out["count(*)"] == int(
        ((c1 >= 0) & (c1 <= 50) & ((c0 == 7) | (c0 == 9))).sum())


def test_sql_review_fixes(table):
    """Round-4 review findings pinned: grouped OFFSET alone slices,
    ORDER BY COUNT(cN) is rejected, and unbound qualified references
    raise EINVAL (not KeyError / silent fact-column reads)."""
    path, schema, c0, c1, c2 = table
    full = sql_query("SELECT c0 FROM t GROUP BY c0", path, schema)
    off2 = sql_query("SELECT c0 FROM t GROUP BY c0 OFFSET 2",
                     path, schema)
    np.testing.assert_array_equal(off2["c0"], full["c0"][2:])
    for sql, needle in [
        ("SELECT c0 FROM t GROUP BY c0 ORDER BY COUNT(c1)",
         "COUNT takes (*)"),
        ("SELECT d.c0 FROM t ORDER BY c0", "no JOIN"),
        ("SELECT SUM(d.c1) FROM t", "no JOIN"),
    ]:
        with pytest.raises(StromError) as ei:
            sql_query(sql, path, schema)
        assert needle.lower() in str(ei.value).lower(), sql


def test_sql_mixed_where_rides_the_index(table):
    """A mixed WHERE (eq + residual) keeps the index path through SQL:
    the first index-capable condition is the Index Cond, the rest
    recheck."""
    from nvme_strom_tpu.scan.index import build_index
    path, schema, c0, c1, c2 = table
    build_index(path, schema, 0)
    q, _ = parse_sql("SELECT COUNT(*), SUM(c1) FROM t "
                     "WHERE c0 = 7 AND c1 > 0", path, schema)
    plan = q.explain()
    assert plan.access_path == "index" and "RECHECKED" in plan.reason
    out = sql_query("SELECT COUNT(*), SUM(c1) FROM t "
                    "WHERE c0 = 7 AND c1 > 0", path, schema)
    m = (c0 == 7) & (c1 > 0)
    assert out["count(*)"] == int(m.sum())
    assert out["sum(c1)"] == int(c1[m].sum())


def test_sql_not(table):
    path, schema, c0, c1, c2 = table
    out = sql_query("SELECT COUNT(*) FROM t WHERE NOT c0 = 7",
                    path, schema)
    assert out["count(*)"] == int((c0 != 7).sum())
    out = sql_query("SELECT COUNT(*) FROM t "
                    "WHERE NOT (c0 = 7 OR c0 = 9) AND c1 > 0",
                    path, schema)
    assert out["count(*)"] == int(
        (~((c0 == 7) | (c0 == 9)) & (c1 > 0)).sum())
    out = sql_query("SELECT COUNT(*) FROM t WHERE NOT NOT c0 = 7",
                    path, schema)
    assert out["count(*)"] == int((c0 == 7).sum())


def test_sql_distinct_alias_multikey_order(table):
    path, schema, c0, c1, c2 = table
    # SELECT DISTINCT == GROUP BY the select list, keys only
    out = sql_query("SELECT DISTINCT c0 FROM t WHERE c1 > 0",
                    path, schema)
    np.testing.assert_array_equal(out["c0"], np.unique(c0[c1 > 0]))
    out = sql_query("SELECT DISTINCT c0 FROM t ORDER BY c0 DESC LIMIT 4",
                    path, schema)
    np.testing.assert_array_equal(out["c0"], np.unique(c0)[::-1][:4])
    # AS aliases relabel outputs
    out = sql_query("SELECT COUNT(*) AS n, SUM(c1) AS total FROM t",
                    path, schema)
    assert out["n"] == len(c0) and out["total"] == int(c1.sum())
    out = sql_query("SELECT c0 AS grp, COUNT(*) AS n FROM t "
                    "GROUP BY c0 ORDER BY COUNT(*) DESC LIMIT 2",
                    path, schema)
    assert len(out["grp"]) == 2 and len(out["n"]) == 2
    # multi-key ORDER BY: later columns break ties
    out = sql_query("SELECT c0, c1 FROM t ORDER BY c0, c1 LIMIT 20",
                    path, schema)
    order = np.lexsort((c1, c0))[:20]
    np.testing.assert_array_equal(out["c0"], c0[order])
    np.testing.assert_array_equal(out["c1"], c1[order])
    with pytest.raises(StromError):
        sql_query("SELECT c0 FROM t GROUP BY c0 ORDER BY c0, c1",
                  path, schema)


def test_sql_group_by_three_columns(tmp_path):
    rng = np.random.default_rng(51)
    schema = HeapSchema(n_cols=4, visibility=False)
    n = schema.tuples_per_page * 4
    cols = [rng.integers(0, k, n).astype(np.int32) for k in (3, 4, 2)]
    c3 = rng.integers(0, 50, n).astype(np.int32)
    path = str(tmp_path / "g3.heap")
    build_heap_file(path, cols + [c3], schema)
    config.set("debug_no_threshold", True)
    out = sql_query("SELECT c0, c1, c2, COUNT(*), SUM(c3) FROM t "
                    "GROUP BY c0, c1, c2 HAVING COUNT(*) > 5",
                    path, schema)
    rows = {}
    for a, b, d, v in zip(*cols, c3):
        rows.setdefault((int(a), int(b), int(d)), []).append(int(v))
    want = sorted(k for k, vs in rows.items() if len(vs) > 5)
    got = list(zip(out["c0"].tolist(), out["c1"].tolist(),
                   out["c2"].tolist()))
    assert got == want
    for i, k in enumerate(want):
        assert out["count(*)"][i] == len(rows[k])
        assert out["sum(c3)"][i] == sum(rows[k])


def test_create_table_as(tmp_path, table):
    """CREATE TABLE AS materializes SQL results as requeryable heap
    tables — projection, grouped (string keys re-encoded with a fresh
    dictionary), and scalar faces."""
    from nvme_strom_tpu.scan.sql import create_table_as
    path, schema, c0, c1, c2 = table
    # projection face
    dest = str(tmp_path / "derived.heap")
    dschema, n = create_table_as(
        dest, "SELECT c0, c1 FROM t WHERE c0 < 10", path, schema)
    sel = c0 < 10
    assert n == int(sel.sum()) and dschema.n_cols == 2
    out = sql_query("SELECT COUNT(*), SUM(c1) FROM t", dest, dschema)
    assert out["count(*)"] == n
    assert out["sum(c1)"] == int(c1[sel].sum())
    # grouped face with aliases
    dest2 = str(tmp_path / "grouped.heap")
    g2, ng = create_table_as(
        dest2, "SELECT c0 AS k, COUNT(*) AS n, AVG(c1) AS m FROM t "
               "GROUP BY c0", path, schema)
    assert ng == len(np.unique(c0)) and g2.col_dtype(2).kind == "f"
    out = sql_query("SELECT SUM(c1) FROM t", dest2, g2)
    assert out["sum(c1)"] == len(c0)   # the counts sum to the row total
    # scalar face -> 1-row table
    g3, n3 = create_table_as(str(tmp_path / "s.heap"),
                             "SELECT COUNT(*), SUM(c1) FROM t",
                             path, schema)
    assert n3 == 1
    # an existing destination is refused unless overwrite=True
    with pytest.raises(StromError) as ei:
        create_table_as(dest, "SELECT c0 FROM t", path, schema)
    assert ei.value.errno == 17
    create_table_as(dest, "SELECT c0 FROM t WHERE c0 < 5", path,
                    schema, overwrite=True)
    out2 = sql_query("SELECT COUNT(*) FROM t", dest,
                     __import__("nvme_strom_tpu.scan.heap",
                                fromlist=["HeapSchema"])
                     .HeapSchema(n_cols=1, visibility=False))
    assert out2["count(*)"] == int((c0 < 5).sum())


def test_create_table_as_strings(tmp_path):
    from nvme_strom_tpu.scan.heap import HeapSchema as HS
    from nvme_strom_tpu.scan.sql import create_table_as
    from nvme_strom_tpu.scan.strings import encode_strings, save_dict
    schema = HS(n_cols=2, visibility=False, dtypes=("uint32", "int32"))
    names = ["b", "a", "c", "a", "b", "a"] * 100
    codes, d = encode_strings(names)
    vals = np.arange(len(names), dtype=np.int32)
    src = str(tmp_path / "src.heap")
    from nvme_strom_tpu.scan.heap import build_heap_file
    build_heap_file(src, [codes[:len(vals)], vals], schema)
    save_dict(src, 0, d)
    config.set("debug_no_threshold", True)
    dest = str(tmp_path / "agg.heap")
    g, n = create_table_as(
        dest, "SELECT c0, COUNT(*) FROM t GROUP BY c0", src, schema)
    assert n == 3
    # the derived table's string column requeries through ITS dictionary
    out = sql_query("SELECT c1 FROM t WHERE c0 = 'a'", dest, g)
    assert out["c1"][0] == names[:len(vals)].count("a")


def test_create_table_as_left_join_real_nulls(joined, tmp_path):
    """Round 5 (VERDICT r4 missing #3): the LEFT row face's unpartnered
    payload materializes as a REAL nullable column, not the round-4
    int32 indicator."""
    from nvme_strom_tpu.scan.sql import create_table_as
    fpath, fschema, c0, c1, dpath, dschema = joined
    dest = str(tmp_path / "lj.heap")
    g, n = create_table_as(
        dest, "SELECT c1, d.c1 FROM t LEFT JOIN d ON c1 = d.c0",
        fpath, fschema, tables={"d": (dpath, dschema)})
    assert n == len(c1) and g.n_cols == 2   # c1, d.c1 — no indicator
    assert g.nullable == (False, True)
    out = sql_query("SELECT COUNT(*), COUNT(c1) FROM t", dest, g)
    assert out["count(*)"] == len(c1)
    assert out["count(c1)"] == int((c1 < 8).sum())   # partnered rows


def test_sql_join_float_payload(joined, tmp_path):
    """SUM(d.cK) over a float dimension column stays float through the
    SQL facade."""
    fpath, fschema, c0, c1, dpath, dschema = joined
    d2schema = HeapSchema(n_cols=2, visibility=False,
                          dtypes=("int32", "float32"))
    keys = np.arange(0, 8, dtype=np.int32)
    fv = (keys * 0.5).astype(np.float32)
    d2 = str(tmp_path / "fdim.heap")
    build_heap_file(d2, [keys, fv], d2schema)
    out = sql_query("SELECT COUNT(*), SUM(d.c1) FROM t "
                    "JOIN d ON c1 = d.c0", fpath, fschema,
                    tables={"d": (d2, d2schema)})
    partner = c1 < 8
    assert isinstance(out["sum(d.c1)"], float)
    np.testing.assert_allclose(out["sum(d.c1)"],
                               float(fv[c1[partner]].sum()), rtol=1e-4)


# ---------------------------------------------------------------------------
# Grouped expression aggregates: TPC-H Q1's shape
# ---------------------------------------------------------------------------

@pytest.fixture()
def x64():
    import jax
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)


#: TPC-H dates as int32 days since 1970-01-01 (1992-01-02, 1995-06-17,
#: 1998-12-01)
SHIP_LO, CURRENT, DATE_END = 8036, 9298, 10561


def _lineitem_q1(tmp_path, n=6000, seed=26):
    """The seven LINEITEM columns Q1 reads at the widths TPC-H asks:
    quantity, price, discount and tax as int64 hundredths, the two flags
    as their byte in an int32, the ship date in int32 days; flag rules
    of dbgen (sec. 4.2.3)."""
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 51, n).astype(np.int64)
    price = qty * rng.integers(90_000, 209_901, n)
    disc = rng.integers(0, 11, n).astype(np.int64)
    tax = rng.integers(0, 9, n).astype(np.int64)
    ship = rng.integers(SHIP_LO, DATE_END, n).astype(np.int32)
    receipt = ship + rng.integers(1, 31, n)
    rf = np.where(receipt <= CURRENT,
                  np.where(rng.integers(0, 2, n) == 0, ord("A"), ord("R")),
                  ord("N")).astype(np.int32)
    ls = np.where(ship > CURRENT, ord("O"), ord("F")).astype(np.int32)
    cols = [qty * 100, price, disc, tax, rf, ls, ship]
    schema = HeapSchema(n_cols=7, visibility=False,
                        dtypes=("int64",) * 4 + ("int32",) * 3)
    path = str(tmp_path / "lineitem.heap")
    build_heap_file(path, cols, schema)
    return path, schema, cols


def _q1_sql(cutoff: int) -> str:
    return ("SELECT c4, c5, SUM(c0) AS sum_qty, SUM(c1) AS sum_base_price, "
            "SUM(c1 * (100 - c2)) AS sum_disc_price, "
            "SUM(c1 * (100 - c2) * (100 + c3)) AS sum_charge, "
            "AVG(c0) AS avg_qty, AVG(c1) AS avg_price, AVG(c2) AS avg_disc, "
            "COUNT(*) AS count_order FROM lineitem "
            f"WHERE c6 <= {cutoff} GROUP BY c4, c5 ORDER BY c4, c5")


def _q1_numpy(cols, cutoff: int) -> dict:
    """Plain Q1: a loop over the groups in (returnflag, linestatus)
    order, exact int64 sums, AVG as the float64 quotient."""
    qty, price, disc, tax, rf, ls, ship = cols
    keep = ship <= cutoff
    out = {k: [] for k in ("c4", "c5", "sum_qty", "sum_base_price",
                           "sum_disc_price", "sum_charge", "count_order")}
    for a, b in sorted(set(zip(rf[keep].tolist(), ls[keep].tolist()))):
        m = keep & (rf == a) & (ls == b)
        dp = price[m] * (100 - disc[m])
        for k, v in (("c4", a), ("c5", b), ("sum_qty", qty[m].sum()),
                     ("sum_base_price", price[m].sum()),
                     ("sum_disc_price", dp.sum()),
                     ("sum_charge", (dp * (100 + tax[m])).sum()),
                     ("count_order", m.sum())):
            out[k].append(int(v))
    out = {k: np.asarray(v, np.int32 if k in ("c4", "c5") else np.int64)
           for k, v in out.items()}
    n = out["count_order"]
    for avg, col in (("avg_qty", qty), ("avg_price", price),
                     ("avg_disc", disc)):
        s = [int(col[keep & (rf == a) & (ls == b)].sum())
             for a, b in zip(out["c4"], out["c5"])]
        out[avg] = np.asarray(s, np.int64) / n
    return out


@pytest.mark.parametrize("delta", [60, 90, 120])
def test_sql_tpch_q1_matches_numpy(x64, tmp_path, delta):
    """TPC-H Q1 (sec. 2.4.1) through sql_query equals the plain numpy Q1
    exactly: int64 sums and counts, AVG as float64 quotients, the groups
    in ORDER BY order."""
    path, schema, cols = _lineitem_q1(tmp_path)
    got = sql_query(_q1_sql(DATE_END - delta), path, schema)
    want = _q1_numpy(cols, DATE_END - delta)
    assert set(got) == set(want)
    assert len(want["c4"]) == 4           # A/F, N/F, N/O, R/F
    for k, v in want.items():
        assert got[k].dtype == v.dtype, k
        np.testing.assert_array_equal(got[k], v, err_msg=k)
    assert want["sum_charge"].max() > 1 << 40


def test_sql_tpch_q1_explain_names_kernel_and_discovery(x64, tmp_path):
    from nvme_strom_tpu.scan.index import build_index
    path, schema, _cols = _lineitem_q1(tmp_path, n=2000)
    q, _ = parse_sql(_q1_sql(10000), path, schema)
    plan = q.explain()
    assert (plan.operator, plan.kernel) == ("group_by", "xla")
    assert "group_efn sums 5 argument(s)" in plan.reason
    assert "keys grow inside the aggregation scan" in plan.reason
    build_index(path, schema, (4, 5))
    q, _ = parse_sql(_q1_sql(10000), path, schema)
    assert "from the fresh sidecar lineitem.heap.idx4_5" in \
        q.explain().reason


def test_sql_grouped_expr_aggregates_at_int64(x64, tmp_path):
    """An int64 column keeps the whole expression at int64 (c1 * c1 is
    past 2^31 a row), and per-group sums pass 2^31 and 2^32; an int32-
    only expression sums in int64 accumulators."""
    rng = np.random.default_rng(7)
    schema = HeapSchema(n_cols=3, visibility=False,
                        dtypes=("int32", "int32", "int64"))
    n = schema.tuples_per_page * 64
    c0 = rng.integers(0, 4, n).astype(np.int32)
    c1 = rng.integers(0, 1_000_000, n).astype(np.int32)
    c2 = rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64)
    path = str(tmp_path / "w.heap")
    build_heap_file(path, [c0, c1, c2], schema)
    got = sql_query("SELECT c0, SUM(c1 * c1 + c2) AS s, AVG(c1 * c1 + c2) "
                    "AS a, SUM(c1 * 7) AS s7, SUM(c1) AS s1 FROM t "
                    "WHERE c1 > 1000 GROUP BY c0 ORDER BY c0 DESC",
                    path, schema)
    keys = np.arange(3, -1, -1)
    np.testing.assert_array_equal(got["c0"], keys)
    w1, w2 = c1.astype(np.int64), c2
    for i, k in enumerate(keys):
        m = (c0 == k) & (c1 > 1000)
        s = int((w1[m] * w1[m] + w2[m]).sum())
        assert got["s"][i] == s
        assert got["a"][i] == np.int64(s) / np.int64(m.sum())
        assert got["s7"][i] == int(w1[m].sum()) * 7 > 1 << 32
        assert got["s1"][i] == int(w1[m].sum()) > 1 << 31


def test_sql_grouped_multikey_order_by_with_ties(table):
    """ORDER BY several group columns sorts the group rows
    lexicographically: later keys break the ties of earlier ones.  A
    trailing DESC refuses: SQL binds it to the last key alone."""
    path, schema, c0, c1, c2 = table
    out = sql_query("SELECT c0, c1, COUNT(*) AS n FROM t WHERE c0 < 6 "
                    "AND c1 BETWEEN -2 AND 2 GROUP BY c0, c1 "
                    "ORDER BY c1, c0", path, schema)
    m = (c0 < 6) & (c1 >= -2) & (c1 <= 2)
    pairs = sorted(set(zip(c1[m].tolist(), c0[m].tolist())))
    assert list(zip(out["c1"].tolist(), out["c0"].tolist())) == pairs
    assert out["n"].tolist() == [int((m & (c1 == b) & (c0 == a)).sum())
                                 for b, a in pairs]
    assert len({b for b, _ in pairs}) < len(pairs)    # ties were broken
    out = sql_query("SELECT c1, c0 FROM t WHERE c0 < 6 AND c1 BETWEEN -2 "
                    "AND 2 GROUP BY c0, c1 ORDER BY c1, c0 ASC",
                    path, schema)
    assert list(zip(out["c1"].tolist(), out["c0"].tolist())) == pairs
    with pytest.raises(StromError) as ei:
        sql_query("SELECT c1, c0 FROM t GROUP BY c0, c1 ORDER BY c1, c0 "
                  "DESC", path, schema)
    assert ei.value.errno == 22 and "DESC" in str(ei.value)
    with pytest.raises(StromError) as ei:
        sql_query("SELECT c0, COUNT(*) FROM t GROUP BY c0 ORDER BY c0, c2",
                  path, schema)
    assert ei.value.errno == 22 and "c2" in str(ei.value)


def test_sql_grouped_expr_refusals(x64, tmp_path):
    """What stays outside the subset refuses with EINVAL: MIN/MAX beside
    grouped expression aggregates (in the list, HAVING or ORDER BY), a
    nullable column in one, a JOIN, integer division, worker
    processes."""
    path, schema, _cols = _lineitem_q1(tmp_path, n=1500)
    base = "SELECT c4, SUM(c1 * (100 - c2)) FROM t"
    for stmt, why in (
            ("SELECT c4, SUM(c1 * 2), MAX(c0) FROM t GROUP BY c4", "MAX"),
            (f"{base} GROUP BY c4 HAVING MIN(c0) > 0", "MIN"),
            (f"{base} GROUP BY c4 ORDER BY MAX(c0)", "MAX"),
            ("SELECT c4, SUM(c1 / c2) FROM t GROUP BY c4", "division"),
            (f"{base} JOIN d ON c4 = d.c0 GROUP BY c4", "JOIN")):
        with pytest.raises(StromError) as ei:
            sql_query(stmt, path, schema, tables={"d": (path, schema)})
        assert ei.value.errno == 22 and why in str(ei.value), stmt
    with pytest.raises(StromError) as ei:
        sql_query(f"{base} GROUP BY c4", path, schema, workers=2)
    assert ei.value.errno == 22 and "worker" in str(ei.value)
    ns = HeapSchema(n_cols=2, visibility=False, dtypes=("int32", "int64"),
                    nullable=(False, True))
    npath = str(tmp_path / "null.heap")
    build_heap_file(npath, [np.zeros(10, np.int32),
                            np.arange(10, dtype=np.int64)], ns,
                    nulls={1: np.arange(10) % 3 == 0})
    with pytest.raises(StromError) as ei:
        sql_query("SELECT c0, SUM(c1 * 2) FROM t GROUP BY c0", npath, ns)
    assert ei.value.errno == 22 and "nullable" in str(ei.value)
