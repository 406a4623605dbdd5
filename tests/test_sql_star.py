"""Star statements (>= 2 JOINs in one statement) and arithmetic
expressions through the SQL facade — round 5 surface breadth.

Reference parity: the reference's scan sits under the full PostgreSQL
executor, which composes any joins/expressions over the handed-up
tuples (`pgsql/nvme_strom.c:941-979`); these tests pin the star +
expression core of that composition against numpy oracles.
"""

import os

import numpy as np
import pytest

from nvme_strom_tpu.api import StromError
from nvme_strom_tpu.config import config
from nvme_strom_tpu.scan.heap import HeapSchema, build_heap_file
from nvme_strom_tpu.scan.query import Query
from nvme_strom_tpu.scan.sql import parse_sql, sql_query


@pytest.fixture(scope="module")
def star(tmp_path_factory):
    d = tmp_path_factory.mktemp("sqlstar")
    rng = np.random.default_rng(1)
    n = 30_000
    c0 = rng.integers(0, 120, n).astype(np.int32)   # dim1 key (some miss)
    c1 = rng.integers(0, 80, n).astype(np.int32)    # dim2 key (some miss)
    c2 = rng.integers(-50, 50, n).astype(np.int32)
    c3 = rng.normal(size=n).astype(np.float32)
    schema = HeapSchema(n_cols=4, dtypes=("int32", "int32", "int32",
                                          "float32"))
    fact = str(d / "fact.heap")
    build_heap_file(fact, [c0, c1, c2, c3], schema)
    d1k = np.arange(100, dtype=np.int32)
    d1v = rng.integers(0, 1000, 100).astype(np.int32)
    ds1 = HeapSchema(n_cols=2)
    dim1 = str(d / "d1.heap")
    build_heap_file(dim1, [d1k, d1v], ds1)
    d2k = np.arange(60, dtype=np.int32)
    d2v = rng.normal(size=60).astype(np.float32)
    ds2 = HeapSchema(n_cols=2, dtypes=("int32", "float32"))
    dim2 = str(d / "d2.heap")
    build_heap_file(dim2, [d2k, d2v], ds2)
    tables = {"d1": (dim1, ds1), "d2": (dim2, ds2)}
    return fact, schema, tables, c0, c1, c2, c3, d1k, d1v, d2k, d2v


def test_expr_scalar_aggregates(star):
    fact, schema, tables, c0, c1, c2, c3, *_ = star
    res = sql_query("SELECT COUNT(*) AS n, SUM(c2 * c2) AS s2, "
                    "AVG(c3 * 2.0 + 1.0) AS a FROM t "
                    "WHERE c2 > c0 - 60", fact, schema)
    sel = c2 > (c0 - 60)
    assert res["n"] == int(sel.sum())
    assert res["s2"] == int(np.sum(np.int32(c2[sel]) * np.int32(c2[sel])))
    a = float(np.mean(c3[sel] * np.float32(2.0) + np.float32(1.0)))
    assert res["a"] == pytest.approx(a, rel=2e-3)


def test_expr_column_vs_column_where(star):
    fact, schema, tables, c0, c1, c2, c3, *_ = star
    res = sql_query("SELECT COUNT(*) AS n FROM t "
                    "WHERE c2 > c1 + 5 AND c0 < 100", fact, schema)
    assert res["n"] == int(((c2 > c1 + 5) & (c0 < 100)).sum())


def test_expr_int_division_refused(star):
    fact, schema, *_ = star
    with pytest.raises(StromError) as ei:
        sql_query("SELECT SUM(c2 / c1) FROM t", fact, schema)
    assert ei.value.errno == 22 and "division" in str(ei.value)


def test_expr_float_division_allowed(star):
    fact, schema, tables, c0, c1, c2, c3, *_ = star
    res = sql_query("SELECT SUM(c3 / 2.0) AS h FROM t WHERE c2 = 0",
                    fact, schema)
    m = c2 == 0
    assert res["h"] == pytest.approx(
        float(np.sum(c3[m] / np.float32(2.0))), rel=1e-3)


def test_expr_under_group_by_refused(star):
    """Grouped expression aggregates run (TPC-H Q1's shape); beside
    MIN/MAX, or with a JOIN, they stay outside the subset."""
    fact, schema, tables, c0, c1, c2, *_ = star
    res = sql_query("SELECT c0, SUM(c2 * c2) AS s FROM t GROUP BY c0 "
                    "ORDER BY c0", fact, schema)
    keys = np.unique(c0)
    np.testing.assert_array_equal(res["c0"], keys)
    assert res["s"].tolist() == [int(np.sum(c2[c0 == k] * c2[c0 == k]))
                                 for k in keys]
    for stmt in ("SELECT c0, SUM(c2 * c2), MIN(c1) FROM t GROUP BY c0",
                 "SELECT c2, SUM(c2 * 2) FROM t JOIN d1 ON c0 = d1.c0 "
                 "JOIN d2 ON c1 = d2.c0 GROUP BY c2"):
        with pytest.raises(StromError) as ei:
            sql_query(stmt, fact, schema, tables=tables)
        assert ei.value.errno == 22, stmt


def test_star_aggregate_two_dims(star):
    fact, schema, tables, c0, c1, c2, c3, d1k, d1v, d2k, d2v = star
    res = sql_query(
        "SELECT COUNT(*) AS n, SUM(c2) AS s, SUM(d1.c1) AS p1, "
        "AVG(d2.c1) AS p2 FROM t JOIN d1 ON c0 = d1.c0 "
        "JOIN d2 ON c1 = d2.c0 WHERE c2 >= 0",
        fact, schema, tables=tables)
    m = (c2 >= 0) & np.isin(c0, d1k) & np.isin(c1, d2k)
    assert res["n"] == int(m.sum())
    assert res["s"] == int(c2[m].sum())
    assert res["p1"] == int(d1v[c0[m]].sum())
    p2 = float(np.sum(d2v[c1[m]].astype(np.float64))) / m.sum()
    assert res["p2"] == pytest.approx(p2, rel=1e-3)


def test_star_left_and_anti_faces(star):
    fact, schema, tables, c0, c1, c2, c3, d1k, d1v, d2k, d2v = star
    res = sql_query(
        "SELECT COUNT(*) AS n, SUM(d1.c1) AS p1 FROM t "
        "LEFT JOIN d1 ON c0 = d1.c0 ANTI JOIN d2 ON c1 = d2.c0",
        fact, schema, tables=tables)
    m = ~np.isin(c1, d2k)
    assert res["n"] == int(m.sum())
    hit = m & np.isin(c0, d1k)
    assert res["p1"] == int(d1v[c0[hit]].sum())


def test_star_row_face_with_limit(star):
    fact, schema, tables, c0, c1, c2, c3, d1k, d1v, d2k, d2v = star
    res = sql_query(
        "SELECT c2, d1.c1, d2.c1 FROM t JOIN d1 ON c0 = d1.c0 "
        "LEFT JOIN d2 ON c1 = d2.c0 WHERE c2 > 45 LIMIT 50",
        fact, schema, tables=tables)
    m = (c2 > 45) & np.isin(c0, d1k)
    pos = res["positions"]
    assert len(pos) == min(50, int(m.sum()))
    assert all(m[p] for p in pos)
    assert (res["c2"] == c2[pos]).all()
    assert (res["d1.c1"] == d1v[c0[pos]]).all()
    m2 = np.isin(c1[pos], d2k)
    assert (res["matched_d2"] == m2).all()
    assert np.allclose(res["d2.c1"],
                       np.where(m2, d2v[np.clip(c1[pos], 0, 59)], 0))


def test_star_expr_aggregate(star):
    fact, schema, tables, c0, c1, c2, c3, d1k, d1v, d2k, d2v = star
    res = sql_query("SELECT SUM(c2 * 2) AS s FROM t "
                    "JOIN d1 ON c0 = d1.c0 JOIN d2 ON c1 = d2.c0",
                    fact, schema, tables=tables)
    m = np.isin(c0, d1k) & np.isin(c1, d2k)
    assert res["s"] == int((c2[m] * 2).sum())


def test_star_explain_names_the_plan(star):
    fact, schema, tables, *_ = star
    q, _ = parse_sql("SELECT COUNT(*) FROM t JOIN d1 ON c0 = d1.c0 "
                     "JOIN d2 ON c1 = d2.c0", fact, schema,
                     tables=tables)
    plan = q.explain()
    assert plan.operator == "star"
    assert "2 broadcast dimensions" in plan.reason


def test_star_refusals(star):
    fact, schema, tables, *_ = star
    cases = [
        # GROUP BY with star
        "SELECT c2, COUNT(*) FROM t JOIN d1 ON c0 = d1.c0 "
        "JOIN d2 ON c1 = d2.c0 GROUP BY c2",
        # semi exposing a column
        "SELECT d1.c1 FROM t SEMI JOIN d1 ON c0 = d1.c0 "
        "JOIN d2 ON c1 = d2.c0",
        # same table twice
        "SELECT COUNT(*) FROM t JOIN d1 ON c0 = d1.c0 "
        "JOIN d1 ON c1 = d1.c0",
        # two payload columns from one dim
        "SELECT d1.c0, d1.c1 FROM t JOIN d1 ON c0 = d1.c0 "
        "JOIN d2 ON c1 = d2.c0",
    ]
    for stmt in cases:
        with pytest.raises(StromError) as ei:
            sql_query(stmt, fact, schema, tables=tables)
        assert ei.value.errno == 22, stmt


def test_star_under_workers(star):
    fact, schema, tables, c0, c1, c2, c3, d1k, d1v, d2k, d2v = star
    res = sql_query(
        "SELECT COUNT(*) AS n, SUM(d1.c1) AS p1, SUM(c2 * c2) AS sq "
        "FROM t JOIN d1 ON c0 = d1.c0 JOIN d2 ON c1 = d2.c0",
        fact, schema, tables=tables, workers=2)
    m = np.isin(c0, d1k) & np.isin(c1, d2k)
    assert res["n"] == int(m.sum())
    assert res["p1"] == int(d1v[c0[m]].sum())
    assert res["sq"] == int(np.sum(np.int32(c2[m]) * np.int32(c2[m])))


def test_expr_aggregate_under_workers(star):
    fact, schema, tables, c0, c1, c2, c3, *_ = star
    res = sql_query("SELECT SUM(c2 * c1) AS s FROM t WHERE c2 > c1",
                    fact, schema, workers=2)
    m = c2 > c1
    assert res["s"] == int(np.sum(np.int32(c2[m]) * np.int32(c1[m])))


def test_star_query_builder_direct(star, tmp_path):
    """Query.star_join direct API: mixed faces + the broadcast cap."""
    fact, schema, tables, c0, c1, c2, c3, d1k, d1v, d2k, d2v = star
    dim1, ds1 = tables["d1"]
    dim2, ds2 = tables["d2"]
    specs = [dict(probe_col=0, table=dim1, schema=ds1, key_col=0,
                  value_col=1, how="inner"),
             dict(probe_col=1, table=dim2, schema=ds2, key_col=0,
                  value_col=None, how="semi")]
    out = Query(fact, schema).star_join(specs).run()
    m = np.isin(c0, d1k) & np.isin(c1, d2k)
    assert int(out["count"]) == int(m.sum())
    assert int(out["pay_sums"][0]) == int(d1v[c0[m]].sum())
    # oversized dim refuses with a clear EINVAL
    config.set("join_broadcast_max", 1024)
    with pytest.raises(StromError) as ei:
        Query(fact, schema).star_join(specs)
    assert ei.value.errno == 22
    assert "join_broadcast_max" in str(ei.value)


@pytest.fixture(scope="module")
def nullable_fact(tmp_path_factory, star):
    """A fact table whose aggregated column is 40% NULL."""
    from nvme_strom_tpu.scan.heap import build_heap_file as _bhf
    d = tmp_path_factory.mktemp("sqlstar_null")
    rng = np.random.default_rng(7)
    n = 20_000
    c0 = rng.integers(0, 120, n).astype(np.int32)
    c1 = rng.integers(0, 80, n).astype(np.int32)
    c2 = rng.integers(1, 100, n).astype(np.int32)
    nn = rng.random(n) < 0.4
    schema = HeapSchema(n_cols=3, nullable=(False, False, True))
    fact = str(d / "nf.heap")
    _bhf(fact, [c0, c1, c2], schema, nulls={2: nn})
    return fact, schema, c0, c1, c2, nn


def test_star_avg_nullable_fact(star, nullable_fact):
    """AVG over a nullable fact column divides by the NON-NULL emitted
    count, not the emitted row count — dividing by total rows returned
    ~0.6x the PostgreSQL answer on a 40%-NULL column."""
    _f, _s, tables, *_rest, d1k, d1v, d2k, d2v = star
    fact, schema, c0, c1, c2, nn = nullable_fact
    res = sql_query(
        "SELECT COUNT(*) AS n, SUM(c2) AS s, AVG(c2) AS a FROM t "
        "JOIN d1 ON c0 = d1.c0 JOIN d2 ON c1 = d2.c0",
        fact, schema, tables=tables)
    m = np.isin(c0, d1k) & np.isin(c1, d2k)
    hit = m & ~nn
    assert res["n"] == int(m.sum())
    assert res["s"] == int(c2[hit].sum())          # sums already skip NULLs
    assert res["a"] == pytest.approx(c2[hit].mean())


def test_star_avg_nullable_fact_under_workers(star, nullable_fact):
    """nncounts fold additively across worker partials."""
    _f, _s, tables, *_rest, d1k, d1v, d2k, d2v = star
    fact, schema, c0, c1, c2, nn = nullable_fact
    res = sql_query(
        "SELECT AVG(c2) AS a FROM t JOIN d1 ON c0 = d1.c0 "
        "JOIN d2 ON c1 = d2.c0", fact, schema, tables=tables, workers=2)
    hit = np.isin(c0, d1k) & np.isin(c1, d2k) & ~nn
    assert res["a"] == pytest.approx(c2[hit].mean())
