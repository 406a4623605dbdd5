"""SQL front-end: a parsed SELECT subset over heap tables.

The reference ships as a PostgreSQL extension — SQL *is* its user
interface (`pgsql/nvme_strom.c:941-979` hands tuples to the SQL
executor).  This module gives the TPU framework the same face for the
scan tier it implements: a hand-rolled tokenizer + recursive-descent
parser (no dependencies) maps a SELECT statement onto the
:class:`.query.Query` builder, so every access path the planner knows
(direct / vfs / index sidecars), both kernels, and the mesh mode are
reachable from a SQL string.

Supported subset (one fact table, one terminal — the Query contract):

    SELECT select_list FROM <name>
      [[INNER|LEFT|SEMI|ANTI] JOIN <dim> ON cN = <dim>.cM]...
      [WHERE cond [AND cond]...]
      [GROUP BY cN[, cM]]
      [HAVING agg cmp literal [AND ...]]
      [ORDER BY cN[, cM]... [ASC|DESC]]
      [LIMIT n [OFFSET m]]

JOIN binds a dimension table supplied via ``tables={"dim": (path,
schema)}`` (on-disk heap; the engine streams it in bounded passes when
it exceeds ``join_broadcast_max``) and serves both faces: aggregates —
``COUNT(*)``, ``SUM(cN)`` over fact columns, ``SUM(dim.cK)`` over the
matched build payload — or, with plain columns in the SELECT list, the
materialized rows.  TWO OR MORE JOIN clauses form a STAR statement
(round 5): every dimension probes in the same fused scan kernel
(broadcast-sized dims only; aggregates gain ``AVG(dim.cK)`` and
``SUM(expr)``, the row face serves any fact columns + one payload
column per dimension, LEFT dims add a ``matched_<dim>`` indicator).

    select_list := [DISTINCT] '*' | item [AS name] (',' item [AS name])*
    item  := cN | COUNT(*) | COUNT(DISTINCT cN)
           | SUM(cN|expr) | AVG(cN|expr) | MIN(cN) | MAX(cN)
    -- SELECT DISTINCT cols == GROUP BY the select list (keys only);
    -- ORDER BY takes cN[, cM]... (later keys break ties); under GROUP BY
    -- every key is a group column, and several keys take no DESC
    where := term (OR term)* ; term := factor (AND factor)*
    factor := NOT factor | '(' where ')' | cond   -- SQL precedence
    cond  := expr cmp expr
           | cN BETWEEN lit AND lit | cN IN (lit[, lit]...)
    expr  := cN | number | '(' expr ')' | -expr
           | expr (+|-|*|/) expr        -- usual precedence
    cmp   := = | == | != | <> | < | <= | > | >=
    literal := number | 'string'   (strings need a dictionary sidecar)

Expression semantics are EXACT, never approximate: int arithmetic runs
at int32 (the storage width — wraparound is the storage semantics), or
at int64 throughout when the expression reads an int64 column (its
int32 columns and literals widen first, so no int32 intermediate
wraps), float math at float32, mixed operands promote to float32; int/int
division is EINVAL (PostgreSQL truncates — returning the float answer
would be silent drift), as are uint32 operands and string columns in
arithmetic.  One DOCUMENTED divergence: float division follows IEEE 754
(``x / 0.0`` is ±inf, ``0.0 / 0.0`` is NaN, and NaN comparisons are
false) where PostgreSQL raises ``division_by_zero`` — a per-row raise
cannot live inside the fused kernel, and a silent wrong answer is
worse than the standard float answer.  Plain ``cN cmp literal`` leaves keep their structured
form, so index promotion and string translation are unchanged;
expression aggregates fuse into the scan kernel: scalar ones into
``Query.aggregate_exprs``, grouped ones (SUM/AVG of expressions beside
COUNT(*), SUM and AVG of columns, as TPC-H Q1 asks) into
``Query.group_by_cols(agg_exprs=...)``.  Under GROUP BY they do not
combine with MIN/MAX, nullable columns, a JOIN or worker processes.

Columns are named ``c0..cN-1`` (the CLI convention).  The mapping is
exact, never approximate: a statement outside the subset raises EINVAL
with a message naming the unsupported construct — silent semantic
drift from real SQL is the one unforgivable failure mode of a facade.

Mapping (each SQL shape -> the Query terminal that serves it):

* plain columns                  -> ``select(cols)`` (LIMIT/OFFSET ride
  the early DMA cut-off)
* COUNT(*) / SUM / AVG, no GROUP -> ``aggregate(cols=...)``
* sole MIN(c) / MAX(c), no GROUP -> ``top_k(c, 1)`` (index-served when
  a sidecar is fresh)
* sole COUNT(DISTINCT c)         -> ``count_distinct(c)``
* GROUP BY c[, c2]               -> ``group_by_cols`` (value-keyed:
  keys found during the aggregation scan, or from a fresh sidecar;
  HAVING composes; SUM/AVG of expressions ride ``agg_exprs``)
* ORDER BY c[, c2] [DESC]        -> ``order_by`` (sidecar-served when
  fresh; other selected columns fetched by position); ORDER BY an
  aggregate + LIMIT on grouped results = top-N groups
* SELECT DISTINCT cols           -> ``group_by_cols`` keys only
* AS name                        -> output relabeling (after string
  decode)
* :func:`create_table_as`        -> materialize any result as a new
  requeryable heap table (CLI ``--sql-create``)
* WHERE: the first index-capable LEAF of a top-level AND becomes a
  STRUCTURED filter (``where_eq`` / ``where_range`` / ``where_in`` —
  the planner can ride a sidecar); the rest of the tree — remaining
  conjuncts, OR subtrees — composes as the residual predicate the
  index path RECHECKS (Index Cond + Filter).
"""

from __future__ import annotations

import os
import re
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..api import StromError
from .query import Query

__all__ = ["parse_sql", "sql_query", "create_table_as"]

_TOKEN = re.compile(r"""
    \s*(?:
      (?P<str>'[^']*')
    | (?P<num>\d+\.\d+(?:[eE][+-]?\d+)?|\d+)
    | (?P<name>[A-Za-z_][A-Za-z_0-9]*)
    | (?P<op><=|>=|!=|<>|==|=|<|>|\(|\)|,|\*|\.|\+|-|/|%)
    )""", re.VERBOSE)

_AGGS = ("count", "sum", "avg", "min", "max")
_CMPS = ("=", "==", "!=", "<>", "<", "<=", ">", ">=")


def _tokenize(sql: str) -> List[Tuple[str, str]]:
    out, pos = [], 0
    while pos < len(sql):
        m = _TOKEN.match(sql, pos)
        if m is None:
            if sql[pos:].strip() == "":
                break
            raise StromError(22, f"SQL: cannot tokenize at "
                                 f"{sql[pos:pos + 20]!r}")
        pos = m.end()
        if m.group("str") is not None:
            out.append(("str", m.group("str")[1:-1]))
        elif m.group("num") is not None:
            out.append(("num", m.group("num")))
        elif m.group("name") is not None:
            out.append(("name", m.group("name")))
        else:
            out.append(("op", m.group("op")))
    return out


class _P:
    """Token cursor with the small helpers a recursive descent needs."""

    def __init__(self, toks: List[Tuple[str, str]]):
        self.toks = toks
        self.i = 0

    def peek(self) -> Optional[Tuple[str, str]]:
        return self.toks[self.i] if self.i < len(self.toks) else None

    def next(self) -> Tuple[str, str]:
        t = self.peek()
        if t is None:
            raise StromError(22, "SQL: unexpected end of statement")
        self.i += 1
        return t

    def kw(self, word: str) -> bool:
        """Consume *word* (case-insensitive keyword) if next."""
        t = self.peek()
        if t and t[0] == "name" and t[1].lower() == word:
            self.i += 1
            return True
        return False

    def expect_op(self, op: str) -> None:
        t = self.next()
        if t != ("op", op):
            raise StromError(22, f"SQL: expected {op!r}, got {t[1]!r}")

    def expect_kw(self, word: str) -> None:
        if not self.kw(word):
            t = self.peek()
            raise StromError(22, f"SQL: expected {word.upper()}, got "
                                 f"{t[1] if t else 'end'!r}")


def _col(tok: Tuple[str, str], n_cols: int) -> int:
    kind, v = tok
    m = re.fullmatch(r"[cC](\d+)", v) if kind == "name" else None
    if not m:
        raise StromError(22, f"SQL: expected a column (c0..c{n_cols - 1})"
                             f", got {v!r}")
    c = int(m.group(1))
    if not 0 <= c < n_cols:
        raise StromError(22, f"SQL: column c{c} out of range "
                             f"(table has {n_cols})")
    return c


class _Str(str):
    """Marker for a parsed SQL string literal ('...') — translated to
    dictionary codes before the numeric machinery sees it."""


def _lit(tok: Tuple[str, str]):
    kind, v = tok
    if kind == "str":
        return _Str(v)
    if kind != "num":
        raise StromError(22, f"SQL: expected a literal, got {v!r}")
    return float(v) if ("." in v or "e" in v or "E" in v) else int(v)


def _plit(p: "_P"):
    """A possibly-negated literal (the tokenizer emits '-' as an
    operator so expressions can subtract)."""
    if p.peek() == ("op", "-"):
        p.next()
        v = _lit(p.next())
        if isinstance(v, _Str):
            raise StromError(22, "SQL: cannot negate a string literal")
        return -v
    return _lit(p.next())


# ---------------------------------------------------------------------------
# Arithmetic expressions (round 5): cN, literals, + - * /, parentheses
# ---------------------------------------------------------------------------
#
# Trees are picklable tuples — ("col", c) | ("lit", v) | ("neg", e) |
# ("bin", op, l, r) — so worker processes can rebuild them, and the SAME
# evaluator serves WHERE leaves and aggregate arguments.  Semantics are
# exact, never approximate: int arithmetic runs at int32 (the storage
# width — wraparound is the documented storage semantics, like the
# kernels' sums), float math at float32, mixed operands promote to
# float32, and integer/integer division is EINVAL (PostgreSQL truncates
# int division; silently returning the float answer would be semantic
# drift, so this subset only serves `/` when a float operand makes the
# answer SQL's answer).

_EXPR_DTS = (np.dtype(np.int32), np.dtype(np.float32))


def _parse_expr(p: "_P", n_cols: int):
    """expr := term (('+'|'-') term)* ; term := factor (('*'|'/'|'%')
    factor)* ; factor := ['-'] atom ; atom := cN | number | '(' expr ')'
    """
    def atom():
        t = p.peek()
        if t == ("op", "("):
            p.next()
            e = add()
            p.expect_op(")")
            return e
        if t is not None and t[0] in ("num", "str"):
            return ("lit", _lit(p.next()))
        return ("col", _col(p.next(), n_cols))

    def factor():
        if p.peek() == ("op", "-"):
            p.next()
            f = factor()
            if f[0] == "lit" and not isinstance(f[1], _Str):
                return ("lit", -f[1])
            return ("neg", f)
        return atom()

    def term():
        e = factor()
        while p.peek() in (("op", "*"), ("op", "/"), ("op", "%")):
            op = p.next()[1]
            if op == "%":
                raise StromError(22, "SQL: the modulo operator is "
                                     "outside this subset")
            e = ("bin", op, e, factor())
        return e

    def add():
        e = term()
        while p.peek() in (("op", "+"), ("op", "-")):
            op = p.next()[1]
            e = ("bin", op, e, term())
        return e

    return add()


def _expr_info(e, schema) -> Tuple[np.dtype, set]:
    """(result dtype, referenced columns) of an expression tree, raising
    EINVAL for shapes outside the subset (strings in arithmetic, uint32
    operands, int/int division, out-of-int32 literals)."""
    k = e[0]
    if k == "col":
        dt = schema.col_dtype(e[1])
        if dt not in _EXPR_DTS and dt != np.dtype(np.uint32) \
                and dt.kind not in "iuf":
            raise StromError(22, f"SQL: c{e[1]} ({dt}) in an expression")
        return dt, {e[1]}
    if k == "lit":
        v = e[1]
        if isinstance(v, _Str):
            raise StromError(22, "SQL: string literals cannot appear in "
                                 "arithmetic")
        if isinstance(v, int):
            if not -(1 << 31) <= v < (1 << 31):
                raise StromError(22, f"SQL: integer literal {v} outside "
                                     f"int32 in an expression")
            return np.dtype(np.int32), set()
        return np.dtype(np.float32), set()
    if k == "neg":
        dt, cs = _expr_info(e[1], schema)
        if dt == np.dtype(np.uint32):
            raise StromError(22, "SQL: negating a uint32 column is "
                                 "outside this subset")
        return dt, cs
    _k, op, l, r = e
    ld, lc = _expr_info(l, schema)
    rd, rc = _expr_info(r, schema)
    if np.dtype(np.uint32) in (ld, rd):
        raise StromError(22, "SQL: uint32 columns in arithmetic are "
                             "outside this subset (no SQL unsigned "
                             "type to map the wraparound onto)")
    if op == "/":
        if ld.kind != "f" and rd.kind != "f":
            raise StromError(22, "SQL: integer / integer is outside "
                                 "this subset (PostgreSQL truncates; "
                                 "use a float operand for float "
                                 "division)")
        return np.dtype(np.float32), lc | rc
    for dt in (np.float32, np.float64, np.int64):
        if np.dtype(dt) in (ld, rd):
            return np.dtype(dt), lc | rc
    return np.dtype(np.int32), lc | rc


def _eval_expr(e, cols, wide: Optional[bool] = None):
    """jnp evaluation of an expression tree over decoded columns —
    dtype rules exactly as :func:`_expr_info` documents (the numpy
    oracle in the tests mirrors this step for step).  An expression
    that reads an int64 column runs at int64 throughout (*wide*): its
    int32 columns and literals widen at the leaves, so no int32
    intermediate can wrap."""
    import jax.numpy as jnp
    if wide is None:
        wide = any(cols[c].dtype == jnp.int64 for c in _expr_cols_of(e))
    k = e[0]
    if k == "col":
        v = cols[e[1]]
        return v.astype(jnp.int64) if wide and v.dtype == jnp.int32 else v
    if k == "lit":
        v = e[1]
        if isinstance(v, float):
            return jnp.float32(v)
        return jnp.int64(v) if wide else jnp.int32(v)
    if k == "neg":
        return -_eval_expr(e[1], cols, wide)
    _k, op, l, r = e
    a, b = _eval_expr(l, cols, wide), _eval_expr(r, cols, wide)
    if op == "/" or a.dtype == jnp.float32 or b.dtype == jnp.float32:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if op == "+":
        return a + b
    if op == "-":
        return a - b
    if op == "*":
        return a * b
    return a / b


def _expr_str(e) -> str:
    k = e[0]
    if k == "col":
        return f"c{e[1]}"
    if k == "lit":
        return str(e[1])
    if k == "neg":
        return f"-{_expr_str(e[1])}"
    _k, op, l, r = e
    return f"({_expr_str(l)} {op} {_expr_str(r)})"


class _Item:
    """One select-list item: ("col", c), ("agg", fn, c|None, distinct),
    or ("agge", fn, expression tree) for SUM/AVG over arithmetic;
    ``table`` is None for fact columns, a dimension name for qualified
    ``dim.cK`` references."""

    def __init__(self, kind, fn=None, col=None, distinct=False,
                 label="", table=None, expr=None):
        self.kind, self.fn, self.col = kind, fn, col
        self.distinct, self.label, self.table = distinct, label, table
        self.expr = expr     # "agge": the argument tree
        self.alias = None   # AS name: relabels the output


def _colref(p: _P, n_cols: int) -> Tuple[Optional[str], int]:
    """(table|None, col): a bare fact column (validated now) or a
    qualified ``name.cK`` reference (validated at binding)."""
    t = p.next()
    if t[0] == "name" and p.peek() == ("op", "."):
        p.next()
        nxt = p.next()
        m = re.fullmatch(r"[cC](\d+)", nxt[1]) if nxt[0] == "name" \
            else None
        if not m:
            raise StromError(22, f"SQL: expected {t[1]}.cK, got "
                                 f"{nxt[1]!r}")
        return t[1], int(m.group(1))
    return None, _col(t, n_cols)


def _parse_select_list(p: _P, n_cols: int) -> Optional[List[_Item]]:
    """None = ``*``."""
    if p.peek() == ("op", "*"):
        p.next()
        return None
    items = []
    while True:
        t = p.peek()
        if t and t[0] == "name" and t[1].lower() in _AGGS \
                and self_is_call(p):
            p.next()
            fn = t[1].lower()
            p.next()   # the '('
            distinct = False
            expr = None
            if p.peek() == ("op", "*"):
                p.next()
                if fn != "count":
                    raise StromError(22, f"SQL: {fn.upper()}(*) is not "
                                         f"a thing; name a column")
                tbl, col = None, None
                label = "count(*)"
            else:
                if p.kw("distinct"):
                    distinct = True
                    if fn != "count":
                        raise StromError(22, "SQL: DISTINCT only under "
                                             "COUNT in this subset")
                t2 = p.peek()
                qualified = (t2 is not None and t2[0] == "name"
                             and p.i + 1 < len(p.toks)
                             and p.toks[p.i + 1] == ("op", "."))
                if distinct or qualified:
                    tbl, col = _colref(p, n_cols)
                else:
                    e = _parse_expr(p, n_cols)
                    if e[0] == "col":
                        tbl, col = None, e[1]
                    elif fn not in ("sum", "avg"):
                        raise StromError(22, f"SQL: {fn.upper()} over "
                                             f"an expression is outside "
                                             f"this subset (SUM/AVG "
                                             f"take arithmetic)")
                    else:
                        tbl, col, expr = None, None, e
                if expr is not None:
                    label = f"{fn}({_expr_str(expr)})"
                else:
                    base = f"{tbl}.c{col}" if tbl else f"c{col}"
                    label = (f"{fn}(distinct {base})" if distinct
                             else f"{fn}({base})")
            p.expect_op(")")
            if expr is not None:
                items.append(_Item("agge", fn, label=label, expr=expr))
            else:
                items.append(_Item("agg", fn, col, distinct, label,
                                   tbl))
        else:
            tbl, c = _colref(p, n_cols)
            label = f"{tbl}.c{c}" if tbl else f"c{c}"
            items.append(_Item("col", col=c, label=label, table=tbl))
        if p.kw("as"):
            alias = p.next()
            if alias[0] != "name":
                raise StromError(22, "SQL: AS needs a name")
            items[-1].alias = alias[1]
        if p.peek() == ("op", ","):
            p.next()
            continue
        return items


def self_is_call(p: _P) -> bool:
    """Lookahead: the NAME at the cursor is followed by '('."""
    return p.i + 1 < len(p.toks) and p.toks[p.i + 1] == ("op", "(")


def _parse_cond_leaf(p: _P, n_cols: int) -> tuple:
    """One comparison: ("cmp", col, op, lit) | ("between", col, lo, hi)
    | ("in", col, [lits]) — or, when either side carries arithmetic or
    a second column, ("cmpe", lexpr, op, rexpr).  The simple shapes
    keep their dedicated forms so index promotion and string-dictionary
    translation stay exactly as before."""
    # a bare string literal can only open `'lit' cmp cN` — it cannot
    # start an expression
    if p.peek() is not None and p.peek()[0] == "str":
        lit = _lit(p.next())
        op = p.next()
        if op[0] != "op" or op[1] not in _CMPS:
            raise StromError(22, f"SQL: expected comparison, got "
                                 f"{op[1]!r}")
        c = _col(p.next(), n_cols)
        flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}
        return ("cmp", c, flip.get(op[1], op[1]), lit)
    left = _parse_expr(p, n_cols)
    if left[0] == "col":
        c = left[1]
        if p.kw("is"):
            neg = p.kw("not")
            p.expect_kw("null")
            return ("isnull", c, neg)
        if p.kw("between"):
            lo = _plit(p)
            p.expect_kw("and")
            hi = _plit(p)
            return ("between", c, lo, hi)
        if p.kw("in"):
            p.expect_op("(")
            lits = [_plit(p)]
            while p.peek() == ("op", ","):
                p.next()
                lits.append(_plit(p))
            p.expect_op(")")
            return ("in", c, lits)
    op = p.next()
    if op[0] != "op" or op[1] not in _CMPS:
        raise StromError(22, f"SQL: expected comparison, got {op[1]!r}")
    if p.peek() is not None and p.peek()[0] == "str":
        right = ("lit", _lit(p.next()))
    else:
        right = _parse_expr(p, n_cols)
    if left[0] == "col" and right[0] == "lit":
        return ("cmp", left[1], op[1], right[1])
    if left[0] == "lit" and right[0] == "col":   # literal cmp col: flip
        flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}
        return ("cmp", right[1], flip.get(op[1], op[1]), left[1])
    return ("cmpe", left, op[1], right)


def _parse_where(p: _P, n_cols: int):
    """Condition TREE with SQL precedence (AND binds tighter than OR;
    parentheses group): ("leaf", cond) | ("and", [t..]) | ("or", [t..]).
    """
    def factor():
        if p.kw("not"):
            return ("not", [factor()])
        if p.peek() == ("op", "("):
            # '(' is ambiguous: a condition group OR an arithmetic
            # subexpression ("(c0 + c1) > 5").  Try the group reading
            # first and backtrack to the expression leaf on failure.
            mark = p.i
            try:
                p.next()
                t = expr()
                p.expect_op(")")
                return t
            except StromError:
                p.i = mark
        return ("leaf", _parse_cond_leaf(p, n_cols))

    def term():
        fs = [factor()]
        while p.kw("and"):
            fs.append(factor())
        return fs[0] if len(fs) == 1 else ("and", fs)

    def expr():
        ts = [term()]
        while p.kw("or"):
            ts.append(term())
        return ts[0] if len(ts) == 1 else ("or", ts)

    return expr()


def _parse_having(p: _P, n_cols: int) -> List[tuple]:
    """[(fn, col|None, op, lit)] — aggregate comparisons only."""
    out = []
    while True:
        t = p.next()
        if t[0] != "name" or t[1].lower() not in _AGGS:
            raise StromError(22, "SQL: HAVING takes aggregate "
                                 "comparisons (COUNT/SUM/AVG/MIN/MAX)")
        fn = t[1].lower()
        p.expect_op("(")
        if p.peek() == ("op", "*"):
            p.next()
            col = None
            if fn != "count":
                raise StromError(22, f"SQL: {fn.upper()}(*) in HAVING")
        else:
            col = _col(p.next(), n_cols)
        p.expect_op(")")
        op = p.next()
        if op[0] != "op" or op[1] not in _CMPS:
            raise StromError(22, "SQL: HAVING needs a comparison")
        lit = _plit(p)
        if isinstance(lit, _Str):
            raise StromError(22, "SQL: HAVING against a string literal "
                                 "is outside this subset (aggregates "
                                 "compare numerically)")
        out.append((fn, col, op[1], lit))
        if p.kw("and"):
            continue
        return out


def _dict_cache(source):
    """Per-statement dictionary loader: ``get(col) -> StringDict|None``
    (missing sidecar = a plain numeric column; a STALE sidecar raises
    EIO loudly — stale codes decode to WRONG strings)."""
    cache: dict = {}

    def get(c: int):
        if c in cache:
            return cache[c]
        d = None
        if isinstance(source, str):
            from .strings import load_dict
            try:
                d = load_dict(source, c)
            except FileNotFoundError:
                d = None
        cache[c] = d
        return d
    return get


def _translate_cond(cond, dicts, schema=None) -> Optional[tuple]:
    """One leaf onto dictionary-code space (see the module docstring);
    None = the leaf is vacuously TRUE (``!= 'absent string'``)."""
    if cond[0] == "isnull":
        _k, c, neg = cond
        if schema is not None and not schema.col_nullable(c):
            # IS NULL on a non-nullable column: constant-fold exactly
            # (always false / always true)
            return None if neg else ("in", c, [])
        return cond
    if cond[0] == "cmpe":
        # expression comparison: validate the subset here (both sides
        # type-check, no dictionary columns — codes are ranks, and
        # arithmetic over ranks would be silent nonsense)
        _k, l, op, r = cond
        ld, lc = _expr_info(l, schema)
        rd, rc = _expr_info(r, schema)
        for cc in sorted(lc | rc):
            if dicts(cc) is not None:
                raise StromError(22, f"SQL: c{cc} (string column) in an "
                                     f"expression comparison")
        if np.dtype(np.uint32) in (ld, rd) and ld != rd:
            raise StromError(22, "SQL: comparing uint32 with a "
                                 "different type is outside this "
                                 "subset")
        return cond
    has_str = any(isinstance(x, _Str) for x in
                  (cond[2:] if cond[0] != "in" else cond[2]))
    c = cond[1]
    if not has_str:
        if dicts(c) is not None:
            raise StromError(22, f"SQL: comparing c{c} (string "
                                 f"column) with a number — use a "
                                 f"'string' literal")
        return cond
    d = dicts(c)
    if d is None:
        raise StromError(22, f"SQL: string literal against c{c}, "
                             f"which has no string dictionary "
                             f"(scan.strings.save_dict builds one)")
    vals = np.asarray(d.values)
    if cond[0] == "cmp":
        _k, _c, op, lit = cond
        if not isinstance(lit, _Str):
            raise StromError(22, f"SQL: comparing c{c} (string "
                                 f"column) with a number")
        if op in ("=", "=="):
            code = d.code_of(lit)
            return ("cmp", c, "=", code) if code is not None                 else ("in", c, [])
        if op in ("!=", "<>"):
            code = d.code_of(lit)
            return ("cmp", c, "!=", code) if code is not None else None
        if op == "<":
            hi = int(np.searchsorted(vals, str(lit), "left")) - 1
            return ("between", c, 0, hi) if hi >= 0 else ("in", c, [])
        if op == "<=":
            hi = int(np.searchsorted(vals, str(lit), "right")) - 1
            return ("between", c, 0, hi) if hi >= 0 else ("in", c, [])
        if op == ">":
            lo = int(np.searchsorted(vals, str(lit), "right"))
            return ("between", c, lo, len(vals) - 1)                 if lo < len(vals) else ("in", c, [])
        lo = int(np.searchsorted(vals, str(lit), "left"))
        return ("between", c, lo, len(vals) - 1)             if lo < len(vals) else ("in", c, [])
    if cond[0] == "between":
        _k, _c, lo, hi = cond
        if not (isinstance(lo, _Str) and isinstance(hi, _Str)):
            raise StromError(22, f"SQL: BETWEEN on c{c} mixes "
                                 f"string and numeric bounds")
        clo, chi = d.range_codes(lo, hi)
        return ("between", c, clo, chi)             if clo is not None and chi is not None and clo <= chi             else ("in", c, [])
    _k, _c, lits = cond
    if not all(isinstance(x, _Str) for x in lits):
        raise StromError(22, f"SQL: IN list on c{c} mixes "
                             f"strings and numbers")
    codes = [d.code_of(x) for x in lits]
    return ("in", c, [x for x in codes if x is not None])


def _translate_tree(tree, dicts, schema=None):
    """Translate every leaf; vacuously-true leaves simplify out (a true
    child erases an OR, drops from an AND).  None = no filter at all."""
    if tree is None:
        return None
    kind = tree[0]
    if kind == "leaf":
        cond = _translate_cond(tree[1], dicts, schema)
        return None if cond is None else ("leaf", cond)
    kids = [_translate_tree(t, dicts, schema) for t in tree[1]]
    if kind == "not":
        # NOT over a vacuously-true child is vacuously FALSE: keep a
        # match-nothing leaf so the truth value survives simplification
        return ("not", kids) if kids[0] is not None \
            else ("leaf", ("in", 0, []))
    if kind == "or" and any(k is None for k in kids):
        return None
    kids = [k for k in kids if k is not None]
    if not kids:
        return None
    return kids[0] if len(kids) == 1 else (kind, kids)


def _decode_strings(out: dict, dicts) -> dict:
    """Result-edge decode: labels naming a dictionary column (``cN``,
    ``min(cN)``, ``max(cN)``) turn codes back into strings."""
    for label, v in list(out.items()):
        m = re.fullmatch(r"(?:(min|max)\()?c(\d+)\)?", label)
        if not m:
            continue
        d = dicts(int(m.group(2)))
        if d is None:
            continue
        if v is None:
            continue
        arr = np.asarray(v)
        if arr.ndim == 0:
            out[label] = d.decode([int(arr)])[0]
        else:
            out[label] = d.decode(arr)
    return out


def _cmp_np(op: str):
    return {"=": np.equal, "==": np.equal, "!=": np.not_equal,
            "<>": np.not_equal, "<": np.less, "<=": np.less_equal,
            ">": np.greater, ">=": np.greater_equal}[op]


def _expr_cols_of(e) -> set:
    if e[0] == "col":
        return {e[1]}
    if e[0] == "lit":
        return set()
    if e[0] == "neg":
        return _expr_cols_of(e[1])
    return _expr_cols_of(e[2]) | _expr_cols_of(e[3])


def _null_mask(cols, refs):
    """OR of the NULL masks of every referenced nullable column — the
    rows where a comparison is UNKNOWN rather than false (SQL 3VL).
    None when no referenced column is nullable (nothing can be
    unknown, the common all-NOT-NULL schema)."""
    u = None
    for c in refs:
        n = getattr(cols, "nulls", {}).get(c)
        if n is not None:
            u = n if u is None else (u | n)
    return u


def _not_null(cols, refs, mask):
    """SQL comparison semantics: NULL cmp x is never true — AND away
    the NULL rows of every referenced nullable column."""
    u = _null_mask(cols, refs)
    return mask if u is None else mask & ~u


def _leaf_mask(cond, cols):
    """jnp mask for one leaf condition (NULL rows of referenced
    nullable columns never match, per SQL three-valued logic)."""
    import jax.numpy as jnp
    if cond[0] == "isnull":
        _k, c, neg = cond
        n = getattr(cols, "nulls", {}).get(c)
        if n is None:              # untranslated non-nullable leaf
            base = jnp.zeros(cols[c].shape, bool)
            return ~base if neg else base
        return ~n if neg else n
    if cond[0] == "cmpe":
        _k, l, op, r = cond
        a, b = _eval_expr(l, cols), _eval_expr(r, cols)
        if a.dtype != b.dtype:     # validated: only int/float mixing
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        fns = {"=": jnp.equal, "==": jnp.equal,
               "!=": jnp.not_equal, "<>": jnp.not_equal,
               "<": jnp.less, "<=": jnp.less_equal,
               ">": jnp.greater, ">=": jnp.greater_equal}
        return _not_null(cols, _expr_cols_of(l) | _expr_cols_of(r),
                         fns[op](a, b))
    if cond[0] == "cmp":
        _, c, op, lit = cond
        fns = {"=": jnp.equal, "==": jnp.equal,
               "!=": jnp.not_equal, "<>": jnp.not_equal,
               "<": jnp.less, "<=": jnp.less_equal,
               ">": jnp.greater, ">=": jnp.greater_equal}
        return _not_null(cols, {c}, fns[op](cols[c], lit))
    if cond[0] == "between":
        _, c, lo, hi = cond
        return _not_null(cols, {c}, (cols[c] >= lo) & (cols[c] <= hi))
    _, c, lits = cond
    import jax.numpy as jnp
    one = jnp.zeros(cols[c].shape, bool)
    for v in lits:
        one = one | (cols[c] == v)
    return _not_null(cols, {c}, one)


def _leaf_unknown(cond, cols):
    """UNKNOWN mask for one leaf: rows where a referenced nullable
    column is NULL.  IS [NOT] NULL is the one predicate that is never
    unknown.  None = no row can be unknown."""
    if cond[0] == "isnull":
        return None
    if cond[0] == "cmpe":
        refs = _expr_cols_of(cond[1]) | _expr_cols_of(cond[3])
    else:
        refs = {cond[1]}
    return _null_mask(cols, refs)


def _or_unknown(a, b):
    if a is None:
        return b
    return a if b is None else (a | b)


def _tree_masks(tree, cols):
    """Kleene 3VL masks for a subtree: ``(true, unknown)``, with
    *unknown* None when no NULL can reach the subtree.  FALSE is
    whatever is neither.  NOT swaps TRUE/FALSE and keeps UNKNOWN
    unknown — a plain ``~true`` wrongly admitted NULL rows; AND is
    false if any operand is false, OR is true if any operand is true
    (truth dominates unknown on the side that decides the row)."""
    if tree[0] == "leaf":
        return _leaf_mask(tree[1], cols), _leaf_unknown(tree[1], cols)
    if tree[0] == "not":
        t, u = _tree_masks(tree[1][0], cols)
        return (~t if u is None else ~t & ~u), u
    t, u = _tree_masks(tree[1][0], cols)
    for kid in tree[1][1:]:
        t2, u2 = _tree_masks(kid, cols)
        if tree[0] == "and":
            if u is not None or u2 is not None:
                f1 = ~t if u is None else ~t & ~u
                f2 = ~t2 if u2 is None else ~t2 & ~u2
                u = _or_unknown(u, u2) & ~f1 & ~f2
            t = t & t2
        else:
            t = t | t2
            if u is not None or u2 is not None:
                u = _or_unknown(u, u2) & ~t
    return t, u


def _tree_mask(tree, cols):
    """The WHERE answer is the definitely-TRUE mask (UNKNOWN rows drop,
    per SQL).  Workers rebuild this from the shipped ``_tree``."""
    return _tree_masks(tree, cols)[0]


def _promotable(cond) -> bool:
    return (cond[0] == "cmp" and cond[2] in ("=", "=="))         or cond[0] in ("between", "in")


def _promote(q: Query, cond) -> Query:
    if cond[0] == "cmp":
        return q.where_eq(cond[1], cond[3])
    if cond[0] == "between":
        return q.where_range(cond[1], cond[2], cond[3])
    return q.where_in(cond[1], cond[2])


def _apply_where(q: Query, tree) -> Query:
    """The first index-capable LEAF of a top-level AND (or a sole leaf)
    becomes a STRUCTURED filter the planner can serve from a sidecar;
    everything else — the rest of the conjunction, or any OR tree —
    composes as a residual ``where`` predicate the index path RECHECKS
    on index-resolved rows (Query's Index Cond + Filter shape)."""
    if tree is None:
        return q
    rest = None
    if tree[0] == "leaf" and _promotable(tree[1]):
        q = _promote(q, tree[1])
    elif tree[0] == "and":
        kids = list(tree[1])
        pick = next((i for i, k in enumerate(kids)
                     if k[0] == "leaf" and _promotable(k[1])), None)
        if pick is None:
            rest = tree
        else:
            q = _promote(q, kids[pick][1])
            kids = kids[:pick] + kids[pick + 1:]
            rest = kids[0] if len(kids) == 1 else ("and", kids)
    else:
        rest = tree
    if rest is not None:
        # _tree rides along so worker processes can rebuild the mask
        # (a bare lambda would mark the query non-parallel)
        q = q.where(lambda cols, rest=rest: _tree_mask(rest, cols),
                    _tree=rest)
    return q


def _having_fn(havings: List[tuple], agg_cols: List[int]):
    if not havings:
        return None

    def hv(res, havings=havings, agg_cols=agg_cols):
        m = np.ones(len(np.asarray(res["count"])), bool)
        for fn, col, op, lit in havings:
            if fn == "count":
                vals = np.asarray(res["count"])
            else:
                if col not in agg_cols:
                    raise StromError(22, f"SQL: HAVING {fn}(c{col}) "
                                         f"needs c{col} aggregated in "
                                         f"the SELECT list")
                i = agg_cols.index(col)
                vals = np.asarray(res[{"sum": "sums", "avg": "avgs",
                                       "min": "mins",
                                       "max": "maxs"}[fn]][i])
            m = m & _cmp_np(op)(vals, lit)
        return m
    return hv


_JOIN_TYPES = ("inner", "left", "semi", "anti")


def _build_star(q: Query, joins, items, tables, group_cols, havings,
                order, limit, off, dicts, schema):
    """The >=2-JOIN statement (star schema in ONE statement, round 5):
    every dimension probes in the same fused scan kernel
    (`Query.star_join`).  Faces: additive aggregates — COUNT(*),
    SUM/AVG over fact columns or expressions, SUM/AVG(dim.cK) — or row
    materialization (fact columns + dim payloads).  The reference's
    scan inherits arbitrary join composition from the executor above it
    (`pgsql/nvme_strom.c:941-979`); this serves the star core of it."""
    from .strings import dict_path_for
    if group_cols is not None or havings or order is not None:
        raise StromError(22, "SQL: GROUP BY/HAVING/ORDER BY with JOIN "
                             "are outside this subset")
    if items is None:
        raise StromError(22, "SQL: JOIN needs an explicit select list")
    dim_names = [dname for _h, dname, _pc, _kc in joins]
    for it in items:
        if it.table is not None and it.table not in dim_names:
            raise StromError(22, f"SQL: unknown table {it.table!r}")
    # per-dim payload columns referenced in the select list
    payload: dict = {}
    for it in items:
        if it.table is not None:
            payload.setdefault(it.table, set()).add(it.col)
    specs = []
    for how, dname, pc, kc in joins:
        if not tables or dname not in tables:
            raise StromError(22, f"SQL: JOIN table {dname!r} not bound "
                                 f"(pass tables={{{dname!r}: (path, "
                                 f"schema)}})")
        dpath, dschema = tables[dname]
        if not 0 <= kc < dschema.n_cols:
            raise StromError(22, f"SQL: {dname}.c{kc} out of range")
        # two string columns carry codes from SEPARATE dictionaries
        # (same refusal as the single join)
        if dicts(pc) is not None or (
                isinstance(dpath, str)
                and os.path.exists(dict_path_for(dpath, kc))):
            raise StromError(22, "SQL: JOIN on string-dictionary "
                                 "columns is outside this subset "
                                 "(separate dictionaries make codes "
                                 "incomparable)")
        cols_ref = sorted(payload.get(dname, ()))
        if len(cols_ref) > 1:
            raise StromError(22, f"SQL: one {dname}.cK column per "
                                 f"dimension in this subset")
        if cols_ref and how in ("semi", "anti"):
            raise StromError(22, f"SQL: {how.upper()} JOIN does not "
                                 f"expose {dname} columns (EXISTS "
                                 f"semantics)")
        vc = cols_ref[0] if cols_ref else None
        if vc is not None and not 0 <= vc < dschema.n_cols:
            raise StromError(22, f"SQL: {dname}.c{vc} out of range")
        specs.append({"probe_col": pc, "table": dpath,
                      "schema": dschema, "key_col": kc,
                      "value_col": vc, "how": how})
    dim_idx = {dname: i for i, dname in enumerate(dim_names)}
    agg_items = [it for it in items if it.kind in ("agg", "agge")]
    if agg_items and len(agg_items) != len(items):
        raise StromError(22, "SQL: JOIN mixes aggregates and bare "
                             "columns")
    if agg_items:
        if limit is not None:
            raise StromError(22, "SQL: LIMIT on a join aggregate")
        exprs, eidx = [], {}
        for it in agg_items:
            if it.kind == "agge":
                eidx[id(it)] = len(exprs)
                exprs.append(it.expr)
                continue
            ok = (it.fn == "count" and it.col is None
                  and not it.distinct) or \
                 (it.fn in ("sum", "avg") and not it.distinct
                  and it.col is not None)
            if not ok:
                raise StromError(22, f"SQL: {it.label} with a star "
                                     f"join is outside this subset")
            if it.table is None and it.fn in ("sum", "avg") \
                    and dicts(it.col) is not None:
                raise StromError(22, f"SQL: {it.label} over a string "
                                     f"column")
        q = q.star_join(specs, exprs=exprs)

        def assemble(res, agg_items=agg_items, eidx=eidx,
                     dim_idx=dim_idx):
            out = {}
            n = int(res["count"])
            for it in agg_items:
                if it.kind == "agge":
                    s = np.asarray(res["esums"][eidx[id(it)]]).item()
                    out[it.label] = s if it.fn == "sum" else \
                        (s / n if n else None)
                elif it.fn == "count":
                    out[it.label] = n
                elif it.table is None:
                    s = np.asarray(res["sums"][it.col]).item()
                    if it.fn == "sum":
                        out[it.label] = s
                    else:   # AVG skips NULL cells: non-NULL denominator
                        nnc = res.get("nncounts")
                        nn = n if nnc is None \
                            else int(np.asarray(nnc[it.col]))
                        out[it.label] = s / nn if nn else None
                else:
                    i = dim_idx[it.table]
                    s = np.asarray(res["pay_sums"][i]).item()
                    if it.fn == "sum":
                        out[it.label] = s
                    else:   # AVG over the dim payload skips NULLs
                        hits = n - int(np.asarray(res["null_counts"][i]))
                        out[it.label] = s / hits if hits else None
            return out
        return q, assemble
    # row face: fact columns + dim payloads
    fact_cols = []
    for it in items:
        if it.table is None and it.col not in fact_cols:
            fact_cols.append(it.col)
    q = q.star_join(specs, materialize=True, fact_cols=fact_cols,
                    limit=limit, offset=off)

    def assemble(res, items=items, dim_idx=dim_idx, joins=joins):
        out = {}
        for it in items:
            if it.table is None:
                out[it.label] = np.asarray(res[f"c{it.col}"])
            else:
                out[it.label] = np.asarray(
                    res[f"pay{dim_idx[it.table]}"])
        for how, dname, _pc, _kc in joins:
            if how == "left":   # the per-dim NULL indicator
                out[f"matched_{dname}"] = np.asarray(
                    res[f"m{dim_idx[dname]}"])
        out["positions"] = np.asarray(res["positions"])
        return out
    return q, assemble


def parse_sql(sql: str, source, schema,
              tables: Optional[dict] = None,
              workers: int = 0) -> Tuple[Query, "callable"]:
    """Parse *sql* against *source*/*schema*; returns ``(query,
    assemble)`` where ``assemble(run_result) -> dict`` relabels the
    terminal's output into the statement's select-list names — with
    dictionary-encoded string columns decoded back to strings at the
    edge.  *tables* binds JOIN dimension names to ``(path, schema)``.
    ``workers=N`` plans the scan over N worker processes (the Gather
    analog; predicate trees ship to workers, so any WHERE subset
    statement parallelizes)."""
    import inspect
    aliases: dict = {}
    q, assemble = _parse_sql_raw(sql, source, schema, tables=tables,
                                 _aliases_out=aliases, workers=workers)
    dicts = _dict_cache(source)

    def assemble_decoded(res, **kw):
        out = _decode_strings(assemble(res, **kw), dicts)
        return {aliases.get(k, k): v for k, v in out.items()}

    assemble_decoded.__signature__ = inspect.signature(assemble)
    return q, assemble_decoded


def _parse_sql_raw(sql: str, source, schema,
                   tables: Optional[dict] = None,
                   _aliases_out: Optional[dict] = None,
                   workers: int = 0) -> Tuple[Query, "callable"]:
    n_cols = schema.n_cols
    p = _P(_tokenize(sql))
    p.expect_kw("select")
    select_distinct = p.kw("distinct")
    items = _parse_select_list(p, n_cols)
    if _aliases_out is not None and items:
        _aliases_out.update({it.label: it.alias for it in items
                             if it.alias})
    p.expect_kw("from")
    t = p.next()
    if t[0] != "name":
        raise StromError(22, f"SQL: FROM needs a table name, got {t[1]!r}")
    joins: List[tuple] = []   # (how, dim_name, probe_col, dim_key_col)
    while True:
        nxt = p.peek()
        how = "inner"
        joining = False
        if nxt and nxt[0] == "name" and nxt[1].lower() in _JOIN_TYPES:
            how = p.next()[1].lower()
            p.expect_kw("join")  # "FROM t LEFT ..." can be nothing else
            joining = True
        else:
            joining = p.kw("join")
        if not joining:
            if how != "inner":
                raise StromError(22, "SQL: join type without JOIN")
            break
        dn = p.next()
        if dn[0] != "name":
            raise StromError(22, "SQL: JOIN needs a table name")
        p.expect_kw("on")
        lt, lc = _colref(p, n_cols)
        p.expect_op("=")
        rt, rc = _colref(p, n_cols)
        sides = {lt: lc, rt: rc}
        if None not in sides or dn[1] not in sides:
            raise StromError(22, f"SQL: ON must equate a fact column "
                                 f"with a {dn[1]}.cK column")
        if any(j[1] == dn[1] for j in joins):
            raise StromError(22, f"SQL: table {dn[1]!r} joined twice")
        joins.append((how, dn[1], sides[None], sides[dn[1]]))
    join = joins[0] if len(joins) == 1 else None
    where_tree = _parse_where(p, n_cols) if p.kw("where") else None
    dicts = _dict_cache(source)
    where_tree = _translate_tree(where_tree, dicts, schema)
    group_cols: Optional[List[int]] = None
    if p.kw("group"):
        p.expect_kw("by")
        group_cols = [_col(p.next(), n_cols)]
        while p.peek() == ("op", ","):
            p.next()
            group_cols.append(_col(p.next(), n_cols))
    havings = _parse_having(p, n_cols) if p.kw("having") else []
    # (("col", c) | ("agg", fn, col), descending)
    order: Optional[Tuple[tuple, bool]] = None
    if p.kw("order"):
        p.expect_kw("by")
        t2 = p.peek()
        if t2 and t2[0] == "name" and t2[1].lower() in _AGGS \
                and self_is_call(p):
            # ORDER BY COUNT(*)/SUM(c)/... — grouped-result ordering
            fn = p.next()[1].lower()
            p.next()   # '('
            if p.peek() == ("op", "*"):
                p.next()
                ocol = None
                if fn != "count":
                    raise StromError(22, f"SQL: {fn.upper()}(*)")
            else:
                ocol = _col(p.next(), n_cols)
                if fn == "count":
                    raise StromError(22, "SQL: COUNT takes (*) in this "
                                         "subset (COUNT(cN) would "
                                         "silently mean COUNT(*))")
            p.expect_op(")")
            okey = ("agg", fn, ocol)
        else:
            ocols = [_col(p.next(), n_cols)]
            while p.peek() == ("op", ","):
                p.next()
                ocols.append(_col(p.next(), n_cols))
            okey = ("col", ocols[0]) if len(ocols) == 1 \
                else ("cols", ocols)
        desc = False
        if p.kw("desc"):
            desc = True
        else:
            p.kw("asc")
        order = (okey, desc)
    limit = offset = None
    if p.kw("limit"):
        limit = int(_plit(p))
    if p.kw("offset"):
        offset = int(_plit(p))
    left = p.peek()
    if left is not None:
        raise StromError(22, f"SQL: trailing input at {left[1]!r}")
    if havings and group_cols is None:
        raise StromError(22, "SQL: HAVING requires GROUP BY")

    if not joins and items is not None:
        for it in items:
            if it.table is not None:
                raise StromError(22, f"SQL: {it.label} references a "
                                     f"table with no JOIN")
    if select_distinct:
        if items is None or any(it.kind != "col" or it.table is not None
                                for it in items):
            raise StromError(22, "SQL: SELECT DISTINCT takes 1-2 plain "
                                 "fact columns")
        if group_cols is not None or join is not None:
            raise StromError(22, "SQL: SELECT DISTINCT with GROUP BY/"
                                 "JOIN is outside this subset")
        seen: List[int] = []
        for it in items:
            if it.col not in seen:
                seen.append(it.col)
        group_cols = seen      # DISTINCT == GROUP BY the select list
    q = _apply_where(Query(source, schema, workers=workers), where_tree)
    off = offset or 0

    # --- STAR (>= 2 JOINs probed in one pass) -----------------------------
    if len(joins) >= 2:
        return _build_star(q, joins, items, tables, group_cols, havings,
                           order, limit, off, dicts, schema)

    # --- JOIN -------------------------------------------------------------
    if join is not None:
        how_, dname, probe_col, key_col = join
        if not tables or dname not in tables:
            raise StromError(22, f"SQL: JOIN table {dname!r} not bound "
                                 f"(pass tables={{{dname!r}: (path, "
                                 f"schema)}})")
        dpath, dschema = tables[dname]
        if group_cols is not None or havings or order is not None:
            raise StromError(22, "SQL: GROUP BY/HAVING/ORDER BY with "
                                 "JOIN are outside this subset")
        if items is None:
            raise StromError(22, "SQL: JOIN needs an explicit select "
                                 "list")
        if not 0 <= key_col < dschema.n_cols:
            raise StromError(22, f"SQL: {dname}.c{key_col} out of range")
        # two string columns carry codes from SEPARATE dictionaries —
        # joining them would compare incomparable ranks and silently
        # return wrong rows; refuse until the tables share an encoding
        from .strings import dict_path_for
        if dicts(probe_col) is not None or (
                isinstance(dpath, str)
                and os.path.exists(dict_path_for(dpath, key_col))):
            raise StromError(22, "SQL: JOIN on string-dictionary "
                                 "columns is outside this subset "
                                 "(separate dictionaries make codes "
                                 "incomparable)")
        for it in items:
            if it.kind == "agge":
                raise StromError(22, f"SQL: {it.label} with a single "
                                     f"JOIN is outside this subset "
                                     f"(star statements serve "
                                     f"expression aggregates)")
            if it.table is not None and it.table != dname:
                raise StromError(22, f"SQL: unknown table {it.table!r}")
        dim_cols = sorted({it.col for it in items if it.table == dname})
        if len(dim_cols) > 1:
            raise StromError(22, f"SQL: one {dname}.cK column per join "
                                 f"in this subset")
        if dim_cols and how_ in ("semi", "anti"):
            raise StromError(22, f"SQL: {how_.upper()} JOIN does not "
                                 f"expose {dname} columns (EXISTS "
                                 f"semantics)")
        value_col = dim_cols[0] if dim_cols else key_col
        if not 0 <= value_col < dschema.n_cols:
            raise StromError(22, f"SQL: {dname}.c{value_col} out of "
                                 f"range")
        agg_items = [it for it in items if it.kind == "agg"]
        if agg_items and len(agg_items) != len(items):
            raise StromError(22, "SQL: JOIN mixes aggregates and bare "
                                 "columns")
        if agg_items:
            if limit is not None:
                raise StromError(22, "SQL: LIMIT on a join aggregate")
            for it in agg_items:
                ok = (it.fn == "count" and it.col is None) or \
                     (it.fn == "sum" and not it.distinct)
                if not ok:
                    raise StromError(22, f"SQL: {it.label} with JOIN "
                                         f"is outside this subset")
            q = q.join_table(probe_col, dpath, dschema, key_col,
                             value_col, how=how_)

            def assemble(res, agg_items=agg_items):
                out = {}
                for it in agg_items:
                    if it.fn == "count":
                        out[it.label] = int(res["matched"])
                    elif it.table is None:
                        out[it.label] = \
                            np.asarray(res["sums"][it.col]).item()
                    else:
                        out[it.label] = \
                            np.asarray(res["payload_sum"]).item()
                return out
            return q, assemble
        for it in items:
            if it.table is None and it.col != probe_col:
                raise StromError(
                    22, f"SQL: the row face serves the probe column "
                        f"c{probe_col} and {dname}.cK; fetch() other "
                        f"fact columns by position")
        q = q.join_table(probe_col, dpath, dschema, key_col, value_col,
                         materialize=True, limit=limit, offset=off,
                         how=how_)

        def assemble(res, items=items):
            out = {}
            for it in items:
                out[it.label] = np.asarray(
                    res["keys"] if it.table is None else res["payload"])
            out["positions"] = np.asarray(res["positions"])
            if "matched" in res:   # the left face's NULL indicator
                out["matched"] = np.asarray(res["matched"])
            return out
        return q, assemble

    # --- GROUP BY ---------------------------------------------------------
    if group_cols is not None:
        if items is None:
            raise StromError(22, "SQL: GROUP BY needs an explicit "
                                 "select list (group cols + aggregates)")
        agg_cols: List[int] = []
        # SUM/AVG of expressions, in select-list order: the grouped
        # expression kernel sums each per group beside the columns
        exprs: list = []
        eidx: dict = {}
        for it in items:
            if it.kind == "agge":
                for cc in sorted(_expr_cols_of(it.expr)):
                    if dicts(cc) is not None:
                        raise StromError(22, f"SQL: c{cc} (string column) "
                                             f"in {it.label}")
                eidx[id(it)] = len(exprs)
                exprs.append(it.expr)
            elif it.kind == "col":
                if it.col not in group_cols:
                    raise StromError(22, f"SQL: c{it.col} is neither "
                                         f"grouped nor aggregated")
            elif it.fn == "count" and it.col is None and not it.distinct:
                pass
            elif it.fn in ("sum", "avg", "min", "max"):
                if it.fn in ("sum", "avg") and dicts(it.col) is not None:
                    raise StromError(22, f"SQL: {it.label} over a "
                                         f"string column (codes would "
                                         f"sum meaninglessly; MIN/MAX/"
                                         f"COUNT are the string "
                                         f"aggregates)")
                if it.col not in agg_cols:
                    agg_cols.append(it.col)
            else:
                raise StromError(22, f"SQL: {it.label} under GROUP BY "
                                     f"is outside this subset")
        for fn, col, _op, _lit_ in havings:
            if col is not None and col not in agg_cols:
                agg_cols.append(col)
        aggfns = {it.fn for it in items if it.kind == "agg"} | \
            {h[0] for h in havings} | \
            ({order[0][1]} if order and order[0][0] == "agg" else set())
        if exprs and aggfns & {"min", "max"}:
            raise StromError(22, "SQL: MIN/MAX with expression aggregates "
                                 "under GROUP BY is outside this subset "
                                 "(the grouped expression kernel sums)")
        # ORDER BY on grouped results sorts groups post-aggregation (the
        # SQL top-N-groups shape): the key is a group column or an
        # aggregate, which may need aggregating even if unselected
        if order is not None and order[0][0] == "agg" \
                and order[0][2] is not None \
                and order[0][2] not in agg_cols:
            agg_cols.append(order[0][2])
        # ORDER BY group columns: a lexicographic sort of the group rows
        ocols = [] if order is None or order[0][0] == "agg" else \
            ([order[0][1]] if order[0][0] == "col" else list(order[0][1]))
        for oc in ocols:
            if oc not in group_cols:
                raise StromError(22, f"SQL: ORDER BY c{oc} is neither "
                                     f"grouped nor an aggregate")
        if len(ocols) > 1 and order[1]:
            # SQL binds DESC to the last key alone; one direction for
            # the whole lexicographic order would be silent drift
            raise StromError(22, "SQL: DESC on a multi-key ORDER BY of "
                                 "grouped results is outside this subset")
        # the column kernels need at least one aggregation column even
        # for a COUNT(*)-only statement: the group key column itself is
        # the free choice (its sums are simply unused)
        eff_aggs = agg_cols or ([] if exprs else [group_cols[0]])
        q = q.group_by_cols(group_cols, agg_cols=eff_aggs,
                            agg_exprs=exprs or None,
                            having=_having_fn(havings, eff_aggs))

        def assemble(res, items=items, group_cols=group_cols,
                     agg_cols=eff_aggs, eidx=eidx, order=order,
                     ocols=ocols, limit=limit, off=off):
            def field(kind, fn=None, col=None, item=None):
                if kind == "col":
                    return np.asarray(
                        res["key_cols"][group_cols.index(col)])
                if fn == "count":
                    return np.asarray(res["count"])
                if kind == "agge":
                    return np.asarray(res["esums" if fn == "sum" else
                                          "eavgs"][eidx[id(item)]])
                return np.asarray(res[{"sum": "sums", "avg": "avgs",
                                       "min": "mins",
                                       "max": "maxs"}[fn]]
                                  [agg_cols.index(col)])

            n = len(np.asarray(res["count"]))
            perm = np.arange(n)
            if order is not None:
                okey, desc = order
                if okey[0] == "agg":
                    perm = np.argsort(field(*okey), kind="stable")
                else:   # np.lexsort's primary key is its last
                    perm = np.lexsort([field("col", col=c)
                                       for c in reversed(ocols)])
                if desc:
                    perm = perm[::-1]
            if order is not None or limit is not None or off:
                end = None if limit is None else off + limit
                perm = perm[off:end]
            out = {}
            for it in items:
                arr = field(it.kind, it.fn, it.col, it)
                out[it.label] = arr[perm]
            return out
        return q, assemble

    # --- ORDER BY ---------------------------------------------------------
    if order is not None:
        okey, desc = order
        if okey[0] == "agg":
            raise StromError(22, "SQL: ORDER BY an aggregate requires "
                                 "GROUP BY")
        ocols = [okey[1]] if okey[0] == "col" else list(okey[1])
        oc = ocols[0]
        extra: List[int] = []
        if items is not None:
            for it in items:
                if it.kind != "col":
                    raise StromError(22, "SQL: ORDER BY with "
                                         "aggregates requires GROUP BY")
                if it.col != oc and it.col not in extra:
                    extra.append(it.col)
        else:
            extra = [c for c in range(n_cols) if c != oc]
        q = q.order_by(ocols, descending=desc, limit=limit, offset=off)
        labels = [it.label for it in items] if items is not None else \
            [f"c{c}" for c in range(n_cols)]

        def assemble(res, oc=oc, extra=extra, labels=labels,
                     source=source, schema=schema, session=None,
                     device=None):
            pos = np.asarray(res["positions"])
            out = {f"c{oc}": np.asarray(res["values"])}
            if extra:
                # projected columns beyond the sort key: point-lookups
                # by position, returned in caller (sorted) order — on
                # the CALLER's session/device (sql_query threads them)
                fetched = Query(source, schema).fetch(
                    pos, cols=extra, session=session, device=device)
                for c in extra:
                    out[f"c{c}"] = np.asarray(fetched[f"col{c}"])
            return {**{lbl: out[lbl] for lbl in labels},
                    "positions": pos}
        return q, assemble

    # --- plain projection -------------------------------------------------
    if items is None or all(it.kind == "col" for it in items):
        cols = None if items is None else [it.col for it in items]
        q = q.select(cols, limit=limit, offset=off)

        def assemble(res, cols=cols):
            sel = cols if cols is not None else \
                [int(k[3:]) for k in res if k.startswith("col")]
            out = {}
            for c in sel:
                arr = np.asarray(res[f"col{c}"])
                if f"null{c}" in res:
                    # nullable column: real NULLs at the result edge
                    # (object array with None — never a sentinel value)
                    m = np.asarray(res[f"null{c}"]).astype(bool)
                    obj = arr.astype(object)
                    obj[m] = None
                    arr = obj
                out[f"c{c}"] = arr
            out["positions"] = np.asarray(res["positions"])
            return out
        return q, assemble

    # --- scalar aggregates ------------------------------------------------
    if limit is not None:
        raise StromError(22, "SQL: LIMIT on a scalar aggregate")
    aggs = [it for it in items if it.kind == "agg"]
    agges = [it for it in items if it.kind == "agge"]
    if len(aggs) + len(agges) != len(items):
        raise StromError(22, "SQL: mixing bare columns with aggregates "
                             "needs GROUP BY")
    if agges:
        # any expression aggregate routes the WHOLE list through the
        # fused expression kernel (plain SUM(cN) becomes the ("col", c)
        # tree) — one scan, one result contract
        trees, tmap = [], {}
        for it in items:
            if it.kind == "agge":
                tmap[id(it)] = len(trees)
                trees.append(it.expr)
            elif it.fn == "count" and it.col is None and not it.distinct:
                pass
            elif it.fn in ("sum", "avg") and not it.distinct:
                if dicts(it.col) is not None:
                    raise StromError(22, f"SQL: {it.label} over a "
                                         f"string column")
                tmap[id(it)] = len(trees)
                trees.append(("col", it.col))
            else:
                raise StromError(22, f"SQL: {it.label} cannot combine "
                                     f"with expression aggregates")
        q = q.aggregate_exprs(trees)

        def assemble(res, items=items, tmap=tmap):
            out = {}
            n = int(res["count"])
            for it in items:
                if it.kind == "agg" and it.fn == "count":
                    out[it.label] = n
                    continue
                s = np.asarray(res["esums"][tmap[id(it)]]).item()
                out[it.label] = s if it.fn == "sum" else \
                    (s / n if n else None)
            return out
        return q, assemble
    if len(aggs) == 1 and aggs[0].distinct:
        q = q.count_distinct(aggs[0].col)
        lbl = aggs[0].label
        return q, (lambda res, lbl=lbl: {lbl: int(res["distinct"])})
    if len(aggs) == 1 and aggs[0].fn in ("min", "max"):
        it = aggs[0]
        q = q.top_k(it.col, 1, largest=(it.fn == "max"))

        def assemble(res, it=it):
            vals = np.asarray(res["values"])
            poss = np.asarray(res["positions"])
            empty = len(vals) == 0 or int(poss[0]) < 0
            return {it.label: None if empty else vals[0].item()}
        return q, assemble
    sum_cols: List[int] = []
    for it in aggs:
        if it.fn in ("sum", "avg"):
            if dicts(it.col) is not None:
                raise StromError(22, f"SQL: {it.label} over a string "
                                     f"column (MIN/MAX/COUNT are the "
                                     f"string aggregates)")
            if it.col not in sum_cols:
                sum_cols.append(it.col)
        elif it.fn == "count" and it.col is None:
            pass
        elif it.fn == "count" and not it.distinct:
            # COUNT(cN): non-NULL count (round 5) — rides the same
            # projected column slot so nncounts stays aligned
            if it.col not in sum_cols:
                sum_cols.append(it.col)
        else:
            raise StromError(22, f"SQL: {it.label} cannot combine with "
                                 f"other aggregates without GROUP BY")
    q = q.aggregate(cols=sum_cols or None)

    def assemble(res, aggs=aggs, sum_cols=sum_cols):
        out = {}
        n = int(res["count"])
        nnc = res.get("nncounts")   # present iff the schema has
        #                             nullable columns (NULL-aware)

        def denom(col):
            return int(np.asarray(nnc[sum_cols.index(col)])) \
                if nnc is not None else n
        for it in aggs:
            if it.fn == "count" and it.col is None:
                out[it.label] = n
            elif it.fn == "count":
                out[it.label] = denom(it.col)
            else:
                s = np.asarray(res["sums"][sum_cols.index(it.col)])
                d = denom(it.col)
                out[it.label] = s.item() if it.fn == "sum" else \
                    (s.item() / d if d else None)
        return out
    return q, assemble


def sql_query(sql: str, source, schema, tables: Optional[dict] = None,
              **run_kw) -> dict:
    """Parse + run in one call; returns the select-list-labeled result.
    ``session``/``device`` run kwargs also reach any post-pass the
    assembler performs (the projected ORDER BY point-lookups)."""
    import inspect
    q, assemble = parse_sql(sql, source, schema, tables=tables,
                            workers=int(run_kw.get("workers") or 0))
    res = q.run(**run_kw)
    params = inspect.signature(assemble).parameters
    extra = {k: run_kw[k] for k in ("session", "device")
             if k in run_kw and k in params}
    out = assemble(res, **extra)
    if isinstance(res, dict) and "_analyze" in res:
        out["_analyze"] = res["_analyze"]   # EXPLAIN ANALYZE face
    if isinstance(res, dict) and "_workers" in res:
        out["_workers"] = res["_workers"]   # per-worker scan seconds
    return out


def create_table_as(dest_path: str, sql: str, source, schema,
                    tables: Optional[dict] = None,
                    overwrite: bool = False, **run_kw):
    """CREATE TABLE AS: run *sql* and materialize its result as a NEW
    heap table at *dest_path* (the ETL face — derived tables requery
    with the full scan machinery, indexes and SQL included).

    Every equal-length result column becomes a table column in
    select-list order: int results land as int32 (range-checked, never
    silently wrapped), uint as uint32, floats as float32, and STRING
    columns re-encode with a fresh sorted dictionary saved as the new
    table's sidecar.  Scalar aggregate results build a 1-row table.
    ``positions`` (row provenance) is dropped.  An existing
    *dest_path* is refused (EEXIST) unless ``overwrite=True``.  Returns
    ``(dest_schema, n_rows)``."""
    from .heap import HeapSchema as _HS, build_heap_file
    from .strings import StringDict, save_dict
    if os.path.exists(dest_path) and not overwrite:
        raise StromError(17, f"CREATE TABLE AS: {dest_path} exists "
                             f"(overwrite=True replaces it)")
    out = sql_query(sql, source, schema, tables=tables, **run_kw)
    out.pop("_analyze", None)
    out.pop("_workers", None)      # scan telemetry, not data
    out.pop("positions", None)     # row provenance, not data
    # LEFT-join NULL indicators become REAL NULLS (round 5): the
    # unaliased dim payload labels ("<dim>.cK") turn into nullable
    # columns masked by their indicator, and the indicator column
    # drops.  Aliased payloads keep the indicator (the label link is
    # gone), preserving the round-4 int32-indicator behavior.
    null_of: dict = {}
    matched = out.get("matched")
    if matched is not None:
        m = ~np.asarray(matched).astype(bool)
        hits = [lbl for lbl in out if "." in lbl]
        if hits:
            for lbl in hits:
                null_of[lbl] = m
            out.pop("matched")
    for key in [k for k in out if k.startswith("matched_")]:
        dname = key[len("matched_"):]
        m = ~np.asarray(out[key]).astype(bool)
        hits = [lbl for lbl in out if lbl.startswith(dname + ".")]
        if hits:
            for lbl in hits:
                null_of[lbl] = m
            out.pop(key)
    cols, dts, dict_cols, nullable, nulls = [], [], {}, [], {}
    n_rows = None

    def add(label, arr, dt, mask):
        if mask is not None and mask.any():
            nulls[len(cols)] = mask
            nullable.append(True)
        else:
            nullable.append(False)
        cols.append(arr)
        dts.append(dt)

    for label, v in out.items():
        if v is None:
            # a NULL scalar aggregate (MIN over zero rows): the heap
            # format has no scalar NULL — refuse rather than silently
            # materializing SQL NULL as a real value
            raise StromError(22, f"CREATE TABLE AS: {label!r} is NULL "
                                 f"(aggregate over zero rows) — no NULL "
                                 f"scalar representation in the heap "
                                 f"format")
        arr = np.asarray([v]) if np.isscalar(v) else np.asarray(v)
        arr = arr.reshape(-1)
        if arr.dtype.kind in "US":     # string results re-encode below
            arr = arr.astype(object)
        if n_rows is None:
            n_rows = len(arr)
        elif len(arr) != n_rows:
            raise StromError(22, f"CREATE TABLE AS: column {label!r} "
                                 f"has {len(arr)} rows, expected "
                                 f"{n_rows} (mixed result faces)")
        mask = null_of.get(label)
        if arr.dtype.kind == "O":
            present = [x for x in arr if x is not None]
            if any(isinstance(x, str) for x in present):
                if len(present) != len(arr) or mask is not None:
                    raise StromError(22, f"CREATE TABLE AS: {label!r} "
                                         f"mixes strings and NULLs "
                                         f"(nullable string columns "
                                         f"are outside this subset)")
                d = StringDict(arr.tolist())
                dict_cols[len(cols)] = d
                add(label, d.encode(arr.tolist()), "uint32", None)
                continue
            # numeric object column with None holes (a nullable source
            # column projected through SQL): real NULLs round-trip
            om = np.array([x is None for x in arr], dtype=bool)
            mask = om if mask is None else (mask | om)
            isf = any(isinstance(x, float) for x in present)
            arr = np.array([0 if x is None else x for x in arr],
                           dtype=np.float64 if isf else np.int64)
        if arr.dtype.kind == "f":
            f32 = arr.astype(np.float32)
            if mask is not None:
                f32 = np.where(mask, np.float32(0), f32)
            add(label, f32, "float32", mask)
        elif arr.dtype.kind == "u":
            if len(arr) and int(arr.max()) > 0xFFFFFFFF:
                raise StromError(34, f"CREATE TABLE AS: {label!r} "
                                     f"exceeds uint32")
            add(label, arr.astype(np.uint32), "uint32", mask)
        else:
            live = arr if mask is None else arr[~mask]
            if len(live) and (int(live.min()) < -(1 << 31)
                              or int(live.max()) >= (1 << 31)):
                raise StromError(34, f"CREATE TABLE AS: {label!r} "
                                     f"exceeds int32")
            i32 = np.where(mask, 0, arr).astype(np.int32) \
                if mask is not None else arr.astype(np.int32)
            add(label, i32, "int32", mask)
    if not cols:
        raise StromError(22, "CREATE TABLE AS: the statement returned "
                             "no columns")
    dest_schema = _HS(n_cols=len(cols), visibility=False,
                      dtypes=tuple(dts),
                      nullable=tuple(nullable) if any(nullable)
                      else None)
    build_heap_file(dest_path, cols, dest_schema, nulls=nulls or None)
    for c, d in dict_cols.items():
        save_dict(dest_path, c, d)
    return dest_schema, n_rows
