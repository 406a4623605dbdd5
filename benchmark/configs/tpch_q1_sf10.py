"""tpch_q1_sf10: TPC-H Q1 over the tpch_sf10 LINEITEM heap.

Data: none of its own.  The table is the ``tpch_sf10`` configuration's
``lineitem.heap``, in that configuration's data directory beside this
one: ``tpch_sf10.Cell``'s own ``prepare`` builds it there when it is
missing or stale (same meta key), so a checkout holds one heap for the
Q6 and Q1 cells, and whichever runs second builds nothing.  The table's
columns, scale factor and seed come from ``tpch_sf10.json``.

Timed path: ``sql_query`` of Q1 (sec. 2.4.1) with the request's DELTA,
on the whole table: the value-keyed GROUP BY's key-discovery scan, then
the grouped expression kernel's pass.

Reference: a plain numpy Q1 over the reference's own draw of the seven
columns Q1 reads (``tpch_sf10.Lineitem``, nothing of the program),
regenerated after the window.  Every sum is aggregated once per (ship
day, return flag, line status) in int64 (``np.add.at``; never float64
``bincount`` weights, which lose the low digits near 1e18), and a
DELTA's answer is the int64 cumulative sum over the days up to its
cutoff.  The number compared is the count of queries whose group keys,
group order or any of the ten output fields differ: sums and counts
exactly, AVGs as the float64 quotient of the exact sum and count.
Limit 0.

Control (``ctx.control``): the reference in the program's place, on the
device in float32 (the step below the exact int64 decimals the
configuration states), so the sums, sum_charge first, come out wrong.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SHIP_END = "1998-12-01"          # l_shipdate <= SHIP_END - DELTA days
#: keys of tpch_sf10.json a run may override (a test's smaller table)
TABLE_KEYS = ("scale_factor", "data_seed")
COLS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate")
KEYS = ("l_returnflag", "l_linestatus")
SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge")
AVGS = ("avg_qty", "avg_price", "avg_disc")
FIELDS = KEYS + SUMS + AVGS + ("count_order",)
#: int64 words each grouped-kernel run writes: for each of the four
#: (returnflag, linestatus) groups and the key table's sentinel, a row
#: count and the sums of Q1's five distinct arguments (quantity, price,
#: discount and the two expressions)
KERNEL_OUT_WORDS = 5 * (1 + 5)


def _table_module():
    spec = importlib.util.spec_from_file_location(
        "bench_tpch_sf10_table", os.path.join(HERE, "tpch_sf10.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


T = _table_module()


def table_cfg(cfg: dict) -> dict:
    """``tpch_sf10.json``, with the table keys *cfg* overrides."""
    with open(os.path.join(HERE, cfg["table"] + ".json")) as f:
        tcfg = json.load(f)
    tcfg.update({k: cfg[k] for k in TABLE_KEYS if k in cfg})
    return tcfg


def cutoff(delta: int) -> int:
    return T.days(SHIP_END) - int(delta)


def q1_sql(tcfg: dict, delta: int) -> str:
    """Q1 with the request's DELTA (sec. 2.4.1.3)."""
    c = {n: f"c{i}" for i, (n, _) in enumerate(tcfg["columns"])}
    q, p, d, t = (c[n] for n in COLS[:4])
    rf, ls = c["l_returnflag"], c["l_linestatus"]
    return (f"SELECT {rf} AS l_returnflag, {ls} AS l_linestatus, "
            f"SUM({q}) AS sum_qty, SUM({p}) AS sum_base_price, "
            f"SUM({p} * (100 - {d})) AS sum_disc_price, "
            f"SUM({p} * (100 - {d}) * (100 + {t})) AS sum_charge, "
            f"AVG({q}) AS avg_qty, AVG({p}) AS avg_price, "
            f"AVG({d}) AS avg_disc, COUNT(*) AS count_order "
            f"FROM lineitem WHERE {c['l_shipdate']} <= {cutoff(delta)} "
            f"GROUP BY {rf}, {ls} ORDER BY {rf}, {ls}")


def reference_columns(tcfg: dict) -> dict:
    """The columns Q1 reads, the reference's own draw."""
    gen = T.Lineitem(tcfg)
    return {n: gen.column(n) for n in COLS}


def _groups(cols: dict):
    """(sorted (returnflag, linestatus) pairs, each row's pair index)."""
    pair = cols["l_returnflag"].astype(np.int64) * 256 + \
        cols["l_linestatus"]
    pairs, code = np.unique(pair, return_inverse=True)
    return pairs, code


class Q1Reference:
    """Plain Q1 at every DELTA: per (ship day, pair) int64 sums, then
    cumulative over the days."""

    def __init__(self, tcfg: dict):
        cols = reference_columns(tcfg)
        self.pairs, code = _groups(cols)
        ship = cols["l_shipdate"]
        self.day0 = int(ship.min())
        n_days = int(ship.max()) - self.day0 + 1
        n_pairs = len(self.pairs)
        cell = (ship - self.day0).astype(np.int64) * n_pairs + code
        price, disc = cols["l_extendedprice"], cols["l_discount"]
        disc_price = price * (100 - disc)
        values = (cols["l_quantity"], price, disc_price,
                  disc_price * (100 + cols["l_tax"]), disc)
        table = np.zeros((len(values) + 1, n_days * n_pairs), np.int64)
        for i, v in enumerate(values):
            np.add.at(table[i], cell, v.astype(np.int64))
        np.add.at(table[-1], cell, 1)
        self.cum = np.cumsum(table.reshape(-1, n_days, n_pairs), axis=1)
        if not (np.abs(self.cum[:, -1]) < 1 << 62).all():
            raise OverflowError("a Q1 group sum reached 2^62")

    def answer(self, delta: int) -> dict:
        i = min(cutoff(delta) - self.day0, self.cum.shape[1] - 1)
        s = self.cum[:, i] if i >= 0 else np.zeros_like(self.cum[:, 0])
        keep = s[-1] > 0
        qty, price, disc_price, charge, disc, count = (v[keep] for v in s)
        out = {"l_returnflag": self.pairs[keep] // 256,
               "l_linestatus": self.pairs[keep] % 256,
               "sum_qty": qty, "sum_base_price": price,
               "sum_disc_price": disc_price, "sum_charge": charge,
               "avg_qty": qty / count, "avg_price": price / count,
               "avg_disc": disc / count, "count_order": count}
        return {k: np.asarray(v).tolist() for k, v in out.items()}


class Float32Q1:
    """The control: Q1 over the reference's columns on the device, in
    float32."""

    def __init__(self, tcfg: dict, device):
        cols = reference_columns(tcfg)
        self.pairs, code = _groups(cols)
        put = lambda v: jax.device_put(v, device)    # noqa: E731
        self.ship = put(cols.pop("l_shipdate"))
        self.code = put(code.astype(np.int32))
        self.vals = [put(cols[n].astype(np.float32)) for n in COLS[:4]]
        self.fn = jax.jit(self._q1, static_argnums=(7,))

    @staticmethod
    def _q1(ship, code, qty, price, disc, tax, cut, n_pairs):
        jnp = jax.numpy
        hit = (code[:, None] == jnp.arange(n_pairs)) & (ship <= cut)[:, None]
        disc_price = price * (100.0 - disc)
        sums = [jnp.sum(jnp.where(hit, v[:, None], 0.0), axis=0)
                for v in (qty, price, disc_price,
                          disc_price * (100.0 + tax), disc)]
        return sums, jnp.sum(hit, axis=0, dtype=jnp.int32)

    def __call__(self, delta: int) -> dict:
        sums, count = self.fn(self.ship, self.code, *self.vals,
                              cutoff(delta), len(self.pairs))
        qty, price, disc_price, charge, disc = (np.asarray(s) for s in sums)
        count = np.asarray(count)
        keep = count > 0
        out = {"l_returnflag": self.pairs[keep] // 256,
               "l_linestatus": self.pairs[keep] % 256,
               "sum_qty": np.rint(qty[keep]).astype(np.int64),
               "sum_base_price": np.rint(price[keep]).astype(np.int64),
               "sum_disc_price": np.rint(disc_price[keep]).astype(np.int64),
               "sum_charge": np.rint(charge[keep]).astype(np.int64),
               "avg_qty": qty[keep] / count[keep],
               "avg_price": price[keep] / count[keep],
               "avg_disc": disc[keep] / count[keep],
               "count_order": count[keep]}
        return {k: np.asarray(v).tolist() for k, v in out.items()}


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.tcfg = table_cfg(ctx.cfg)
        tdir = os.path.join(os.path.dirname(ctx.data_dir), ctx.cfg["table"])
        os.makedirs(tdir, exist_ok=True)
        self.table = T.Cell(dataclasses.replace(ctx, cfg=self.tcfg,
                                                data_dir=tdir))
        self.path, self.warm_path = self.table.path, self.table.warm_path

    def prepare(self) -> None:
        """The statement first (a program that cannot parse Q1 fails
        before any table is built), then the shared table."""
        from nvme_strom_tpu.scan.sql import parse_sql
        if self.ctx.device.platform != "tpu" and \
                self.tcfg["scale_factor"] > 0.1:
            raise RuntimeError("tpch_q1_sf10 off the chip builds its table "
                               "at a test's scale factor, not "
                               f"{self.tcfg['scale_factor']}")
        parse_sql(q1_sql(self.tcfg, 90), self.path, self.table._schema())
        self.table.prepare()

    def open(self) -> None:
        from nvme_strom_tpu import config
        from nvme_strom_tpu.scan.sql import parse_sql
        for k, v in self.ctx.traffic.get("program_config", {}).items():
            config.set(k, v)
        self.schema = self.table._schema()
        q, _ = parse_sql(q1_sql(self.tcfg, 90), self.path, self.schema)
        plan = q.explain()
        self.plan = {"access_path": plan.access_path, "kernel": plan.kernel,
                     "mode": plan.mode}
        self.ctx.log(f"EXPLAIN Q1: {plan.kernel} kernel, {plan.access_path}"
                     f" path, {plan.mode} ({plan.reason})")
        self.control = Float32Q1(self.tcfg, self.ctx.device) \
            if self.ctx.control else None

    def counters(self) -> dict:
        from nvme_strom_tpu.stats import stats
        return dict(stats.snapshot().counters)

    def warm(self, space) -> None:
        """Every distinct Q1 of the mix on the warm table: the same
        programs, so the compile cache holds them all before the window."""
        from nvme_strom_tpu.scan.sql import sql_query
        for req in space:
            sql_query(q1_sql(self.tcfg, req["delta"]), self.warm_path,
                      self.schema)

    def serve(self, req) -> dict:
        from nvme_strom_tpu.scan.sql import sql_query
        if self.control is not None:
            return {"work": 1, "answer": self.control(req["delta"])}
        with jax.profiler.TraceAnnotation("bench.sql_query"):
            out = sql_query(q1_sql(self.tcfg, req["delta"]), self.path,
                            self.schema)
        return {"work": 1, "answer": {k: np.asarray(out[k]).tolist()
                                      for k in FIELDS}}

    def facts(self) -> dict:
        """What the group kernel's roofline reads: the pages a query
        scans and the words each kernel run writes."""
        return {"pages_per_request": self.table.meta["pages"],
                "page_bytes": self.tcfg["page_bytes"],
                "group_out_words": KERNEL_OUT_WORDS, **self.plan}

    def close(self) -> None:
        self.control = None

    def check(self, records) -> dict:
        """Every answer of the window against the reference (one table of
        cumulative sums serves the 61 DELTAs)."""
        ref = Q1Reference(self.tcfg)
        want: dict = {}
        wrong = 0
        for r in records:
            d = int(r["req"]["delta"])
            if d not in want:
                want[d] = ref.answer(d)
            wrong += r["answer"] != want[d]
        return {"wrong_answers": {"value": wrong, "limit": 0}}
