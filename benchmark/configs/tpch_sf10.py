"""tpch_sf10: TPC-H LINEITEM at scale factor 10, queried with Q6.

Data: ``lineitem.heap`` in the configuration's data directory, built once
per checkout from ``data_seed`` (dbgen's data is fixed per scale factor
too) by numpy draws that follow sec. 4.2.3 of the spec, and written with
the program's own heap writer (``scan.heap.build_heap_file``: loading
the table is the system's job).  ``warm.heap`` holds the table's first
``2048 + pages % 2048`` pages: the same page-batch shapes, for warm-up.

Timed path: ``sql_query`` of Q6 (sec. 2.4.6) with the request's
substitution parameters, on the whole table.

Columns: every LINEITEM column at no less than the width sec. 1.3.1
allows: decimals as int64 counts of 0.01, identifiers, integers and
dates (days) as int32, CHAR(1) flags as their byte in an int32, and the
text columns as their bytes in 8-byte words (``text_bytes``).

Reference: a plain numpy evaluation of Q6 over the same columns,
regenerated from ``data_seed`` after the window (nothing of the
program).  Decimals are scaled integers, so the answer is exact: the
number compared is the count of queries whose revenue differs, limit 0.

Control (``ctx.control``): the reference in the program's place, on the
device in float32 (the step below the exact int64 decimals the
configuration states): prices, discounts and their products rounded to
24 bits, so the revenues come out wrong.
"""

from __future__ import annotations

import json
import os

import jax
import numpy as np

EPOCH = np.datetime64("1970-01-01", "D")
ORDER_WINDOW_DAYS = 151          # O_ORDERDATE <= ENDDATE - 151 days
#: one independent stream per drawn quantity, so the reference draws only
#: the columns Q6 reads
STREAMS = ("lines", "orderdate", "partkey", "suppidx", "quantity",
           "discount", "tax", "shipoff", "commitoff", "receiptoff",
           "retflag", "instruct", "mode", "comment")
INSTRUCT = ("COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN")
MODES = ("AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK")
CENTS = 100                      # decimals are counts of 0.01
GEN_VERSION = 2
WARM_UNIT = 2048                 # pages; a multiple of every batch width


def days(iso: str) -> int:
    return int((np.datetime64(iso, "D") - EPOCH).astype(int))


START, END, CURRENT = days("1992-01-01"), days("1998-12-31"), \
    days("1995-06-17")


class Lineitem:
    """Column generator, lazy and memoised: ask for a column by name."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.n_orders = int(round(cfg["scale_factor"] * 1_500_000))
        self.sf = cfg["scale_factor"]
        seqs = np.random.SeedSequence(int(cfg["data_seed"])).spawn(
            len(STREAMS))
        self._seq = dict(zip(STREAMS, seqs))
        self._memo: dict = {}

    def rng(self, name):
        return np.random.default_rng(self._seq[name])

    def _ints(self, stream, lo, hi, n, dtype=np.int32):
        return self.rng(stream).integers(lo, hi + 1, n, dtype=dtype)

    def get(self, name: str) -> np.ndarray:
        if name not in self._memo:
            self._memo[name] = getattr(self, "_" + name)()
        return self._memo[name]

    # per order
    def _lines(self):
        return self._ints("lines", 1, 7, self.n_orders, np.int8)

    def _order_of_row(self):
        return np.repeat(np.arange(self.n_orders, dtype=np.int32),
                         self.get("lines"))

    def _orderdate(self):
        return self._ints("orderdate", START, END - ORDER_WINDOW_DAYS,
                          self.n_orders)

    @property
    def rows(self) -> int:
        return int(self.get("lines").sum(dtype=np.int64))

    # per row
    def _l_orderkey(self):
        i = self.get("order_of_row")
        return (i // 8) * 32 + (i % 8) + 1          # sparse keys

    def _l_partkey(self):
        return self._ints("partkey", 1, int(self.sf * 200_000), self.rows)

    def _l_suppkey(self):
        s = int(self.sf * 10_000)
        pk = self.get("l_partkey").astype(np.int64)
        i = self._ints("suppidx", 0, 3, self.rows).astype(np.int64)
        return ((pk + i * (s // 4 + (pk - 1) // s)) % s + 1).astype(np.int32)

    def _l_linenumber(self):
        lines = self.get("lines").astype(np.int64)
        starts = np.repeat(np.cumsum(lines) - lines, lines)
        return (np.arange(self.rows, dtype=np.int64) - starts + 1) \
            .astype(np.int32)

    def _quantity(self):
        return self._ints("quantity", 1, 50, self.rows)

    def _l_quantity(self):
        return self.get("quantity").astype(np.int64) * CENTS

    def _l_extendedprice(self):
        pk = self.get("l_partkey").astype(np.int64)
        retail_cents = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
        return self.get("quantity") * retail_cents

    def _l_discount(self):
        return self._ints("discount", 0, 10, self.rows, np.int64)

    def _l_tax(self):
        return self._ints("tax", 0, 8, self.rows, np.int64)

    def _l_shipdate(self):
        od = self.get("orderdate")[self.get("order_of_row")]
        return od + self._ints("shipoff", 1, 121, self.rows)

    def _l_commitdate(self):
        od = self.get("orderdate")[self.get("order_of_row")]
        return od + self._ints("commitoff", 30, 90, self.rows)

    def _l_receiptdate(self):
        return self.get("l_shipdate") + self._ints("receiptoff", 1, 30,
                                                   self.rows)

    def _l_returnflag(self):
        ra = np.where(self._ints("retflag", 0, 1, self.rows) == 0,
                      ord("A"), ord("R"))
        return np.where(self.get("l_receiptdate") <= CURRENT, ra,
                        ord("N")).astype(np.int32)

    def _l_linestatus(self):
        return np.where(self.get("l_shipdate") > CURRENT, ord("O"),
                        ord("F")).astype(np.int32)

    def _fixed_text(self, stream, values, nbytes):
        """CHAR(nbytes) drawn from *values*: (rows, words) bytes,
        space-padded to nbytes and zero-padded to the words."""
        lut = np.zeros((len(values), self._words(nbytes) * 8), np.uint8)
        for i, v in enumerate(values):
            lut[i, :nbytes] = np.frombuffer(v.ljust(nbytes).encode(),
                                            np.uint8)
        return lut[self._ints(stream, 0, len(values) - 1, self.rows)]

    def _text_l_shipinstruct(self):
        return self._fixed_text("instruct", INSTRUCT,
                                self.cfg["text_bytes"]["l_shipinstruct"])

    def _text_l_shipmode(self):
        return self._fixed_text("mode", MODES,
                                self.cfg["text_bytes"]["l_shipmode"])

    def _text_l_comment(self):
        """VARCHAR: 10..43 letters and spaces, zero-padded."""
        nbytes = self.cfg["text_bytes"]["l_comment"]
        n = self.rows
        lut = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz ", np.uint8)
        out = np.zeros((n, self._words(nbytes) * 8), np.uint8)
        rng = self.rng("comment")
        pos = np.arange(nbytes, dtype=np.int8)
        step = 1 << 22
        for lo in range(0, n, step):
            m = min(step, n - lo)
            sym = lut[rng.integers(0, len(lut), (m, nbytes), dtype=np.uint8)]
            length = rng.integers(10, nbytes, m, dtype=np.int8)
            out[lo:lo + m, :nbytes] = np.where(pos[None, :] < length[:, None],
                                               sym, 0)
        return out

    @staticmethod
    def _words(nbytes: int) -> int:
        return -(-nbytes // 8)

    def column(self, name: str) -> np.ndarray:
        """A stored column; text column ``<name>_<i>`` is its i-th 8-byte
        word, contiguous (the heap writer views each column whole)."""
        text, _, i = name.rpartition("_")
        if text in self.cfg["text_bytes"]:
            words = self.get("text_" + text).view("<i8")
            return np.ascontiguousarray(words[:, int(i)])
        return self.get(name)


def q6_sql(cfg: dict, req: dict) -> str:
    """Q6 with the request's substitution parameters (sec. 2.4.6.3)."""
    c = {n: f"c{i}" for i, (n, _) in enumerate(cfg["columns"])}
    y, d, q = int(req["year"]), int(req["discount"]), int(req["quantity"])
    return (f"SELECT SUM({c['l_extendedprice']} * {c['l_discount']}) "
            f"AS revenue FROM lineitem "
            f"WHERE {c['l_shipdate']} >= {days(f'{y}-01-01')} "
            f"AND {c['l_shipdate']} < {days(f'{y + 1}-01-01')} "
            f"AND {c['l_discount']} BETWEEN {d - 1} AND {d + 1} "
            f"AND {c['l_quantity']} < {q * CENTS}")


def q6_reference(cols: dict, req: dict, by_year: dict) -> int:
    """Plain Q6: revenue in units of 1e-4 dollars, exact.  *by_year* keeps
    each year's rows (discount, quantity, price) once they are cut out."""
    y, d, q = int(req["year"]), int(req["discount"]), int(req["quantity"])
    if y not in by_year:
        ship = cols["l_shipdate"]
        inyear = (ship >= days(f"{y}-01-01")) & (ship < days(f"{y + 1}-01-01"))
        by_year[y] = tuple(cols[n][inyear] for n in (
            "l_discount", "l_quantity", "l_extendedprice"))
    disc, qty, price = by_year[y]
    sel = (disc >= d - 1) & (disc <= d + 1) & (qty < q * CENTS)
    return int(np.dot(price[sel].astype(np.int64), disc[sel].astype(np.int64)))


def reference_columns(cfg: dict) -> dict:
    """The columns Q6 reads, the reference's own draw."""
    gen = Lineitem(cfg)
    return {n: gen.column(n) for n in ("l_shipdate", "l_discount",
                                       "l_quantity", "l_extendedprice")}


class Float32Q6:
    """The control: Q6 over the reference's columns on the device, in
    float32."""

    def __init__(self, cfg: dict, device):
        cols = reference_columns(cfg)
        self.ship = jax.device_put(cols.pop("l_shipdate"), device)
        self.cols = {n: jax.device_put(v.astype(np.float32), device)
                     for n, v in cols.items()}
        self.fn = jax.jit(self._q6)

    @staticmethod
    def _q6(ship, disc, qty, price, lo, hi, d, q):
        sel = (ship >= lo) & (ship < hi) & (disc >= d - 1) \
            & (disc <= d + 1) & (qty < q)
        return jax.numpy.sum(jax.numpy.where(sel, price * disc, 0.0))

    def __call__(self, req) -> int:
        y, d, q = int(req["year"]), int(req["discount"]), int(req["quantity"])
        c = self.cols
        got = self.fn(self.ship, c["l_discount"], c["l_quantity"],
                      c["l_extendedprice"], days(f"{y}-01-01"),
                      days(f"{y + 1}-01-01"), np.float32(d),
                      np.float32(q * CENTS))
        return int(round(float(got)))


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.cfg
        self.path = os.path.join(ctx.data_dir, "lineitem.heap")
        self.warm_path = os.path.join(ctx.data_dir, "warm.heap")
        self.meta_path = os.path.join(ctx.data_dir, "meta.json")
        self.meta = None

    def _key(self) -> dict:
        c = self.cfg
        return {"gen": GEN_VERSION, "sf": c["scale_factor"],
                "seed": c["data_seed"], "columns": c["columns"],
                "text": c["text_bytes"], "page": c["page_bytes"]}

    def _schema(self):
        from nvme_strom_tpu.scan.heap import HeapSchema
        cols = self.cfg["columns"]
        return HeapSchema(n_cols=len(cols), visibility=False,
                          dtypes=tuple(d for _, d in cols))

    def prepare(self) -> None:
        try:
            with open(self.meta_path) as f:
                meta = json.load(f)
            if meta["key"] != self._key() or any(
                    os.path.getsize(p) != meta[k] for p, k in
                    ((self.path, "bytes"), (self.warm_path, "warm_bytes"))):
                meta = None
        except (OSError, ValueError, KeyError):
            meta = None
        if meta is None:
            meta = self._build()
        self.meta = meta

    def _build(self) -> dict:
        from nvme_strom_tpu.scan.heap import build_heap_file
        gen = Lineitem(self.cfg)
        with jax.profiler.TraceAnnotation("bench.datagen"):
            cols = [gen.column(n) for n, _ in self.cfg["columns"]]
        self.ctx.log(f"building {gen.rows} rows into {self.path}")
        tmp = self.path + ".tmp"
        pages = build_heap_file(tmp, cols, self._schema())
        del cols, gen
        # write the table back in set-up, not under the first window
        fd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
        os.replace(tmp, self.path)
        page = self.cfg["page_bytes"]
        warm = WARM_UNIT + pages % WARM_UNIT
        with open(self.path, "rb") as f, open(self.warm_path + ".tmp",
                                               "wb") as g:
            g.write(f.read(min(warm, pages) * page))
        os.replace(self.warm_path + ".tmp", self.warm_path)
        meta = {"key": self._key(), "pages": pages,
                "bytes": os.path.getsize(self.path),
                "warm_bytes": os.path.getsize(self.warm_path)}
        with open(self.meta_path, "w") as f:
            json.dump(meta, f)
        return meta

    def open(self) -> None:
        from nvme_strom_tpu import config
        from nvme_strom_tpu.scan.sql import parse_sql
        for k, v in self.ctx.traffic.get("program_config", {}).items():
            config.set(k, v)
        self.schema = self._schema()
        req = {"year": 1994, "discount": 6, "quantity": 24}
        q, _ = parse_sql(q6_sql(self.cfg, req), self.path, self.schema)
        plan = q.explain()
        self.plan = {"access_path": plan.access_path, "kernel": plan.kernel,
                     "mode": plan.mode}
        self.ctx.log(f"EXPLAIN Q6: {plan.kernel} kernel, {plan.access_path}"
                     f" path, {plan.mode} ({plan.reason})")
        self.control = Float32Q6(self.cfg, self.ctx.device) \
            if self.ctx.control else None

    def counters(self) -> dict:
        from nvme_strom_tpu.stats import stats
        return dict(stats.snapshot().counters)

    def warm(self, space) -> None:
        """Every distinct Q6 of the mix on the warm table: the same
        programs, so the compile cache holds them all before the window."""
        from nvme_strom_tpu.scan.sql import sql_query
        for req in space:
            sql_query(q6_sql(self.cfg, req), self.warm_path, self.schema)

    def serve(self, req) -> dict:
        from nvme_strom_tpu.scan.sql import sql_query
        if self.control is not None:
            return {"work": 1, "revenue": self.control(req)}
        with jax.profiler.TraceAnnotation("bench.sql_query"):
            out = sql_query(q6_sql(self.cfg, req), self.path, self.schema)
        rev = int(np.asarray(out["revenue"]).reshape(-1)[0])
        return {"work": 1, "revenue": rev}

    def facts(self) -> dict:
        return {"pages_per_request": self.meta["pages"],
                "page_bytes": self.cfg["page_bytes"], **self.plan}

    def close(self) -> None:
        self.control = None

    def check(self, records) -> dict:
        """Every answer of the window against the reference, which runs
        once per distinct request (the mix has 80)."""
        cols = reference_columns(self.cfg)
        by_year: dict = {}
        want: dict = {}
        wrong = 0
        for r in records:
            key = tuple(sorted(r["req"].items()))
            if key not in want:
                want[key] = q6_reference(cols, r["req"], by_year)
            wrong += r["revenue"] != want[key]
        return {"wrong_revenue": {"value": wrong, "limit": 0}}
