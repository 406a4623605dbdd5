"""tpch_q6_sf10_mesh4: TPC-H Q6 over the tpch_sf10 LINEITEM heap, each
query sharded over a four-chip host.

Data: the table is the ``tpch_sf10`` configuration's ``lineitem.heap``,
in that configuration's data directory beside this one:
``tpch_sf10.Cell``'s own ``prepare`` builds it there when it is missing
or stale (same meta key), so a checkout holds one heap for every scan
cell.  The table's columns, scale factor and seed come from
``tpch_sf10.json``.  This configuration keeps only a warm copy in its own
data directory: the table's first ``step_pages + pages % step_pages``
pages, one full mesh step and a tail as long as the table's, so the
warm-up compiles exactly the programs the window runs (the mesh step's
kernel and the tail's local batches).

Timed path: ``sql_query(..., mesh=mesh)`` of Q6 (sec. 2.4.6) with the
request's substitution parameters, on the whole table; the mesh is
``make_scan_mesh(ctx.devices, sp=1)``, so dp is the cell's chip count.

Reference: ``tpch_sf10``'s plain numpy Q6 over the reference's own draw
of the four columns Q6 reads (nothing of the program), regenerated after
the window; the number compared is the count of queries whose revenue
differs, limit 0.

Control (``ctx.control``): ``tpch_sf10``'s float32 Q6 on one chip, in the
program's place, so the revenues come out wrong.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
#: keys of tpch_sf10.json a run may override (a test's smaller table)
TABLE_KEYS = ("scale_factor", "data_seed")
#: Q6's parameters for the EXPLAIN logged at open
EXPLAIN_REQ = {"year": 1994, "discount": 6, "quantity": 24}


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


def warm_pages(pages: int, step: int) -> int:
    """One full step and a tail of the table's length (all of a table
    shorter than that)."""
    return min(pages, step + pages % step)


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.tcfg = table_cfg(ctx.cfg)
        tdir = os.path.join(os.path.dirname(ctx.data_dir), ctx.cfg["table"])
        os.makedirs(tdir, exist_ok=True)
        self.table = T.Cell(dataclasses.replace(ctx, cfg=self.tcfg,
                                                data_dir=tdir))
        self.path = self.table.path
        self.warm_path = os.path.join(ctx.data_dir, "warm.heap")
        self.meta_path = os.path.join(ctx.data_dir, "meta.json")

    def prepare(self) -> None:
        """The shared table, then this cell's warm copy of its head."""
        if self.ctx.device.platform != "tpu" and \
                self.tcfg["scale_factor"] > 0.1:
            raise RuntimeError("tpch_q6_sf10_mesh4 off the chip builds its "
                               "table at a test's scale factor, not "
                               f"{self.tcfg['scale_factor']}")
        self.table.prepare()
        tmeta = self.table.meta
        n = warm_pages(tmeta["pages"], int(self.ctx.cfg["step_pages"]))
        page = self.tcfg["page_bytes"]
        key = {"table": tmeta["key"], "table_bytes": tmeta["bytes"],
               "warm_pages": n}
        try:
            with open(self.meta_path) as f:
                meta = json.load(f)
            if meta["key"] != key or \
                    os.path.getsize(self.warm_path) != n * page:
                meta = None
        except (OSError, ValueError, KeyError):
            meta = None
        if meta is None:
            with open(self.path, "rb") as f, \
                    open(self.warm_path + ".tmp", "wb") as g:
                g.write(f.read(n * page))
            os.replace(self.warm_path + ".tmp", self.warm_path)
            with open(self.meta_path, "w") as f:
                json.dump({"key": key}, f)

    def open(self) -> None:
        from nvme_strom_tpu import config
        from nvme_strom_tpu.parallel.mesh import make_scan_mesh
        from nvme_strom_tpu.scan.sql import parse_sql
        for k, v in self.ctx.traffic.get("program_config", {}).items():
            config.set(k, v)
        self.schema = self.table._schema()
        self.mesh = make_scan_mesh(self.ctx.devices,
                                   sp=int(self.ctx.cfg["mesh"]["sp"]))
        q, _ = parse_sql(T.q6_sql(self.tcfg, EXPLAIN_REQ), self.path,
                         self.schema)
        plan = q.explain(mesh=self.mesh)
        self.plan = {"access_path": plan.access_path, "kernel": plan.kernel,
                     "mode": plan.mode, "dp": int(self.mesh.shape["dp"])}
        self.ctx.log(f"EXPLAIN Q6: {plan.kernel} kernel, {plan.access_path}"
                     f" path, {plan.mode} mode over dp="
                     f"{self.mesh.shape['dp']} ({plan.reason})")
        self.control = T.Float32Q6(self.tcfg, self.ctx.device) \
            if self.ctx.control else None

    def counters(self) -> dict:
        from nvme_strom_tpu.stats import stats
        return dict(stats.snapshot().counters)

    def warm(self, space) -> None:
        """Every distinct Q6 of the mix on the warm copy: the same
        programs, so the compile cache holds them all before the window."""
        from nvme_strom_tpu.scan.sql import sql_query
        for req in space:
            sql_query(T.q6_sql(self.tcfg, req), self.warm_path, self.schema,
                      mesh=self.mesh)

    def serve(self, req) -> dict:
        from nvme_strom_tpu.scan.sql import sql_query
        if self.control is not None:
            return {"work": 1, "revenue": self.control(req)}
        with jax.profiler.TraceAnnotation("bench.sql_query"):
            out = sql_query(T.q6_sql(self.tcfg, req), self.path, self.schema,
                            mesh=self.mesh)
        return {"work": 1,
                "revenue": int(np.asarray(out["revenue"]).reshape(-1)[0])}

    def facts(self) -> dict:
        return {"pages_per_request": self.table.meta["pages"],
                "page_bytes": self.tcfg["page_bytes"], **self.plan}

    def close(self) -> None:
        self.control = None

    def check(self, records) -> dict:
        """Every answer of the window against the reference
        (``tpch_sf10``'s check: ``wrong_revenue``, limit 0)."""
        return self.table.check(records)
