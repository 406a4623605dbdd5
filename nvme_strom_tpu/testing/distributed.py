"""Multi-process distributed proof harness (jax.distributed, CPU backend).

The reference's multi-worker story is process-parallel PostgreSQL workers
sharing DSM state (`pgsql/nvme_strom.c:1057-1112`).  The TPU rebuild's
analog is multi-host SPMD: every process owns a slice of the global device
mesh and the framework's loaders/restores touch only **addressable** shards
(each host reads its own rows from its own storage).  Single-process mesh
tests cannot prove that posture — `addressable_devices_indices_map` covers
the whole array there — so this module launches real separate processes
connected through ``jax.distributed.initialize`` and runs, across them:

* sharded direct loading (:func:`..parallel.stream.load_pages_sharded`),
* the distributed scan step with cross-process psum
  (:func:`..parallel.dscan.make_distributed_scan_step`),
* the streamed scan fold (:func:`..parallel.stream.distributed_scan_filter`),
* sharded checkpoint restore (:func:`..data.checkpoint.restore_checkpoint`)
  verified against an independent byte-level oracle.

Every check validates content per addressable shard, so a process reading
another host's rows (or the wrong rows) fails loudly.

Used by ``tests/test_distributed.py`` and by ``__graft_entry__.
dryrun_multichip`` (2-process × n/2-device leg, VERDICT r1 #5).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

HEAP_NAME = "table.heap"
CKPT_NAME = "ck.strom"


# ---------------------------------------------------------------------------
# fixtures (parent side; numpy-only so the parent needs no live backend)
# ---------------------------------------------------------------------------

def _make_schema():
    from ..scan.heap import HeapSchema
    return HeapSchema(n_cols=2, visibility=True)


def prepare_fixtures(workdir: str, n_global_devices: int) -> None:
    """Write the shared on-disk inputs every worker reads:
    a page-formatted heap table (2 batches of pages per device) and a
    checkpoint with one dp-shardable leaf plus a scalar leaf."""
    from ..data.checkpoint import save_checkpoint
    from ..scan.heap import build_heap_file

    schema = _make_schema()
    n_pages = 2 * n_global_devices
    n_rows = schema.tuples_per_page * n_pages
    rng = np.random.default_rng(1234)
    cols = [rng.integers(-100, 100, n_rows).astype(np.int32),
            rng.integers(0, 50, n_rows).astype(np.int32)]
    build_heap_file(os.path.join(workdir, HEAP_NAME), cols, schema)

    tree = {"w": rng.standard_normal((4 * n_global_devices, 16))
                    .astype(np.float32),
            "step": np.int32(7)}
    save_checkpoint(os.path.join(workdir, CKPT_NAME), tree)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch(num_processes: int, devices_per_proc: int,
           workdir: Optional[str] = None, *,
           timeout: float = 420.0) -> List[Dict]:
    """Spawn *num_processes* worker processes over a shared coordinator and
    return their result dicts (one per process, in process-id order).

    Raises ``RuntimeError`` with the offending worker's log tail on any
    nonzero exit, missing result, or per-check failure."""
    own_dir = workdir is None
    if own_dir:
        workdir = tempfile.mkdtemp(prefix="strom_dist_")
    try:
        return _launch_in(num_processes, devices_per_proc, workdir, timeout)
    finally:
        if own_dir:
            import shutil
            shutil.rmtree(workdir, ignore_errors=True)


def _launch_in(num_processes: int, devices_per_proc: int, workdir: str,
               timeout: float) -> List[Dict]:
    prepare_fixtures(workdir, num_processes * devices_per_proc)
    port = _free_port()

    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    procs = []
    logs = []
    for pid in range(num_processes):
        log_path = os.path.join(workdir, f"worker_{pid}.log")
        logs.append(log_path)
        lf = open(log_path, "wb")
        procs.append((subprocess.Popen(
            [sys.executable, "-m", "nvme_strom_tpu.testing.distributed",
             str(pid), str(num_processes), str(devices_per_proc),
             str(port), workdir],
            env=env, cwd=_REPO_ROOT, stdout=lf, stderr=subprocess.STDOUT),
            lf))

    # poll ALL workers: a worker that dies mid-run (e.g. a failed assert
    # before a collective) leaves its peers blocked in the collective — a
    # sequential pid-order wait would burn the whole timeout on the hung
    # peer and blame ITS (clean) log.  First nonzero exit wins and the
    # rest are killed.
    deadline = time.monotonic() + timeout
    first_bad: Optional[int] = None
    try:
        while True:
            running = [pid for pid, (p, _lf) in enumerate(procs)
                       if p.poll() is None]
            for pid, (p, _lf) in enumerate(procs):
                if p.poll() is not None and p.returncode != 0 \
                        and first_bad is None:
                    first_bad = pid
            if first_bad is not None or not running:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"distributed workers {running} timed out after "
                    f"{timeout}s; log: {_tail(logs[running[0]])}")
            time.sleep(0.1)
    finally:
        for p, lf in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
            lf.close()
    if first_bad is not None:
        raise RuntimeError(
            f"distributed worker {first_bad} exited "
            f"rc={procs[first_bad][0].returncode}; "
            f"log: {_tail(logs[first_bad])}")

    results = []
    for pid, (p, _lf) in enumerate(procs):
        if p.returncode != 0:
            raise RuntimeError(
                f"distributed worker {pid} exited rc={p.returncode}; "
                f"log: {_tail(logs[pid])}")
        rpath = os.path.join(workdir, f"result_{pid}.json")
        if not os.path.exists(rpath):
            raise RuntimeError(f"worker {pid} wrote no result; "
                               f"log: {_tail(logs[pid])}")
        with open(rpath) as f:
            results.append(json.load(f))
    for r in results:
        if not r.get("ok"):
            raise RuntimeError(f"worker {r.get('process_id')} failed: {r}")
    return results


def _tail(path: str, n: int = 2500) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, 2)
            f.seek(max(f.tell() - n, 0))
            return f.read().decode("utf-8", "replace")
    except OSError:
        return "<no log>"


# ---------------------------------------------------------------------------
# worker (child process)
# ---------------------------------------------------------------------------

def _worker_main(process_id: int, num_processes: int, devices_per_proc: int,
                 port: int, workdir: str) -> None:
    # replace (not merely append) any inherited device-count flag: a parent
    # test process passes its own 8-device XLA_FLAGS down, and each worker
    # must own exactly devices_per_proc local devices
    import re
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags +
        f" --xla_force_host_platform_device_count={devices_per_proc}"
    ).strip()
    import jax
    # the virtual-device CPU mesh is the point of this harness: pin the
    # platform even where JAX_PLATFORMS names an accelerator
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=num_processes, process_id=process_id)

    # test hook: die between init and the first collective, so launch()'s
    # failure attribution (blame the dead worker, kill its blocked peer)
    # is exercisable.  os._exit, not sys.exit: a crash must not run jax's
    # atexit distributed-shutdown barrier, which would block THIS process
    # on its (soon to be hung) peer and invert the failure order
    if os.environ.get("STROM_TEST_DIE_AFTER_INIT") and process_id == 1:
        os._exit(41)

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..data.checkpoint import checkpoint_info, restore_checkpoint
    from ..engine import open_source
    from ..ops.filter_xla import decode_pages
    from ..parallel.dscan import make_distributed_scan_step
    from ..parallel.mesh import make_scan_mesh
    from ..parallel.stream import distributed_scan_filter, load_pages_sharded
    from ..scan.heap import PAGE_SIZE

    n_global = len(jax.devices())
    n_local = len(jax.local_devices())
    assert n_global == num_processes * devices_per_proc, \
        (n_global, num_processes, devices_per_proc)
    assert n_local == devices_per_proc, (n_local, devices_per_proc)

    schema = _make_schema()
    heap_path = os.path.join(workdir, HEAP_NAME)
    pages_np = np.fromfile(heap_path, np.uint8).reshape(-1, PAGE_SIZE)
    result = {"process_id": process_id, "n_global": n_global,
              "n_local": n_local, "checks": {}}

    # 1. sharded direct load: every addressable shard must hold exactly its
    #    own page rows (the multi-host "each host reads its own rows" claim)
    mesh = make_scan_mesh(jax.devices(), sp=1)
    with open_source(heap_path) as src:
        arr = load_pages_sharded(src, mesh)
    assert arr.shape == pages_np.shape
    seen_rows = 0
    for shard in arr.addressable_shards:
        rows = shard.index[0]
        got = np.asarray(shard.data)
        want = pages_np[rows]
        np.testing.assert_array_equal(got, want)
        seen_rows += got.shape[0]
    assert seen_rows == pages_np.shape[0] * n_local // n_global
    result["checks"]["sharded_load"] = seen_rows

    # 2. distributed scan step: dp×sp shardings with cross-process psum;
    #    oracle = eager single-device decode of the full table
    cols, valid = decode_pages(jnp.asarray(pages_np), schema)
    sel = np.asarray(valid & (cols[0] > 0))
    exp_count = int(sel.sum())
    exp_sums = [int(np.where(sel, np.asarray(c), 0).sum(dtype=np.int64))
                for c in cols]
    sp = 2 if n_global % 2 == 0 else 1
    run, smesh = make_distributed_scan_step(jax.devices(), sp=sp,
                                            schema=schema)
    out = run(pages_np, np.int32(0))
    got_count = int(np.asarray(out["count"]))
    got_sums = [int(v) for v in np.asarray(out["sums"])]
    assert got_count == exp_count, (got_count, exp_count)
    assert got_sums == exp_sums, (got_sums, exp_sums)
    result["checks"]["scan_step"] = {"count": got_count, "sp": sp}

    # 3. streamed fold: submit-ahead batches over the same mesh (exercises
    #    ShardedBatchStream's per-addressable-device DMA in multi-process)
    with open_source(heap_path) as src:
        folded = distributed_scan_filter(
            src, smesh, lambda a: run(a, np.int32(0)),
            batch_pages=n_global)
    # two batches of n_global pages cover the 2*n_global-page table once
    assert int(folded["count"]) == exp_count, \
        (int(folded["count"]), exp_count)
    result["checks"]["stream_fold"] = int(folded["count"])

    # 4. distributed sample sort: splitter election (all_gather) and the
    #    capacity-bounded bucket exchange (all_to_all) across REAL process
    #    boundaries — the collectives the psum-based checks don't touch
    from ..parallel.sort import make_distributed_sort
    rng = np.random.default_rng(99)
    svals = rng.integers(-10_000, 10_000, 64 * n_global).astype(np.int32)
    srun, _smesh = make_distributed_sort(jax.devices(),
                                         capacity=len(svals))
    sout = srun(svals)
    assert int(np.asarray(sout["n_dropped"])) == 0
    # counts are dp-sharded; gather the tiny vector so every process can
    # compute the global bucket boundaries, then check only its own
    # addressable value rows against the numpy oracle
    from jax.experimental import multihost_utils
    scounts = np.asarray(
        multihost_utils.process_allgather(sout["count"],
                                          tiled=True)).reshape(-1)
    sorted_all = np.sort(svals)
    bounds = np.concatenate([[0], np.cumsum(scounts)])
    for shard in sout["values"].addressable_shards:
        b = shard.index[0].start or 0
        got = np.asarray(shard.data).reshape(-1)[:scounts[b]]
        want = sorted_all[bounds[b]:bounds[b + 1]]
        np.testing.assert_array_equal(got, want)
    result["checks"]["dist_sort"] = int(scounts.sum())

    # 5. sharded checkpoint restore: dp-sharded leaf + replicated scalar;
    #    oracle = raw bytes straight from the file (no framework code)
    ck_path = os.path.join(workdir, CKPT_NAME)
    meta = checkpoint_info(ck_path)
    leaves = {e["key"]: e for e in meta["leaves"]}
    wmeta = leaves["['w']"]
    wshape = tuple(wmeta["shape"])
    raw_w = np.fromfile(ck_path, np.uint8,
                        count=wmeta["nbytes"],
                        offset=meta["data_offset"] + wmeta["offset"]
                        ).view(wmeta["dtype"]).reshape(wshape)
    sh = NamedSharding(mesh, P("dp", None))
    restored = restore_checkpoint(
        ck_path, shardings={"['w']": sh})
    rw = restored["['w']"]
    assert rw.shape == wshape
    for shard in rw.addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data),
                                      raw_w[shard.index[0]])
    # scalar leaf restores unsharded onto the local default device
    np.testing.assert_array_equal(np.asarray(restored["['step']"]),
                                  np.int32(7))
    result["checks"]["ckpt_restore"] = list(wshape)

    # 6. sharded checkpoint SAVE: each process writes only its own
    #    shards into one shared file (replicated leaf written once);
    #    oracle = raw bytes vs the deterministic global value
    from ..data.checkpoint import save_checkpoint_sharded
    wsave = (np.arange(np.prod(wshape), dtype=np.float32)
             .reshape(wshape) * 0.5)
    wsh = jax.make_array_from_callback(wshape, sh, lambda i: wsave[i])
    rsh = NamedSharding(mesh, P())
    rep = jax.make_array_from_callback(
        (3,), rsh, lambda i: np.arange(3, dtype=np.int32)[i])
    save_path = os.path.join(workdir, "saved.strom")
    save_checkpoint_sharded(save_path, {"w": wsh, "r": rep,
                                        "step": np.int32(11)})
    smeta = checkpoint_info(save_path)
    sl = {e["key"]: e for e in smeta["leaves"]}
    raw_saved = np.fromfile(save_path, np.float32,
                            count=int(np.prod(wshape)),
                            offset=smeta["data_offset"]
                            + sl["['w']"]["offset"]).reshape(wshape)
    np.testing.assert_array_equal(raw_saved, wsave)
    raw_rep = np.fromfile(save_path, np.int32, count=3,
                          offset=smeta["data_offset"]
                          + sl["['r']"]["offset"])
    np.testing.assert_array_equal(raw_rep, np.arange(3, dtype=np.int32))
    # roundtrip through the sharded restore
    back = restore_checkpoint(save_path, shardings={"['w']": sh})
    for shard in back["['w']"].addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data),
                                      wsave[shard.index[0]])
    result["checks"]["ckpt_save_sharded"] = list(wshape)

    # 7. partitioned hash join: build hash-sharded 1/dp per device,
    #    all_to_all row routing to key owners across REAL process
    #    boundaries, local sorted-probe, psum — the exchange-based join
    #    strategy end to end in multi-process
    from ..parallel.pjoin import make_partitioned_join_step
    jkeys = np.arange(-60, 60, dtype=np.int32)
    jstep = make_partitioned_join_step(mesh, schema, 0, jkeys,
                                       (jkeys * 3).astype(np.int32))
    jout = jstep(pages_np)
    exp_m = int((np.asarray(valid)
                 & np.isin(np.asarray(cols[0]), jkeys)).sum())
    got_m = int(np.asarray(jout["matched"]))
    assert got_m == exp_m, (got_m, exp_m)
    result["checks"]["pjoin"] = got_m

    # 7b. partitioned join ROW face across process boundaries (VERDICT
    #     r3 #3): each process sees only its ADDRESSABLE output shards —
    #     the outcomes of rows routed TO its devices — so the oracle per
    #     process is "valid matching rows whose key's hash owner is one
    #     of my dp indices", positions rejoined from the int32 words
    from ..ops.join import key_hash32
    from ..parallel.pjoin import (combine_pos_words,
                                  make_partitioned_join_rows_step)
    jrstep = make_partitioned_join_rows_step(
        mesh, schema, 0, jkeys, (jkeys * 3).astype(np.int32))
    jr = jrstep(pages_np)

    def by_dev(a):
        return {s.device: np.asarray(s.data)
                for s in a.addressable_shards}
    hits = by_dev(jr["hit"])
    los = by_dev(jr["pos_lo"])
    his = by_dev(jr["pos_hi"])
    mypos = [combine_pos_words(los[d][h.astype(bool)],
                               his[d][h.astype(bool)])
             for d, h in hits.items()]
    mypos = np.sort(np.concatenate(mypos))
    dp = mesh.shape["dp"]
    mesh_devs = list(mesh.devices.reshape(-1))
    my_idx = [i for i, d in enumerate(mesh_devs)
              if d.process_index == process_id]
    c0v = np.asarray(cols[0]).reshape(-1)
    vv = np.asarray(valid).reshape(-1)
    owner = (key_hash32(c0v) % np.uint32(dp)).astype(np.int64)
    exp_pos = np.flatnonzero(vv & np.isin(c0v, jkeys)
                             & np.isin(owner, my_idx))
    np.testing.assert_array_equal(mypos, exp_pos)
    result["checks"]["pjoin_rows"] = int(len(mypos))

    # 7c. value-keyed GROUP BY across process boundaries (round 4):
    #     pass 1 discovers the distinct keys per process from the shared
    #     table, pass 2 psum-folds over the real 2-process mesh — the
    #     replicated result must equal the global oracle on EVERY process
    from ..config import config as _gcfg
    from ..scan.query import Query
    gsnap = _gcfg.snapshot()
    try:
        _gcfg.set("debug_no_threshold", True)
        gout = Query(os.path.join(workdir, HEAP_NAME), schema) \
            .group_by_cols(1, agg_cols=[0]).run(mesh=mesh)
    finally:
        _gcfg.restore(gsnap)
    c1v = np.asarray(cols[1]).reshape(-1)
    vv2 = np.asarray(valid).reshape(-1).astype(bool)
    want_keys = np.unique(c1v[vv2])
    np.testing.assert_array_equal(np.asarray(gout["key_cols"][0]),
                                  want_keys)
    assert int(np.asarray(gout["count"]).sum()) == int(vv2.sum())
    result["checks"]["group_by_cols"] = int(len(want_keys))

    result["ok"] = True
    with open(os.path.join(workdir, f"result_{process_id}.json"), "w") as f:
        json.dump(result, f)
    jax.distributed.shutdown()


if __name__ == "__main__":
    _pid, _np_, _dpp, _port = (int(a) for a in sys.argv[1:5])
    _worker_main(_pid, _np_, _dpp, _port, sys.argv[5])
