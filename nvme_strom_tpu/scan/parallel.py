"""Multi-worker parallel scan: shared cursor across processes.

Capability analog of the pgsql Gather integration (`pgsql/nvme_strom.c:
582-595,1057-1112`): a DSM segment carries the scan descriptor (relation
id, total blocks, a shared atomic cursor, shared DMA counters) and every
worker claims disjoint block ranges from it.  Here the descriptor lives
in ``multiprocessing.shared_memory`` and workers are processes running
their own :class:`~nvme_strom_tpu.scan.executor.TableScanner` against
the shared cursor — the same data-parallel shape, minus the PostgreSQL
executor.

Planner-integrated since round 5: ``Query(..., workers=N).run()`` (or
``run(workers=N)`` / ``sql_query(..., workers=N)`` / ``strom_query
--workers N``) ships a picklable spec (structured filters, SQL predicate
trees, terminal, resolved GROUP BY keys) to N spawned processes via
:func:`run_query_workers`; each rebuilds the query
(`Query._from_worker_spec`), scans chunks claimed from the shared
cursor with its OWN Session, and the leader folds the partial results
exactly like the batch fold (`Query._run_workers`).
"""

from __future__ import annotations

import multiprocessing as mp
import struct
from multiprocessing import shared_memory
from typing import List, Optional, Tuple

__all__ = ["SharedCursor", "run_query_workers", "parallel_scan"]

_HDR = struct.Struct("<qq")  # next_chunk, n_chunks


class SharedCursor:
    """Cross-process atomic chunk cursor (the DSM ``nsp_cblock`` analog).

    Safe under the ``spawn`` start method: workers re-attach by name and
    share the externally-provided lock (fork is unusable once a PJRT
    backend has initialized in the parent)."""

    def __init__(self, n_chunks: int, *, name: Optional[str] = None,
                 create: bool = True, lock=None):
        if create:
            self._shm = shared_memory.SharedMemory(create=True, size=_HDR.size)
            _HDR.pack_into(self._shm.buf, 0, 0, n_chunks)
        else:
            assert name is not None
            self._shm = shared_memory.SharedMemory(name=name)
        self._lock = lock if lock is not None else mp.Lock()
        self.name = self._shm.name

    @property
    def n_chunks(self) -> int:
        return _HDR.unpack_from(self._shm.buf, 0)[1]

    def claim(self, count: int) -> Tuple[int, int]:
        with self._lock:
            nxt, total = _HDR.unpack_from(self._shm.buf, 0)
            n = min(count, total - nxt)
            if n <= 0:
                return nxt, 0
            _HDR.pack_into(self._shm.buf, 0, nxt + n, total)
            return nxt, n

    def reset(self) -> None:
        """Rewind the shared cursor for a rescan (ExecReScan in parallel
        mode reinitializes the DSM block counter)."""
        with self._lock:
            _, total = _HDR.unpack_from(self._shm.buf, 0)
            _HDR.pack_into(self._shm.buf, 0, 0, total)

    def close(self, *, unlink: bool = False) -> None:
        self._shm.close()
        if unlink:
            try:
                self._shm.unlink()
            except FileNotFoundError:
                pass


def _query_worker(spec: dict, cursor_name: str, lock, out_q) -> None:
    """Worker entry (spawned process): rebuild the query from the spec,
    scan shared-cursor chunks, report the picklable partial."""
    import os
    if spec.get("_test_crash_worker"):
        # test hook: die like an OOM-kill/segfault — no report, no
        # cleanup — so the leader's death detection is testable in CI
        os._exit(42)
    # workers compute on the host CPU (EXPLAIN and --workers say so): a
    # chip belongs to one process, and the leader may hold it
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cursor = None
    try:
        # mirror the leader's runtime state BEFORE building anything:
        # the x64 flag changes accumulator widths (acc_dtypes) and the
        # config snapshot carries the scan/join knobs — a worker running
        # defaults would fold silently different partials
        import jax
        jax.config.update("jax_enable_x64", bool(spec.get("x64")))
        if spec.get("config") is not None:
            from ..config import config
            config.restore(spec["config"])
        cursor = SharedCursor(0, name=cursor_name, create=False,
                              lock=lock)
        from .query import Query
        q = Query._from_worker_spec(spec)
        out_q.put(("ok", q._run_worker_partial(spec, cursor)))
    except BaseException as e:  # noqa: BLE001 — worker must always report
        out_q.put(("err", repr(e)))
    finally:
        if cursor is not None:
            cursor.close()


def run_query_workers(spec: dict, n_workers: int, *,
                      timeout_s: float = 600.0) -> List[dict]:
    """Fan a worker spec out to *n_workers* spawned processes sharing one
    cursor; returns each worker's partial result (the leader folds).
    The cursor is sized by ``executor.cursor_chunk_count`` — the SAME
    formula ``TableScanner`` sizes its own cursor with."""
    import os

    from .executor import cursor_chunk_count
    if n_workers < 2:
        raise ValueError("run_query_workers needs >= 2 workers")
    size = os.path.getsize(spec["source"])
    total = cursor_chunk_count(size, spec["chunk_size"])
    ctx = mp.get_context("spawn")
    lock = ctx.Lock()
    cursor = SharedCursor(total, lock=lock)
    q = ctx.Queue()
    procs = [ctx.Process(target=_query_worker,
                         args=(spec, cursor.name, lock, q))
             for _ in range(n_workers)]
    import queue as _queue
    import time as _time
    try:
        for p in procs:
            p.start()
        results: List[dict] = []
        errors: List[str] = []
        # poll instead of one blocking get: a worker killed by the OOM
        # killer (or a segfault) never reports, and a bare
        # q.get(timeout=600) would sit out the whole deadline.  Short
        # get timeouts + liveness checks surface the death in seconds,
        # with a small grace window for the queue feeder thread to flush
        # a report that raced the exit.
        deadline = _time.monotonic() + timeout_s
        grace_until = None
        while len(results) + len(errors) < len(procs):
            try:
                kind, payload = q.get(timeout=0.25)
            except _queue.Empty:
                now = _time.monotonic()
                reported = len(results) + len(errors)
                if now > deadline:
                    raise RuntimeError(
                        f"parallel scan timed out after {timeout_s:.0f}s: "
                        f"{len(procs) - reported} worker(s) never reported")
                alive = sum(p.is_alive() for p in procs)
                if alive < len(procs) - reported:
                    if grace_until is None:
                        grace_until = now + 2.0
                    elif now > grace_until:
                        dead = [(p.pid, p.exitcode) for p in procs
                                if not p.is_alive()]
                        raise RuntimeError(
                            "parallel scan worker died without reporting "
                            f"(pid, exitcode of exited workers: {dead}); "
                            f"{reported}/{len(procs)} partials received")
                else:
                    grace_until = None
                continue
            (results if kind == "ok" else errors).append(payload)
        for p in procs:
            p.join(timeout=60)
        if errors:
            raise RuntimeError(f"parallel scan worker failed: {errors[0]}")
        return results
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
        cursor.close(unlink=True)


def parallel_scan(path: str, *, n_workers: int = 2,
                  chunk_size: int = 1 << 20,
                  threshold: int = 0) -> dict:
    """Back-compat demo face (subsumed by ``Query(..., workers=N)``):
    scan *path* with ``n_workers`` processes sharing one cursor over the
    demo filter (count rows with col0 > threshold, sum col1 over them);
    returns summed count/sum plus the worker count.  Unlike the old
    standalone harness this rides the planner-integrated path, so the
    sub-chunk tail IS covered."""
    from ..config import config
    from .heap import HeapSchema
    from .query import Query
    schema = HeapSchema(n_cols=2, visibility=True)
    q = Query(path, schema).where_range(0, threshold + 1, None) \
        .aggregate(cols=[1])
    prev = config.get("chunk_size")
    config.set("chunk_size", chunk_size)
    try:
        out = q.run(workers=n_workers)
    finally:
        config.set("chunk_size", prev)
    return {"count": int(out["count"]) if out else 0,
            "sum": int(out["sums"][0]) if out else 0,
            "workers": n_workers}
