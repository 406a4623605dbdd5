"""Direct-to-HBM checkpoint restore (and the matching writer).

The reference has no checkpoint subsystem (SURVEY.md SS5.4: stateless data
path) — but restoring model state from NVMe into device memory is the
flagship *use* of an SSD→HBM direct path on TPU, so this tier exceeds the
reference rather than mirroring it.  Restore streams every tensor through
the same pinned-staging/merge-planned DMA engine as the scan path; a
sharded restore reads only the byte ranges owned by this process's
addressable devices (the multi-host posture of `parallel/stream.py`).

On-disk layout (single file)::

    [ header: magic u64 | json_len u64 | header json, padded to 4096 ]
    [ leaf 0 bytes, padded to 4096 ]
    [ leaf 1 bytes, padded to 4096 ] ...

Header json: ``{version, leaves: [{key, dtype, shape, offset, nbytes,
crc32c?}]}``.  Leaf offsets are 4096-aligned so restores ride the O_DIRECT
path with a 4KB chunk grid that the planner merges into ``dma_max_size``
requests (`engine.plan_requests`).

``crc32c`` (ISSUE 11) is the per-leaf checksum of the exact serialized
bytes (padding excluded), written by :func:`save_checkpoint`;
``restore_checkpoint(verify=True)`` and ``strom_ckpt verify`` recompute it
so a torn write, bit rot, or a truncated leaf surfaces as EBADMSG instead
of silently-wrong weights.  Sharded saves omit it (no process sees a whole
leaf), so verification is when-present: headers without the key — older
files or sharded saves — still restore.
"""

from __future__ import annotations

import errno as _errno
import json
import os
import struct
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..api import StromError
from ..tiering import extent_space
from ..engine import Session, open_source, read_chunk_ids
from ..hbm.staging import default_device, safe_device_put
from ..scan.heap import crc32c as _leaf_crc, crc32c_update as _leaf_crc_update

__all__ = ["save_checkpoint", "save_checkpoint_sharded",
           "restore_checkpoint", "checkpoint_info"]

_MAGIC = 0x53544B50_54505531  # "STKP" "TPU1"
_ALIGN = 4096
# temp litter younger than this may be a live concurrent save; only
# older files are swept (an in-flight writer touches its temp constantly)
_TMP_SWEEP_AGE_S = 3600.0


def _read_umask() -> int:
    """Current process umask WITHOUT the mutating os.umask(0) dance (which
    opens a world-writable window for other threads): Linux exposes it in
    /proc/self/status.  Falls back to the import-time snapshot below."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("Umask:"):
                    return int(line.split()[1], 8)
    except (OSError, ValueError, IndexError):
        pass
    return _UMASK_AT_IMPORT


def _umask_at_import() -> int:
    u = os.umask(0)   # import runs single-threaded; window is confined
    os.umask(u)
    return u


_UMASK_AT_IMPORT = _umask_at_import()
_CHUNK = 4096          # restore chunk grid; contiguous ids merge to dma_max
_VERSION = 1


def _pad(n: int, align: int = _ALIGN) -> int:
    return (n + align - 1) // align * align


def _flatten(tree) -> List:
    import jax
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in leaves]


# -- save --------------------------------------------------------------------

def save_checkpoint(path: str, tree: Any, *, direct: bool = False,
                    session: Optional[Session] = None,
                    staging_bytes: int = 64 << 20) -> Dict:
    """Serialize a pytree of (fully addressable) arrays.

    Default writer is ordinary buffered I/O + fsync.  ``direct=True``
    streams leaf bytes through pinned buffers and the engine's async
    RAM→SSD write path (``memcpy_ram2ssd``) — O_DIRECT, merge-planned,
    page-cache-free — which keeps a large save from evicting the page
    cache the rest of the host is using.

    Crash-safe: bytes land in a same-directory temp file that is fsynced
    and atomically renamed over *path* — a failure mid-save never
    corrupts an existing checkpoint at *path*.
    """
    import jax

    flat = _flatten(tree)
    for key, leaf in flat:
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            raise StromError(_errno.EINVAL,
                             f"leaf {key} is not fully addressable from this "
                             f"process; gather before saving, or use "
                             f"save_checkpoint_sharded")
    entries = _entries_for(flat)
    # per-leaf crc32c (ISSUE 11): the header precedes the data on disk,
    # so checksums come from a pre-pass — one leaf materialized at a
    # time, the same peak host memory as the writer loop below
    for e, (key, leaf) in zip(entries, flat):
        e["crc32c"] = _leaf_crc(_leaf_bytes(leaf, e))
    header = json.dumps({"version": _VERSION, "leaves": entries}).encode()
    header_len = _pad(16 + len(header))
    end = header_len + (entries[-1]["offset"] + _pad(entries[-1]["nbytes"])
                        if entries else 0)
    # write through symlinks ('latest.strom -> step-N.strom' layouts):
    # os.replace on the link path would swap the link for a regular file
    # and leave the target stale
    path = os.path.realpath(path)
    directory = os.path.dirname(path) or "."
    base = os.path.basename(path)
    # sweep temp litter from hard-killed saves (checkpoint-sized files
    # nothing else would ever reclaim) — but only litter OLD enough that
    # it cannot be a concurrent saver's in-flight temp
    now = time.time()
    for stale in os.listdir(directory):
        if stale.startswith(base + ".tmp.") \
                or stale == base + ".shared_tmp":
            sp = os.path.join(directory, stale)
            try:
                if now - os.path.getmtime(sp) > _TMP_SWEEP_AGE_S:
                    os.unlink(sp)
            except OSError:
                pass
    # mkstemp: unique per save, so concurrent savers to one path cannot
    # truncate each other's in-flight temp (same pattern as stats.export)
    tmp_fd, tmp = tempfile.mkstemp(dir=directory, prefix=base + ".tmp.")
    try:
        # mkstemp's 0600 would stick after the rename; honor the umask
        # like a plain open(path, 'wb') writer would
        os.fchmod(tmp_fd, 0o666 & ~_read_umask())
        with os.fdopen(tmp_fd, "wb") as f:
            f.write(struct.pack("<QQ", _MAGIC, len(header)))
            f.write(header)
            f.write(b"\0" * (header_len - 16 - len(header)))
            if not direct:
                # stream one leaf at a time: peak extra host memory = one
                # leaf
                for e, (key, leaf) in zip(entries, flat):
                    f.seek(header_len + e["offset"])
                    f.write(_leaf_bytes(leaf, e))
            f.truncate(_pad(end))
            f.flush()
            os.fsync(f.fileno())
        if direct:
            _save_leaves_direct(tmp, entries, flat, header_len,
                                session, staging_bytes)
        os.replace(tmp, path)
        # the rename just installed new bytes under the old identity:
        # drop any residency-tier extents over this path (ISSUE 9)
        extent_space.invalidate_paths([path])
        try:
            dirfd = os.open(directory, os.O_RDONLY)
            try:
                os.fsync(dirfd)     # persist the rename itself
            finally:
                os.close(dirfd)
        except OSError:
            # the checkpoint IS installed at this point; a directory-fsync
            # refusal (weird fs, EACCES) only weakens rename durability —
            # failing the whole save here would misreport installed state
            pass
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return {"path": path, "leaves": len(entries), "bytes": _pad(end)}


def _save_leaves_direct(path, entries, flat, header_len,
                        session, staging_bytes) -> None:
    """Write every leaf via the engine's async O_DIRECT write path."""
    own = session is None
    sess = session or Session()
    staging_bytes = _pad(staging_bytes, _CHUNK)
    try:
        with open_source(path, writable=True) as sink:
            handle, buf = sess.alloc_dma_buffer(staging_bytes)
            try:
                for e, (key, leaf) in zip(entries, flat):
                    arr = np.ascontiguousarray(np.asarray(leaf))
                    blob = arr.reshape(-1).view(np.uint8) if arr.shape \
                        else np.frombuffer(arr.tobytes(), np.uint8)
                    base = header_len + e["offset"]  # _ALIGN-aligned
                    done = 0
                    while done < e["nbytes"]:
                        take = min(staging_bytes, e["nbytes"] - done)
                        padded = _pad(take, _CHUNK)
                        staged = np.frombuffer(buf.view()[:padded], np.uint8)
                        staged[:take] = blob[done:done + take]
                        staged[take:] = 0
                        c0 = (base + done) // _CHUNK
                        ids = list(range(c0, c0 + padded // _CHUNK))
                        res = sess.memcpy_ram2ssd(sink, handle, ids, _CHUNK)
                        sess.memcpy_wait(res.dma_task_id)
                        done += take
            finally:
                sess.unmap_buffer(handle)
                buf.close()
            sink.sync()
    finally:
        if own:
            sess.close()


def _pwrite_all(fd: int, data, off: int) -> None:
    """pwrite the whole buffer: loops over the ~2GiB-per-call Linux cap
    and genuine short writes (NFS), without the full-copy ``tobytes()``
    an ndarray would otherwise pay."""
    mv = memoryview(data).cast("B")
    done = 0
    while done < len(mv):
        n = os.pwrite(fd, mv[done:], off + done)
        if n <= 0:
            raise StromError(_errno.EIO,
                            f"pwrite returned {n} at offset {off + done}")
        done += n


def _leaf_bytes(leaf, e: Dict):
    """The exact bytes entry *e*'s leaf serializes to — shared by the
    checksum pre-pass and the buffered writer so they cannot diverge."""
    arr = np.ascontiguousarray(np.asarray(leaf))
    if arr.dtype.str != e["dtype"]:
        arr = arr.astype(np.dtype(e["dtype"]))
    return arr.data if arr.shape else arr.tobytes()


def _entries_for(flat) -> List[Dict]:
    """Leaf table from GLOBAL shapes (identical on every process — a
    jax.Array's .shape/.dtype are global even when sharded across hosts)."""
    entries = []
    off = 0
    for key, leaf in flat:
        dtype = np.dtype(getattr(leaf, "dtype", None)
                         or np.asarray(leaf).dtype)
        shape = tuple(int(s) for s in np.shape(leaf))
        nbytes = int(dtype.itemsize * np.prod(shape, dtype=np.int64)) \
            if shape else dtype.itemsize
        entries.append({"key": key, "dtype": dtype.str,
                        "shape": list(shape), "offset": off,
                        "nbytes": nbytes})
        off = _pad(off + nbytes)
    return entries


def save_checkpoint_sharded(path: str, tree: Any) -> Dict:
    """Collective save of a pytree whose leaves may be sharded across
    hosts: every process writes ONLY the row ranges its addressable
    shards own into one shared file — the mirror image of the sharded
    restore (no gather; a multi-terabyte model checkpoint never crosses
    DCN).  The file layout is identical to :func:`save_checkpoint`, so
    either restore path reads it.

    Requirements: a filesystem every process can reach at *path*;
    jax.Array leaves sharded (if at all) on the LEADING axis with
    unit-step slices and full trailing axes (the same layout the sharded
    restore reads natively); every process calls this function (it
    synchronizes through global-device barriers when
    ``jax.process_count() > 1``).  Replicated shards are written once,
    by the process holding ``replica_id == 0``; non-array leaves are
    written by process 0.

    Crash-safe per save: bytes land in a shared deterministic temp file,
    every process fsyncs its own writes, and process 0 renames it over
    *path* after the barrier — but unlike :func:`save_checkpoint`,
    CONCURRENT sharded saves to one path are not supported (all
    processes must share one temp name to write into one file).  Shard
    layouts are validated on every process BEFORE the first barrier so
    bad specs fail symmetrically; a mid-write I/O error on one host
    (ENOSPC/EIO), however, leaves the other hosts blocked at the data
    barrier — the barrier has no timeout, so job-level supervision must
    kill the collective (the installed checkpoint at *path* is never
    touched until the final rename, so nothing is corrupted).
    """
    import jax

    flat = _flatten(tree)
    entries = _entries_for(flat)
    # validate EVERY local shard's layout BEFORE the first barrier: a
    # layout error must fail symmetrically on all processes, not strand
    # the conforming ones at the data barrier while one process raises
    for key, leaf in flat:
        if not isinstance(leaf, jax.Array):
            continue
        if not np.shape(leaf):
            continue
        for shard in leaf.addressable_shards:
            idx = shard.index
            rows = idx[0] if idx else slice(None)
            if not isinstance(rows, slice) or rows.step not in (None, 1):
                raise StromError(
                    _errno.EINVAL,
                    f"leaf {key}: sharded save needs a unit-step "
                    f"leading-axis slice, got {rows!r}")
            if any(s != slice(None, None, None) for s in idx[1:]):
                raise StromError(
                    _errno.EINVAL,
                    f"leaf {key}: sharded save supports leading-axis "
                    f"sharding only (trailing index {idx[1:]!r} is "
                    f"partial)")
    header = json.dumps({"version": _VERSION,
                         "leaves": entries}).encode()
    header_len = _pad(16 + len(header))
    end = header_len + (entries[-1]["offset"] + _pad(entries[-1]["nbytes"])
                        if entries else 0)
    path = os.path.realpath(path)
    tmp = path + ".shared_tmp"
    multi = jax.process_count() > 1
    pid0 = jax.process_index() == 0

    def barrier(tag: str) -> None:
        if multi:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(f"strom_ckpt:{tag}")

    if pid0:
        with open(tmp, "wb") as f:
            f.write(struct.pack("<QQ", _MAGIC, len(header)))
            f.write(header)
            f.write(b"\0" * (header_len - 16 - len(header)))
            f.truncate(_pad(end))
            f.flush()
            os.fsync(f.fileno())
    barrier("header")
    try:
        fd = os.open(tmp, os.O_WRONLY)
        try:
            for e, (key, leaf) in zip(entries, flat):
                base = header_len + e["offset"]
                if not isinstance(leaf, jax.Array):
                    if pid0:
                        arr = np.ascontiguousarray(np.asarray(leaf))
                        if arr.dtype.str != e["dtype"]:
                            arr = arr.astype(np.dtype(e["dtype"]))
                        _pwrite_all(fd, arr.reshape(-1).view(np.uint8)
                                    if arr.shape else arr.tobytes(), base)
                    continue
                shape = tuple(e["shape"])
                rowbytes = int(np.dtype(e["dtype"]).itemsize
                               * np.prod(shape[1:], dtype=np.int64)) \
                    if len(shape) > 1 else np.dtype(e["dtype"]).itemsize
                for shard in leaf.addressable_shards:
                    if shard.replica_id != 0:
                        continue   # one canonical writer per index block
                    idx = shard.index
                    if shape:   # layouts pre-validated before the barrier
                        rows = idx[0] if idx else slice(None)
                        r0 = rows.start or 0
                        off = base + r0 * rowbytes
                    else:
                        off = base
                    data = np.ascontiguousarray(np.asarray(shard.data))
                    _pwrite_all(fd, data.reshape(-1).view(np.uint8)
                                if data.shape else data.tobytes(), off)
            os.fsync(fd)   # each process persists its own writes
        finally:
            os.close(fd)
        barrier("data")
        if pid0:
            os.replace(tmp, path)
            try:
                dirfd = os.open(os.path.dirname(path) or ".", os.O_RDONLY)
                try:
                    os.fsync(dirfd)
                finally:
                    os.close(dirfd)
            except OSError:
                pass
        barrier("installed")
        # every process drops its own residency-tier extents over the
        # freshly installed bytes (the cache is process-local)
        extent_space.invalidate_paths([path])
    except BaseException:
        if pid0:
            try:
                os.unlink(tmp)
            except OSError:
                pass
        raise
    return {"path": path, "leaves": len(entries), "bytes": _pad(end)}


# -- inspect -----------------------------------------------------------------

def checkpoint_info(path: str) -> Dict:
    """Read the header (magic check + leaf table) without touching data."""
    with open(path, "rb") as f:
        magic, jlen = struct.unpack("<QQ", f.read(16))
        if magic != _MAGIC:
            raise StromError(_errno.EINVAL, f"{path}: not a strom checkpoint")
        meta = json.loads(f.read(jlen))
    if meta.get("version") != _VERSION:
        raise StromError(_errno.EINVAL, f"checkpoint version {meta.get('version')}")
    meta["data_offset"] = _pad(16 + jlen)
    return meta


# -- restore -----------------------------------------------------------------

def _leaf_sharding(shardings, key: str):
    if shardings is None:
        return None
    if isinstance(shardings, dict):
        return shardings.get(key)
    return shardings  # one sharding for every leaf


class _PinnedRing:
    """Rotating pinned buffers + H2D fencing for checkpoint restore.

    Max width comes from config ``h2d_depth_max`` (min 2); the ACTIVE
    rotation window is :class:`..hbm.staging.AdaptiveH2DDepth` — it
    starts at 2, widens whenever the rotation actually blocks on a fence
    (a wider window would have hidden that wait) and decays back when
    fences stop blocking, the same deferred-fence policy as the scan
    executor's pipeline (VERDICT r2 #3 + r3 #6).  Out-of-window buffers
    keep their pending fences; they are fenced when the window grows back
    over them or at close()."""

    def __init__(self, sess: Session, staging_bytes: int):
        from ..config import config
        from ..hbm.staging import AdaptiveH2DDepth
        self.sess = sess
        self.cap = staging_bytes
        n = max(2, int(config.get("h2d_depth_max")))
        self.adaptive = AdaptiveH2DDepth(n)
        # buffers allocate LAZILY as the window grows: pinned memory
        # tracks the high-water of the window actually used, not
        # h2d_depth_max (an 8-deep config on a never-blocking transport
        # pins 2 buffers, not 8)
        self.bufs: List[tuple] = []
        self.fences: List[list] = []
        self.cur = -1

    def next_buf(self):
        """Rotate to the next in-window pinned buffer; fence its previous
        H2D reads, feeding the observed wait back to the depth policy."""
        import time as _time

        from ..hbm.staging import bounded_fence
        self.cur = (self.cur + 1) % self.adaptive.depth
        while self.cur >= len(self.bufs):   # window grew: alloc lazily
            self.bufs.append(self.sess.alloc_dma_buffer(self.cap))
            self.fences.append([])
        t0 = _time.monotonic_ns()
        for f in self.fences[self.cur]:
            bounded_fence(f, "ckpt-h2d")   # ENODEV on a dead backend
        blocked_ns = _time.monotonic_ns() - t0
        self.fences[self.cur] = []
        self.adaptive.observe(blocked_ns)
        return self.bufs[self.cur]

    def put(self, host: np.ndarray, dev):
        """device_put that records a fence on the current buffer (several
        puts may read the same staged bytes — e.g. replicated shards)."""
        arr = safe_device_put(host, dev)
        self.fences[self.cur].append(arr)
        return arr

    def close(self):
        from ..api import StromError as _SE
        from ..hbm.staging import bounded_fence
        for fl in self.fences:
            for f in fl:
                try:
                    bounded_fence(f, "ckpt-drain")
                except _SE:
                    # per-fence: a per-array ENOMEM must not abandon the
                    # other buffers' drains (their transfers still read
                    # pinned memory); a latched loss fails the rest
                    # instantly anyway
                    continue
        for handle, buf in self.bufs:
            try:
                self.sess.unmap_buffer(handle)
            except StromError:
                pass
            buf.close()
        self.bufs = []


def _read_span(sess, source, file_off: int, nbytes: int,
               ring: _PinnedRing) -> np.ndarray:
    """Read one byte span through the direct path.

    Returns a view into the ring's current pinned buffer (consume with
    ``ring.put`` before the next ``_read_span``), or an owned array when
    the span exceeds one staging buffer."""
    if nbytes == 0:
        return np.empty(0, np.uint8)
    handle, buf = ring.next_buf()
    cap = len(buf.view())
    out = np.empty(nbytes, np.uint8) if nbytes > cap else None
    done = 0
    view = None
    while done < nbytes:
        take = min(cap, nbytes - done)
        start = file_off + done
        c0 = start // _CHUNK
        c1 = (start + take + _CHUNK - 1) // _CHUNK
        if start % _CHUNK == 0 and c1 * _CHUNK <= source.size:
            view = read_chunk_ids(sess, source, range(c0, c1), _CHUNK,
                                  handle, buf.view())[:take]
        else:
            # unaligned head or grid running past EOF: buffered leg
            source.read_buffered(start, buf.view()[:take])
            view = np.frombuffer(buf.view()[:take], np.uint8)
        if out is not None:
            out[done:done + take] = view
        done += take
    return out if out is not None else view[:nbytes]


_INT32_MAX = (1 << 31) - 1


def _restore_streamed(sess, source, base: int, dtype: np.dtype,
                      shape, dev, ring: _PinnedRing,
                      compute_crc: bool = False):
    """Stream a leaf larger than one staging buffer straight onto the
    device: each staged sub-span lands with a donated
    ``dynamic_update_slice`` into the preallocated device leaf — no
    owned-host assembly copy (the old path materialized the whole leaf on
    the host a second time before one giant device_put).

    Same-shaped spans COALESCE: up to config ``scan_dispatch_batch``
    staged chunks land in one ``_write_slices`` dispatch instead of a
    per-span jitted call — per-dispatch latency on a high-latency backend
    otherwise adds a round trip per 64MB span (the scan executor's
    CoalescedFold discipline applied to restore)."""
    import jax
    import jax.numpy as jnp

    from ..config import config
    from ..hbm.staging import _write_slice, _write_slices
    nbytes = int(dtype.itemsize * np.prod(shape, dtype=np.int64)) \
        if shape else dtype.itemsize
    with jax.default_device(dev):
        dest = jnp.zeros(nbytes // dtype.itemsize, dtype)
    kmax = max(1, int(config.get("scan_dispatch_batch")))
    pending: List[tuple] = []   # (chunk_dev, elem_offset), same shapes

    def flush(dest):
        from ..stats import stats
        if not pending:
            return dest
        if len(pending) == 1:
            dest = _write_slice(dest, pending[0][0],
                                np.int32(pending[0][1]))
        else:
            starts = np.asarray([p[1] for p in pending], np.int32)
            dest = _write_slices(dest, starts,
                                 *[p[0] for p in pending])
        stats.add("nr_kernel_dispatch")
        pending.clear()
        return dest

    done = 0
    crc = 0
    while done < nbytes:
        take = min(ring.cap, nbytes - done)
        # element-align every take (a staging buffer not divisible by the
        # itemsize must not split an element across sub-spans); the final
        # take is nbytes - done, already element-aligned by induction
        take -= take % dtype.itemsize
        view = _read_span(sess, source, base + done, take, ring)
        if compute_crc:
            # incremental: sub-spans are sequential and exhaustive, so
            # the running crc equals the whole-leaf checksum at the end
            crc = _leaf_crc_update(crc, view)
        chunk = ring.put(view.view(dtype), dev)
        if pending and pending[0][0].shape != chunk.shape:
            # a shape change (final short span) would force a fresh
            # _write_slices specialization: land it separately instead
            dest = flush(dest)
        pending.append((chunk, done // dtype.itemsize))
        if len(pending) >= kmax:
            dest = flush(dest)
        done += take
    dest = flush(dest)
    return dest.reshape(shape), (crc if compute_crc else None)


def restore_checkpoint(path: str, *, shardings=None, like=None,
                       session: Optional[Session] = None,
                       device=None, staging_bytes: int = 64 << 20,
                       verify: bool = False):
    """Load a checkpoint into device arrays through the direct path.

    ``shardings`` — None (single device, see *device*), one
    ``jax.sharding.Sharding`` for all leaves, or a dict ``{key: Sharding}``
    (keys as printed by ``jax.tree_util.keystr``).  With a sharding, each
    addressable device's row-range of the leaf is read individually, so a
    multi-host restore only touches local shards.  ``like`` — optional
    pytree with the same structure used to rebuild the tree shape (by
    default a flat ``{key: array}`` dict is returned).

    ``verify=True`` recomputes each leaf's crc32c from the bytes actually
    read and compares it against the header's per-leaf checksum —
    corruption latches EBADMSG naming the leaf.  When-present semantics:
    leaves without a stored checksum (sharded saves, older files) and
    sharded restores (no process reads a whole leaf) are skipped.
    """
    import jax

    meta = checkpoint_info(path)
    data0 = meta["data_offset"]
    own = session is None
    sess = session or Session()
    out: Dict[str, jax.Array] = {}
    try:
        with open_source(path) as source:
            # two pinned buffers, alternated per transfer: device_put is
            # async and the host view points into the pinned buffer, so the
            # buffer being refilled is never the one still feeding an H2D
            # read — reuse is fenced in _PinnedRing (staging.py discipline)
            ring = _PinnedRing(sess, staging_bytes)
            try:
                for e in meta["leaves"]:
                    key = e["key"]
                    dtype = np.dtype(e["dtype"])
                    shape = tuple(e["shape"])
                    base = data0 + e["offset"]
                    sh = _leaf_sharding(shardings, key)
                    want = e.get("crc32c") if verify else None
                    if sh is None:
                        dev = device or default_device()
                        n_elems = int(e["nbytes"]) // dtype.itemsize
                        if (e["nbytes"] > ring.cap
                                and ring.cap >= dtype.itemsize
                                and n_elems <= _INT32_MAX):
                            out[key], got = _restore_streamed(
                                sess, source, base, dtype, shape, dev,
                                ring, compute_crc=want is not None)
                        else:
                            span = _read_span(sess, source, base,
                                              e["nbytes"], ring)
                            got = _leaf_crc(span) if want is not None \
                                else None
                            host = span.view(dtype)
                            out[key] = ring.put(host.reshape(shape), dev)
                        if want is not None and got != want:
                            raise StromError(
                                _errno.EBADMSG,
                                f"{path}: leaf {key} crc32c mismatch "
                                f"(header {want:#010x}, data {got:#010x})"
                                f" — checkpoint is corrupt")
                    else:
                        # sharded restores read only local row ranges —
                        # no process sees a whole leaf, so per-leaf crc
                        # verification cannot run here
                        out[key] = _restore_sharded(sess, source, base, dtype,
                                                    shape, sh, ring)
            finally:
                ring.close()
    finally:
        if own:
            sess.close()
    if like is not None:
        leaves = [out[k] for k, _ in _flatten(like)]
        treedef = jax.tree_util.tree_structure(like)
        return jax.tree_util.tree_unflatten(treedef, leaves)
    return out


def _restore_sharded(sess, source, base, dtype, shape, sharding,
                     ring: _PinnedRing):
    """Assemble a sharded leaf from per-device shard reads.

    Shards that are contiguous in the row-major leaf (sharding split only
    on the leading axis) read exactly their byte range; other layouts read
    the covering row range and slice host-side — still only the rows this
    process's devices own."""
    import jax

    idx_map = sharding.addressable_devices_indices_map(shape)
    rowbytes = int(dtype.itemsize * np.prod(shape[1:], dtype=np.int64)) \
        if len(shape) > 1 else dtype.itemsize

    # one SSD read per unique row range: replicated / column-sharded specs
    # would otherwise re-read the same bytes once per device
    by_range: Dict[tuple, List] = {}
    for dev, idx in idx_map.items():
        if not shape:
            rkey = (0, 1)
        else:
            rows = idx[0] if idx else slice(None)
            if not isinstance(rows, slice) or rows.step not in (None, 1):
                raise StromError(
                    _errno.EINVAL,
                    f"unsupported leading-axis index {rows!r} for device "
                    f"{dev}: sharded restore needs a unit-step slice")
            rkey = (rows.start or 0,
                    rows.stop if rows.stop is not None else shape[0])
        by_range.setdefault(rkey, []).append((dev, idx))

    arrays = []
    for (r0, r1), members in by_range.items():
        if not shape:  # scalar leaf: replicate
            host = _read_span(sess, source, base, dtype.itemsize,
                              ring).view(dtype).reshape(())
            arrays.extend(ring.put(host, dev) for dev, _ in members)
            continue
        host = _read_span(sess, source, base + r0 * rowbytes,
                          (r1 - r0) * rowbytes, ring)
        block = host.view(dtype).reshape((r1 - r0,) + shape[1:])
        for dev, idx in members:
            sub = idx[1:]
            if any(s != slice(None, None, None) for s in sub):
                shard = np.ascontiguousarray(block[(slice(None),) + tuple(sub)])
            else:
                shard = block
            arrays.append(ring.put(shard, dev))
    return jax.make_array_from_single_device_arrays(shape, sharding, arrays)
