"""Model cold-start: checkpoint shards streamed layer-ordered into HBM.

The serving-path restore (ISSUE 15): where :func:`..data.checkpoint.
restore_checkpoint` materializes a pytree for training, this streamer
lands a model's weight bytes into DONATED device buffers for inference —
layer by layer, in file order, with layer N+1's SSD reads in flight
while layer N's landed bytes are adopted as device arrays (the
``DeviceLoader.epochs()`` prefetch discipline applied to cold-start).

Per layer the flow is exactly PR 8's zero-copy landing ladder:

1. allocate an owned :class:`..hbm.registry.LandingBuffer` sized to the
   layer's (4096-aligned) byte span,
2. submit one async ``memcpy_ssd2ram`` of the span's chunk grid into it
   (the planner merges the 4KB grid into ``dma_max_size`` requests, the
   fault ladder heals what it heals),
3. at retire: crc32c-verify each leaf against the checkpoint manifest
   (PR 11 semantics, on by default), adopt the buffer as a device
   array (``LandingBuffer.adopt_array`` → registry handle →
   ``HbmBuffer.adopt``) — zero-copy where the backend aliases host
   pages (CPU), one H2D copy otherwise.

Each retired layer emits a ``weight_stream`` span (submit→adopt) whose
``layer`` arg lets tests assert layer-ordered landing from the flight
recorder; the aggregate landing rate is published as the
``coldstart_bytes_per_sec`` gauge.
"""

from __future__ import annotations

import errno as _errno
import re
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import numpy as np

from ..api import StromError
from ..config import config
from ..stats import stats
from ..trace import recorder as _trace

__all__ = ["StreamedModel", "stream_weights", "stream_weights_sharded"]

_ALIGN = 4096
#: layer index from a leaf key: "...layers.12...", "...layer_3...",
#: "['blocks'][7]" etc.; keys without one belong to the root group
#: (embeddings, norms, heads) and stream in file order around the layers
_LAYER_RE = re.compile(r"(?:^|[^a-z])(?:layers?|blocks?|h)[._\[\]'\"]*(\d+)",
                       re.IGNORECASE)


def _layer_of(key: str) -> Optional[int]:
    m = _LAYER_RE.search(key)
    return int(m.group(1)) if m else None


class _Layer:
    __slots__ = ("index", "label", "base", "nbytes", "leaves", "handle")

    def __init__(self, index: int, label, base: int) -> None:
        self.index = index          # stream order (file order)
        self.label = label          # parsed layer number or None (root)
        self.base = base            # absolute file offset of the span
        self.nbytes = 0             # span length (padded to _ALIGN)
        self.leaves: List[dict] = []
        self.handle = 0             # hbm registry handle once adopted


class StreamedModel:
    """Handle set for a streamed weight checkpoint.

    ``handles`` maps stream index → hbm registry handle (each holding
    one layer span as a device-resident uint8 array that ALIASES its
    LandingBuffer where the backend allows).  :meth:`leaf` carves a
    typed view out of its layer's array on device — a reshape+bitcast,
    no host round-trip.  :meth:`close` unmaps every handle (releasing
    the landing buffers)."""

    def __init__(self, path: str, layers: List[_Layer]) -> None:
        self.path = path
        self._layers = layers
        self._by_key: Dict[str, tuple] = {}
        for ly in layers:
            for e in ly.leaves:
                self._by_key[e["key"]] = (ly, e)
        self.total_bytes = sum(ly.nbytes for ly in layers)

    @property
    def handles(self) -> Dict[int, int]:
        return {ly.index: ly.handle for ly in self._layers}

    def keys(self) -> List[str]:
        return list(self._by_key)

    def layer_array(self, index: int):
        """One layer span as its device-resident uint8 array."""
        from ..hbm.registry import registry
        return registry.get(self._layers[index].handle).array

    def leaf(self, key: str):
        """Leaf *key* as a typed device array (device-side bitcast)."""
        import jax.lax as lax
        try:
            ly, e = self._by_key[key]
        except KeyError:
            raise StromError(_errno.ENOENT,
                             f"{self.path}: no leaf {key!r}") from None
        u8 = self.layer_array(ly.index)
        rel = e["abs"] - ly.base
        sl = lax.slice(u8, (rel,), (rel + e["nbytes"],))
        dt = np.dtype(e["dtype"])
        shape = tuple(e["shape"])
        if dt.itemsize == 1:
            out = lax.bitcast_convert_type(sl, dt)
        else:
            out = lax.bitcast_convert_type(
                sl.reshape(-1, dt.itemsize), dt)
        return out.reshape(shape)

    def close(self) -> None:
        from ..hbm.registry import registry
        for ly in self._layers:
            if ly.handle:
                try:
                    registry.unmap(ly.handle, timeout=5.0)
                except StromError:
                    pass
                ly.handle = 0


def _plan_layers(meta: dict) -> List[_Layer]:
    """Group manifest leaves into contiguous streamed spans: consecutive
    leaves (file order) sharing a parsed layer label form one span, so
    every span is one contiguous chunk-grid read whatever the naming."""
    data0 = meta["data_offset"]
    layers: List[_Layer] = []
    cur: Optional[_Layer] = None
    for e in meta["leaves"]:
        label = _layer_of(e["key"])
        abs_off = data0 + e["offset"]
        if cur is None or label != cur.label:
            cur = _Layer(len(layers), label, abs_off)
            layers.append(cur)
        cur.leaves.append({"key": e["key"], "dtype": e["dtype"],
                           "shape": e["shape"], "abs": abs_off,
                           "nbytes": int(e["nbytes"]),
                           "crc32c": e.get("crc32c")})
        end = abs_off + int(e["nbytes"])
        cur.nbytes = (end - cur.base + _ALIGN - 1) // _ALIGN * _ALIGN
    return layers


def _stream_layer_subset(path: str, layers: List[_Layer], *, sess, src,
                         dev, verify: bool, depth: int, chunk_size: int,
                         host: Optional[int] = None) -> None:
    """The layer-pipelined submit→verify→adopt loop over one subset of
    spans: ``depth`` layers in flight through ONE session, each retired
    layer crc-verified against the manifest and adopted via the PR 8
    landing ladder.  Shared verbatim between the single-host streamer
    (subset = every layer) and each host thread of the sharded
    cold-start (subset = that host's round-robin slice) — the pipeline
    is the invariant, only the span ownership differs.  Fills
    ``ly.handle`` per layer; on failure drains ITS in-flight reads and
    unmaps ITS adoptions, then re-raises."""
    from ..hbm.registry import LandingBuffer, registry
    from ..scan.heap import crc32c as _crc

    inflight: deque = deque()   # (layer, landing, task_id, t_submit)

    def _retire() -> None:
        ly, landing, task, ts = inflight.popleft()
        try:
            sess.memcpy_wait(task.dma_task_id)
            if verify:
                view = landing.view()
                for e in ly.leaves:
                    want = e["crc32c"]
                    if want is None:
                        continue
                    rel = e["abs"] - ly.base
                    got = _crc(view[rel:rel + e["nbytes"]])
                    if got != want:
                        raise StromError(
                            _errno.EBADMSG,
                            f"{path}: leaf {e['key']} crc32c mismatch "
                            f"(manifest {want:#010x}, landed {got:#010x})")
            # the PR 8 adoption ladder: the device array aliases the
            # landing buffer where the backend zero-copies, and the
            # HbmBuffer owns the landing from here on
            arr = landing.adopt_array(np.uint8, dev)
            handle = registry.map_device_memory(arr)
            registry.get(handle).adopt(arr, landing)
            ly.handle = handle
        except BaseException:
            landing.release()
            raise
        if _trace.active:
            args = {"layer": ly.index, "label": ly.label,
                    "leaves": len(ly.leaves)}
            if host is not None:
                args["host"] = host
            _trace.span("weight_stream", ts, time.monotonic_ns(),
                        offset=ly.base, length=ly.nbytes, args=args)

    try:
        for ly in layers:
            if len(inflight) >= depth:
                _retire()       # adopt layer N while N+1.. are landing
            landing = LandingBuffer(sess, ly.nbytes)
            c0 = ly.base // chunk_size
            ids = list(range(c0, c0 + ly.nbytes // chunk_size))
            ts = time.monotonic_ns()
            try:
                task = sess.memcpy_ssd2ram(src, landing.handle, ids,
                                           chunk_size)
            except BaseException:
                landing.release()
                raise
            inflight.append((ly, landing, task, ts))
        while inflight:
            _retire()
    except BaseException:
        # drain whatever is still in flight, then unwind the adoptions
        from ..hbm.registry import registry
        while inflight:
            ly, landing, task, _ = inflight.popleft()
            try:
                sess.memcpy_wait(task.dma_task_id, timeout=30.0)
            except StromError:
                pass
            landing.release()
        for ly in layers:
            if ly.handle:
                try:
                    registry.unmap(ly.handle, timeout=5.0)
                except StromError:
                    pass
                ly.handle = 0
        raise


def stream_weights(path: str, *, session=None, source=None, device=None,
                   verify: bool = True, depth: Optional[int] = None,
                   chunk_size: int = _ALIGN) -> StreamedModel:
    """Cold-start a model: stream checkpoint *path* layer-ordered into
    donated HBM weight buffers, ``depth`` layers in flight
    (``weight_stream_depth`` default).  ``verify`` recomputes each
    leaf's crc32c against the manifest before adoption (PR 11; leaves
    without a stored checksum are skipped).  *source* overrides the
    file source (tests inject the loopback fake)."""
    import jax
    from ..data.checkpoint import checkpoint_info
    from ..engine import Session, open_source

    meta = checkpoint_info(path)
    layers = _plan_layers(meta)
    depth = depth or int(config.get("weight_stream_depth"))
    own_sess = session is None
    sess = session or Session()
    own_src = source is None
    src = source or open_source(path)
    dev = device or jax.local_devices()[0]
    total = sum(ly.nbytes for ly in layers)
    t0 = time.monotonic_ns()
    try:
        _stream_layer_subset(path, layers, sess=sess, src=src, dev=dev,
                             verify=verify, depth=depth,
                             chunk_size=chunk_size)
    finally:
        if own_src:
            src.close()
        if own_sess:
            sess.close()
    elapsed = max(time.monotonic_ns() - t0, 1)
    stats.gauge_set("coldstart_bytes_per_sec",
                    int(total * 1_000_000_000 / elapsed))
    return StreamedModel(path, layers)


def _digest_handshake(layers: List[_Layer], hosts: int,
                      backend: Optional[str]) -> None:
    """The on-fabric end of the sharded cold-start: every host
    contributes a digest row covering ITS layers (span base ^ length,
    +1 so a zero-offset layer still registers) and the rows all-gather
    around the hosts ring (:func:`..parallel.ring.ring_all_gather` —
    Pallas remote DMA on TPU, the ppermute collective elsewhere).  Each
    host then checks the summed gathered rows against the full
    manifest-derived expectation: a host that adopted nothing, or a
    layer nobody streamed, fails the handshake BEFORE the model is
    handed to serving.  On a real mesh this is also where the weight
    shards themselves all-gather; the digest rides the same lane and
    the same accounting (``nr_ici_permute``/``bytes_ici``)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from ..parallel.ring import permute_backend, ring_all_gather

    if len(jax.local_devices()) < hosts:
        return                  # no fabric to cross (single-device CI)
    mesh = Mesh(np.array(jax.local_devices()[:hosts]), ("hosts",))
    n = len(layers)
    rows = np.zeros((hosts, n), np.int64)
    for ly in layers:
        rows[ly.index % hosts, ly.index] = (ly.base ^ ly.nbytes) + 1
    arr = jax.device_put(rows, NamedSharding(mesh, P("hosts", None)))
    ts = time.monotonic_ns()
    gathered = ring_all_gather(arr, mesh, axis="hosts", backend=backend)
    got = np.asarray(gathered).sum(axis=0)
    moved = hosts * hosts * n * rows.itemsize
    stats.add("nr_ici_permute", hosts)
    stats.add("bytes_ici", moved)
    if _trace.active:
        _trace.span("ici_permute", ts, time.monotonic_ns(), length=moved,
                    args={"steps": hosts, "ring": hosts,
                          "backend": permute_backend(backend),
                          "hosts": hosts, "gather": True,
                          "what": "weight_digest"})
    want = np.array([(ly.base ^ ly.nbytes) + 1 for ly in layers], np.int64)
    if not np.array_equal(got, want):
        missing = [int(i) for i in np.nonzero(got != want)[0]]
        raise StromError(_errno.EIO,
                         f"sharded cold-start handshake failed: layer "
                         f"digests {missing} missing or wrong")


def stream_weights_sharded(path: str, *, hosts: Optional[int] = None,
                           source_factory: Optional[Callable[[int], object]]
                           = None,
                           verify: bool = True, depth: Optional[int] = None,
                           chunk_size: int = _ALIGN, device=None,
                           backend: Optional[str] = None) -> StreamedModel:
    """Sharded cold-start (ISSUE 17): split the checkpoint's layer spans
    round-robin across *hosts* (``shard_hosts`` default), stream each
    subset through that host's OWN session + source concurrently — the
    per-layer verify/adopt pipeline is byte-for-byte the single-host
    one (:func:`_stream_layer_subset`) — then run the on-fabric
    all-gather digest handshake so no host serves before every layer
    has landed somewhere.  Each host's spans adopt onto that host's
    device, so the landing is per-host HBM.  Wall time divides by the
    host count when the stream is latency-bound (per-host submission
    windows run in parallel).  ``source_factory(h)`` opens host *h*'s
    local view of the checkpoint (tests inject loopback fakes); default
    is ``open_source(path)`` per host."""
    import jax
    from ..data.checkpoint import checkpoint_info
    from ..engine import Session, open_source
    from ..hbm.registry import registry

    hosts = int(hosts or config.get("shard_hosts") or 1)
    if hosts < 1:
        raise StromError(_errno.EINVAL, f"bad host count {hosts}")
    meta = checkpoint_info(path)
    layers = _plan_layers(meta)
    depth = depth or int(config.get("weight_stream_depth"))
    total = sum(ly.nbytes for ly in layers)
    n_dev = len(jax.local_devices())
    subsets = [[ly for ly in layers if ly.index % hosts == h]
               for h in range(hosts)]
    errors: List[BaseException] = []
    t0 = time.monotonic_ns()

    def _run(h: int) -> None:
        sess = Session()
        src = source_factory(h) if source_factory else open_source(path)
        dev = device or jax.local_devices()[h % n_dev]
        try:
            _stream_layer_subset(path, subsets[h], sess=sess, src=src,
                                 dev=dev, verify=verify, depth=depth,
                                 chunk_size=chunk_size, host=h)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)
        finally:
            src.close()
            sess.close()

    if hosts == 1:
        _run(0)
    else:
        threads = [threading.Thread(target=_run, args=(h,),
                                    name=f"strom-coldstart-{h}")
                   for h in range(hosts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def _unwind() -> None:
        for ly in layers:
            if ly.handle:
                try:
                    registry.unmap(ly.handle, timeout=5.0)
                except StromError:
                    pass
                ly.handle = 0

    if errors:
        _unwind()
        raise errors[0]
    try:
        _digest_handshake(layers, hosts, backend)
    except BaseException:
        _unwind()
        raise
    elapsed = max(time.monotonic_ns() - t0, 1)
    stats.gauge_set("coldstart_bytes_per_sec",
                    int(total * 1_000_000_000 / elapsed))
    return StreamedModel(path, layers)
