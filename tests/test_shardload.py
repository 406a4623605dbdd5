"""Multi-host scale-out proofs on the virtual 8-device mesh (ISSUE 17).

Everything here runs single-process over the forced-CPU mesh (conftest
pins ``xla_force_host_platform_device_count=8``): "hosts" are the
planner's ownership units, each backed by its own engine session, which
is exactly the posture the multichip gate scales.  What is asserted:

* the host-ownership partition is disjoint, exhaustive, and
  member-aligned on striped sources;
* the sharded loader's redistributed (and gathered) bytes are identical
  to a single-host ``load_pages_sharded`` of the same source;
* the sharded cold-start lands a byte-identical model with layer-ordered
  adoption per host;
* cross-host KV migration is byte-identical, and a mid-migration
  destination failure rolls back leaving the source SSD-resumable.
"""

import numpy as np
import pytest

from nvme_strom_tpu.config import config
from nvme_strom_tpu.engine import PlainSource, Session, StripedSource
from nvme_strom_tpu.scan.heap import PAGE_SIZE
from nvme_strom_tpu.stats import stats
from nvme_strom_tpu.stripe import host_members, host_of
from nvme_strom_tpu.trace import recorder

pytestmark = pytest.mark.multihost

N_PAGES = 32


@pytest.fixture
def page_file(tmp_path):
    rng = np.random.default_rng(17)
    path = tmp_path / "pages.dat"
    path.write_bytes(rng.integers(0, 256, N_PAGES * PAGE_SIZE,
                                  dtype=np.uint8).tobytes())
    return str(path)


@pytest.fixture
def striped_pages(tmp_path):
    """4-member stripe, chunk = PAGE_SIZE: page i lives on member i%4."""
    rng = np.random.default_rng(18)
    data = rng.integers(0, 256, N_PAGES * PAGE_SIZE,
                        dtype=np.uint8).tobytes()
    members = [tmp_path / f"m{k}.dat" for k in range(4)]
    per = N_PAGES // 4
    for k, m in enumerate(members):
        m.write_bytes(b"".join(
            data[i * PAGE_SIZE:(i + 1) * PAGE_SIZE]
            for i in range(N_PAGES) if i % 4 == k))
        assert m.stat().st_size == per * PAGE_SIZE
    return [str(m) for m in members], data


def _mesh():
    from nvme_strom_tpu.parallel.mesh import make_scan_mesh
    return make_scan_mesh(sp=1)


# -- ownership partition ---------------------------------------------------

def test_host_ownership_partition_disjoint_exhaustive(page_file):
    from nvme_strom_tpu.parallel import shard_ownership

    with PlainSource(page_file) as src:
        for hosts in (1, 2, 3, 4, 8):
            owned = shard_ownership(src, hosts)
            assert sorted(owned) == list(range(hosts))
            flat = [c for ids in owned.values() for c in ids]
            assert sorted(flat) == list(range(N_PAGES)), \
                f"hosts={hosts}: not a partition"
            assert len(flat) == len(set(flat)), f"hosts={hosts}: overlap"
            # plain (single-member) sources split into contiguous runs
            for ids in owned.values():
                assert ids == list(range(ids[0], ids[0] + len(ids)))


def test_host_ownership_member_aligned_on_stripes(striped_pages):
    """On a striped source every chunk lands on the host that locally
    holds its first extent's member — the whole point of the planner:
    no host ever reads a remote member's chunk."""
    from nvme_strom_tpu.parallel import shard_ownership

    paths, _ = striped_pages
    with StripedSource(paths, stripe_chunk_size=PAGE_SIZE) as src:
        for hosts in (2, 4):
            owned = shard_ownership(src, hosts)
            for h, ids in owned.items():
                local = set(host_members(h, 4, hosts))
                for cid in ids:
                    member = src.extents(cid * PAGE_SIZE,
                                         PAGE_SIZE)[0].member
                    assert member in local, \
                        f"host {h} owns chunk {cid} on member {member}"
                    assert host_of(member, hosts) == h


# -- sharded load byte identity -------------------------------------------

def test_multihost_load_identical_to_single_host(page_file):
    from nvme_strom_tpu.parallel import (load_pages_multihost,
                                         load_pages_sharded)

    mesh = _mesh()
    with PlainSource(page_file) as src:
        ref = np.asarray(load_pages_sharded(src, mesh))
        for hosts in (1, 2, 4, 8):
            out = load_pages_multihost(src, mesh, hosts=hosts)
            assert out.shape == ref.shape
            assert np.array_equal(np.asarray(out), ref), f"hosts={hosts}"


@pytest.mark.parametrize("cached", [0.0, 1.0])
def test_multihost_load_reads_in_bounded_tasks(page_file, monkeypatch,
                                               cached):
    """A host's shard goes out as one engine task per config chunk_size
    of pages (the task deadline bounds one task; one task per GiB shard
    overran it on a page-cached heap on the chip), on the direct and the
    write-back path alike, and the bytes stay identical."""
    from nvme_strom_tpu.parallel import load_pages_multihost
    from nvme_strom_tpu.testing import FakeNvmeSource

    config.set("chunk_size", 64 << 10)          # 8 pages per task
    tasks = []
    submit = Session.memcpy_ssd2ram

    def counted(self, source, handle, ids, chunk_size, **kw):
        res = submit(self, source, handle, ids, chunk_size, **kw)
        tasks.append((len(ids), res.nr_ram2dev))
        return res

    monkeypatch.setattr(Session, "memcpy_ssd2ram", counted)
    want = np.fromfile(page_file, np.uint8).reshape(N_PAGES, PAGE_SIZE)
    with FakeNvmeSource(page_file, force_cached_fraction=cached) as src:
        out = load_pages_multihost(src, _mesh(), hosts=2)
    assert np.array_equal(np.asarray(out), want)
    assert [n for n, _ in tasks] == [8] * 4
    assert sum(r for _, r in tasks) == (N_PAGES if cached else 0)


def test_multihost_load_striped_gather_and_spans(striped_pages):
    """Striped source, trace on: the gathered array equals the file
    bytes, one shard_load span fires per host, and the redistribution
    emits ici_permute spans + ICI byte accounting."""
    from nvme_strom_tpu.parallel import load_pages_multihost

    paths, data = striped_pages
    mesh = _mesh()
    config.set("trace_policy", "all")
    recorder.configure()
    recorder.clear()
    before = stats.snapshot().counters
    with StripedSource(paths, stripe_chunk_size=PAGE_SIZE) as src:
        out = load_pages_multihost(src, mesh, hosts=4, gather=True)
    got = np.asarray(out).tobytes()
    assert got == data, "gathered bytes diverge from the file"
    after = stats.snapshot().counters
    assert after["nr_shard_load"] - before["nr_shard_load"] == 4
    assert after["bytes_shard_load"] - before["bytes_shard_load"] \
        == N_PAGES * PAGE_SIZE
    assert after["nr_ici_permute"] > before["nr_ici_permute"]
    assert after["bytes_ici"] > before["bytes_ici"]
    spans = [e for e in recorder.snapshot_events() if e[2] == "shard_load"]
    assert sorted(e[8]["host"] for e in spans) == [0, 1, 2, 3]
    assert [e for e in recorder.snapshot_events() if e[2] == "ici_permute"]


def test_shard_wait_histogram_populated(page_file):
    """The fan-in observer (satellite 2): streaming a batch leaves a
    per-shard wait histogram behind for straggler attribution."""
    from nvme_strom_tpu.parallel import ShardedBatchStream

    mesh = _mesh()
    before = stats.snapshot().counters.get("nr_shard_wait", 0)
    with PlainSource(page_file) as src:
        with ShardedBatchStream(src, mesh, batch_pages=16) as stream:
            for _first, arr in stream:
                arr.block_until_ready()
    after = stats.snapshot().counters
    n_shards = mesh.shape["dp"]
    assert after["nr_shard_wait"] - before >= n_shards
    assert after["clk_shard_wait"] > 0
    shards = stats.shard_snapshot()
    assert set(range(n_shards)) <= set(shards)
    for d in shards.values():
        assert d["n"] >= 1 and d.get("p50_ns", 0) >= 0


# -- sharded cold-start ----------------------------------------------------

def test_sharded_coldstart_identity_and_layer_order(tmp_path):
    from nvme_strom_tpu.serving.weights import stream_weights_sharded
    from nvme_strom_tpu.testing.coldstart_gate import (_check_tree,
                                                       _make_checkpoint)

    path, tree = _make_checkpoint(str(tmp_path))
    config.set("trace_policy", "all")
    recorder.configure()
    recorder.clear()
    model = stream_weights_sharded(path, hosts=2)
    try:
        _check_tree(model, tree)
    finally:
        model.close()
    spans = [e for e in recorder.snapshot_events()
             if e[2] == "weight_stream"]
    assert spans, "no weight_stream spans under trace_policy=all"
    hosts = sorted({e[8]["host"] for e in spans})
    assert hosts == [0, 1]
    for h in hosts:
        order = [e[8]["layer"] for e in sorted(
            (e for e in spans if e[8]["host"] == h), key=lambda e: e[0])]
        assert order == sorted(order), \
            f"host {h} adopted layers out of order: {order}"
        assert all(i % 2 == h for i in order), \
            f"host {h} streamed another host's layers: {order}"
    # the handshake crossed the fabric
    assert [e for e in recorder.snapshot_events() if e[2] == "ici_permute"]


# -- cross-host KV migration ----------------------------------------------

def _mk_pool(session, tmp_path, name, blocks=32, bb=4096):
    spill = tmp_path / f"{name}.spill"
    spill.write_bytes(b"\0" * bb * blocks)
    src = PlainSource(str(spill), writable=True)
    from nvme_strom_tpu.serving.kvcache import KvBlockPool
    return KvBlockPool(session, src, block_bytes=bb, ram_blocks=4), src


def test_kv_migrate_byte_identity_and_failed_host_resume(tmp_path):
    rng = np.random.default_rng(23)
    bb = 4096
    blobs = [rng.integers(0, 256, bb, dtype=np.uint8).tobytes()
             for _ in range(8)]
    config.set("trace_policy", "all")
    recorder.configure()
    recorder.clear()
    with Session() as s1, Session() as s2:
        hot, src_a = _mk_pool(s1, tmp_path, "hot", bb=bb)
        cold, src_b = _mk_pool(s2, tmp_path, "cold", bb=bb)
        try:
            for x in blobs:
                hot.append("chain", x, qos_class="bulk")
            # ram_blocks=4 < 8 appended: part of the chain is already
            # SSD-spilled, so migration exercises page-in on copy-out
            assert hot.residency()["ssd"] > 0

            # -- seeded mid-migration destination-host failure --------
            real_append = cold.append
            fails = {"left": 3}

            def dying_append(seq, data, qos_class=None):
                if fails["left"] == 0:
                    raise OSError("peer host fail-stopped mid-migration")
                fails["left"] -= 1
                return real_append(seq, data, qos_class=qos_class)

            cold.append = dying_append
            before = stats.snapshot().counters.get("nr_kv_migrate_fail", 0)
            with pytest.raises(OSError):
                hot.migrate("chain", cold)
            cold.append = real_append
            after = stats.snapshot().counters
            assert after["nr_kv_migrate_fail"] - before == 1
            assert cold.blocks("chain") == 0, "peer not rolled back"
            assert hot.blocks("chain") == 8, "source chain damaged"

            # the source survives a full spill + SSD resume untouched
            hot.shed(1 << 30, reason="test")
            assert hot.residency()["ram"] == 0
            assert hot.resume("chain") > 0
            got = [hot.read("chain", i) for i in range(8)]
            assert got == blobs, "post-rollback SSD resume diverged"

            # -- clean migration: byte identity, class preserved ------
            moved = hot.migrate("chain", cold)
            assert moved == 8 * bb
            assert hot.blocks("chain") == 0
            assert [cold.read("chain", i) for i in range(8)] == blobs
            assert cold._classes["chain"] == "bulk"
            spans = [e for e in recorder.snapshot_events()
                     if e[2] == "kv_migrate"]
            assert spans and spans[-1][8]["blocks"] == 8
        finally:
            hot.close()
            cold.close()
            src_a.close()
            src_b.close()


def test_kv_migrate_config_gate_and_shed_to_peer(tmp_path):
    import errno

    from nvme_strom_tpu.api import StromError

    rng = np.random.default_rng(29)
    bb = 4096
    with Session() as s1, Session() as s2:
        hot, src_a = _mk_pool(s1, tmp_path, "hot2", bb=bb)
        cold, src_b = _mk_pool(s2, tmp_path, "cold2", bb=bb)
        try:
            for seq, qos in (("bulk0", "bulk"), ("lat0", "latency")):
                for _ in range(2):
                    hot.append(seq, rng.integers(0, 256, bb,
                                                 dtype=np.uint8).tobytes(),
                               qos_class=qos)
            config.set("kv_migrate", False)
            with pytest.raises(StromError) as ei:
                hot.migrate("bulk0", cold)
            assert ei.value.errno == errno.EOPNOTSUPP
            config.set("kv_migrate", True)

            # bulk sheds first; the latency chain stays local
            shed = hot.shed_to_peer(cold, bb)
            assert shed == 2 * bb
            assert cold.blocks("bulk0") == 2
            assert hot.blocks("lat0") == 2 and cold.blocks("lat0") == 0
        finally:
            hot.close()
            cold.close()
            src_a.close()
            src_b.close()
