"""chip_smoke.py on the CPU: its phases at a tiny size against the numpy
reference, and its entry point refusing to pass without a TPU."""

import argparse
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK = 64 << 10


@pytest.fixture(scope="module")
def cs():
    sys.path.insert(0, REPO)
    import chip_smoke
    return chip_smoke


def test_load_phase_matches_expected_bytes(cs, tmp_path):
    cs.phase_native()
    got = cs.phase_load(str(tmp_path), 4 << 20, 3, jax.devices()[0],
                        block=BLOCK)
    assert got["bytes"] == 4 << 20
    assert not os.path.exists(tmp_path / "load.bin")


def test_device_checksums_catch_a_moved_byte(cs):
    data = np.frombuffer(os.urandom(4 * BLOCK), np.uint8).copy()
    host = cs.host_checksums(
        lambda k: data[k * BLOCK:(k + 1) * BLOCK].tobytes(), 4, BLOCK)
    dev = np.asarray(cs.device_checksums(jax.numpy.asarray(data), BLOCK))
    np.testing.assert_array_equal(dev, host)
    i = BLOCK + 17          # swap two bytes inside block 1
    data[i], data[i + 1] = data[i + 1], data[i] ^ 1
    moved = np.asarray(cs.device_checksums(jax.numpy.asarray(data), BLOCK))
    assert list(np.flatnonzero(moved != host)) == [1]


def test_reference_wraps_like_int32_accumulators(cs):
    c0 = np.array([5, 1, -3, 7, 21], np.int32)
    c1 = np.array([2**31 - 1, 2**31 - 1, 4, 1, 2**31 - 1], np.int32)
    ref = cs.reference(c0, c1)
    assert ref["count"] == 4
    want = (np.int64(2**31 - 1) * 3 + 1 + 2**31) % 2**32 - 2**31
    assert ref["sum"] == want
    assert ref["g_count"][5] == 2 and ref["g_count"][13] == 1
    assert ref["g_sum"][5] == np.int32(np.int64(2**32 - 2) - 2**32)


@pytest.mark.parametrize("mesh", [False, True])
def test_scan_phases_match_reference(cs, tmp_path, mesh):
    """Both queries, local and over a 4-device mesh (with the sharded
    load and its ring on the XLA transport the CPU runs), equal the
    numpy reference."""
    path, schema, c0, c1 = cs.phase_heap(str(tmp_path), 64, 5)
    ref = cs.reference(c0, c1)
    m = None
    if mesh:
        from nvme_strom_tpu.parallel.mesh import make_scan_mesh
        devs = jax.devices()[:4]
        cs.phase_mesh_load(path, 64, devs, block=BLOCK, transport="xla")
        m = make_scan_mesh(devs)
    cs.phase_scan(path, schema, ref, mesh=m, require_pallas=False)


def test_calibrate_phase_runs(cs):
    speedup, times = cs.phase_calibrate(jax.devices()[0], batch_pages=8,
                                        iters=1)
    assert speedup > 0 and set(times) == {"xla", "pallas"}


def test_h2d_phase_runs(cs):
    rates = cs.phase_h2d(jax.devices()[0], 4 << 20, reps=3)
    assert len(rates) == 3 and rates == sorted(rates) and rates[0] > 0


def test_run_removes_only_its_own_files(cs, tmp_path, monkeypatch):
    """--data-dir may be a disk holding other files: the run removes the
    files it made and nothing else."""
    keep = tmp_path / "keep.bin"
    keep.write_bytes(b"x")
    monkeypatch.setattr(cs, "phase_device", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(cs, "phase_native", lambda: None)
    monkeypatch.setattr(cs, "phase_scan", lambda *a, **kw: None)
    monkeypatch.setattr(cs, "phase_calibrate", lambda dev: (
        1.0, {"xla": 1.0, "pallas": 1.0}))
    args = argparse.Namespace(chips=1, size_gib=1 / 64, seed=0,
                              data_dir=str(tmp_path))
    assert cs.run(args)["count"] == 1
    assert os.listdir(tmp_path) == ["keep.bin"]


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_main_fails_without_a_tpu(cs, tmp_path, capsys, where):
    """No accelerator (the CPU here), or the script without the repo
    around it: non-zero exit, and the ok line is never printed."""
    if where == "repo":
        rc = cs.main(["--data-dir", str(tmp_path / "d")])
        out = capsys.readouterr().out
    else:
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        env["JAX_PLATFORMS"] = "cpu"
        p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                           env=env, capture_output=True, text=True,
                           timeout=120)
        rc, out = p.returncode, p.stdout
    assert rc != 0
    assert '"ok": true' not in out
