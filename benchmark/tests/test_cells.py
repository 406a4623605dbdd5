"""CPU rehearsal: every cell's code path at a tiny size, called directly.

Data builder, traffic, the timed path through the program's entry points,
the reference comparison and the metric readers; never through run.py
(which demands a TPU), and never a timing."""

import json
import os
import shutil
import time

import jax
import pytest

import harness
import traffic

SPEC = harness.load_spec()
SEED = 2 ** 33 + 12345            # larger than 32 signed bits hold
#: tiny sizes: the same code, a few MiB of data
SMALL = {
    "ckpt_restore_8g": {"bytes": 16 << 20, "checksum_block_bytes": 1 << 20,
                        "patch_chunks": 4, "patch_chunk_bytes": 256 << 10},
    "tpch_sf10": {"scale_factor": 0.01},
}


def cell_named(name, spec=SPEC):
    return next(w for w in spec["workloads"] if w["name"] == name)


def run(cell, data_dir, *, seed=SEED, spec=SPEC, base=harness.HERE,
        control=False, override=None):
    cfg = dict(SMALL.get(cell["config"], {}))
    cfg.update(override or {})
    return harness.run_cell(spec, cell, seed, 0.2, False, time.monotonic(),
                            base=base, devices=jax.devices(),
                            control=control, cfg_override=cfg,
                            data_dir=str(data_dir))


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_cell_runs_correct_with_its_metrics(name, tmp_path):
    cell = cell_named(name)
    got = run(cell, tmp_path)
    assert got["failed"] == 0 and got["records"], got["checks"]
    assert harness.is_correct(got), got["checks"]
    m = harness.metrics_for(SPEC, cell, got, False, None)
    want = {e["name"] for e in SPEC["end_to_end"]
            if harness.applies(e, name)}
    assert set(m) == want
    assert all(v["value"] > 0 for v in m.values())


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_second_run_of_a_seed_writes_nothing(name, tmp_path):
    cell = cell_named(name)
    run(cell, tmp_path)
    ddir = tmp_path / cell["config"]
    before = {p: os.stat(ddir / p).st_mtime_ns for p in os.listdir(ddir)
              if not os.path.isdir(ddir / p)}
    run(cell, tmp_path)
    after = {p: os.stat(ddir / p).st_mtime_ns for p in before}
    assert after == before


def test_restore_new_seed_rewrites_only_patches(tmp_path):
    cell = cell_named("restore8g.direct")
    run(cell, tmp_path, seed=5)
    got = run(cell, tmp_path, seed=6)
    assert harness.is_correct(got), got["checks"]
    st = json.loads((tmp_path / cell["config"] / "state.json").read_text())
    assert st["seed"] == 6 and len(st["patched"]) == 4


def test_requests_follow_the_seed():
    mix = traffic.load(os.path.join(harness.HERE, "traffic",
                                    "q6_stream.json"))
    a = [next(s) for s in [traffic.requests(mix, SEED)] for _ in range(20)]
    s1, s2 = traffic.requests(mix, SEED), traffic.requests(mix, SEED + 1)
    first = [next(s1) for _ in range(20)]
    assert a == first and first != [next(s2) for _ in range(20)]
    sp = traffic.space(mix)
    assert len(sp) == 5 * 8 * 2
    assert all(t is None and r in sp for t, r in first)


def test_open_loop_zipf_mix(tmp_path):
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(
        {"loop": "open", "clients": 1, "rate_per_s": 100.0, "op": "get",
         "draw": {"block": {"zipf": [64, 1.2]}}}))
    mix = traffic.load(str(path))
    got = [next(s) for s in [traffic.requests(mix, SEED)] for _ in range(4000)]
    t = [a for a, _ in got]
    assert all(b > a for a, b in zip(t, t[1:]))
    assert 3000 < 100.0 * t[-1] < 5000          # about 100 a second
    blocks = [r["block"] for _, r in got]
    assert set(blocks) <= set(range(1, 65))
    assert blocks.count(1) > 4 * blocks.count(8) > 0   # skewed to the head
    assert len(traffic.space(mix)) == 64


TOY_CELL = (
    "import numpy as np\n"
    "class Cell:\n"
    "    def __init__(self, ctx): self.ctx = ctx\n"
    "    def prepare(self): pass\n"
    "    def open(self): pass\n"
    "    def warm(self, space): self.serve(space[0])\n"
    "    def counters(self): return {}\n"
    "    def serve(self, req):\n"
    "        import jax.numpy as jnp\n"
    "        n = self.ctx.cfg['n'] * req['k']\n"
    "        return {'work': n, 'got': int(jnp.arange(n).sum()), 'n': n}\n"
    "    def close(self): pass\n"
    "    def check(self, recs):\n"
    "        bad = sum(r['got'] != r['n'] * (r['n'] - 1) // 2"
    " for r in recs)\n"
    "        return {'wrong': {'value': bad, 'limit': 0}}\n")

#: toy mixes, each a new file: a closed loop, an open loop with Zipf
#: draws, and one that names a generator module of its own
TOY_MIXES = {
    "closed": {"loop": "closed", "clients": 1, "op": "sum",
               "draw": {"k": {"range": [1, 3]}}},
    "open_zipf": {"loop": "open", "clients": 1, "rate_per_s": 200.0,
                  "op": "sum", "draw": {"k": {"zipf": [3, 1.5]}}},
    "own_generator": {"generator": "toy_bursts"},
}
TOY_GENERATOR = (
    "def space(mix):\n"
    "    return [{'op': 'sum', 'k': k} for k in (1, 2)]\n"
    "def requests(mix, seed):\n"
    "    t = 0.0\n"
    "    while True:\n"
    "        t += 0.02\n"
    "        for k in (1, 2, 1):\n"
    "            yield t, {'op': 'sum', 'k': k}\n")


@pytest.mark.parametrize("mix", sorted(TOY_MIXES))
def test_toy_cell_from_files_alone(mix, tmp_path):
    """A new configuration, traffic mix and metrics are new files plus new
    entries; no existing file of the harness changes.  The open mixes
    report a latency tail, the kind of metric an open loop is for."""
    base = tmp_path / "bench"
    shutil.copytree(harness.HERE, base,
                    ignore=shutil.ignore_patterns(".data", "tests",
                                                  "__pycache__"))
    (base / "configs" / "toy_sum.json").write_text(json.dumps(
        {"name": "toy_sum", "n": 4096, "reduced": [], "assumed": {}}))
    (base / "configs" / "toy_sum.py").write_text(TOY_CELL)
    (base / "traffic" / "toy_mix.json").write_text(
        json.dumps(TOY_MIXES[mix]))
    (base / "traffic" / "toy_bursts.py").write_text(TOY_GENERATOR)
    (base / "metrics" / "toy_rate.py").write_text(
        "def read(run):\n"
        "    return sum(r['work'] for r in run.records) / run.window_s\n")
    (base / "metrics" / "toy_p95_ms.py").write_text(
        "import numpy as np\n"
        "def read(run):\n"
        "    lat = [r['t1'] - r['ta'] for r in run.records]\n"
        "    return float(np.percentile(lat, 95)) * 1e3\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "toy_sum"})
    spec["workloads"].append({"name": "toy.sum", "config": "toy_sum",
                              "traffic": "toy_mix", "chips": 1})
    spec["end_to_end"].append({"name": "toy_rate", "unit": "1/s",
                               "workloads": ["toy.sum"]})
    spec["end_to_end"].append({"name": "toy_p95_ms", "unit": "ms",
                               "workloads": ["toy.sum"]})
    cell = cell_named("toy.sum", spec)
    got = run(cell, tmp_path / "data", spec=spec, base=str(base))
    assert harness.is_correct(got)
    m = harness.metrics_for(spec, cell, got, False, None, base=str(base))
    assert set(m) == {"toy_rate", "toy_p95_ms", "setup_s"}
    assert m["toy_rate"]["value"] > 0 and m["toy_p95_ms"]["value"] > 0
    recs = got["records"]
    assert all(r["ta"] <= r["t0"] < r["t1"] for r in recs)
    if mix != "closed":      # arrivals paced the window, not the server
        assert recs[-1]["ta"] > 0.1 and len(recs) < 200
    for sub in ("configs", "traffic", "metrics"):
        for f in os.listdir(os.path.join(harness.HERE, sub)):
            if f.endswith((".py", ".json")):
                assert (base / sub / f).read_bytes() == open(
                    os.path.join(harness.HERE, sub, f), "rb").read()


def test_traced_run_sees_every_request(tmp_path):
    cell = cell_named("restore8g.direct")
    got = harness.run_cell(SPEC, cell, SEED, 0.2, True, time.monotonic(),
                           devices=jax.devices(),
                           cfg_override=SMALL[cell["config"]],
                           data_dir=str(tmp_path))
    assert harness.is_correct(got)
    assert got["trace"].n_serve == len(got["records"]) >= 1


def test_split_metric_parts_share_one_reader():
    spec_names = {m["name"] for m in SPEC["per_layer"]}
    assert {"device_idle_share.restore",
            "device_idle_share.query"} <= spec_names
    paths = {harness.reader_path(harness.HERE, n) for n in spec_names
             if n.startswith("device_idle_share.")}
    assert paths == {os.path.join(harness.HERE, "metrics",
                                  "device_idle_share.py")}
    for n in spec_names | {m["name"] for m in SPEC["end_to_end"]}:
        assert os.path.exists(harness.reader_path(harness.HERE, n)), n
