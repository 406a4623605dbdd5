"""The examples/ scripts must stay runnable — they are the first thing a
new user executes, and a bit-rotted example is worse than none."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("script", ["01_direct_load.py", "02_query.py",
                                    "03_distributed.py",
                                    "04_indexes_and_joins.py",
                                    "05_sql.py"])
def test_example_runs_clean(script, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    args = [sys.executable, os.path.join(REPO, "examples", script)]
    if script == "01_direct_load.py":
        args.append(str(tmp_path / "ex.bin"))   # keep /tmp clean in CI
    out = subprocess.run(args, capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stdout + out.stderr
    assert out.stdout.strip(), "example printed nothing"
