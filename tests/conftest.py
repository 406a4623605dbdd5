"""Test harness setup.

Forces JAX onto a virtual 8-device CPU mesh (multi-chip shardings are
validated without TPU hardware) — must run before any jax import.
"""

import os

# Must happen before any backend init.  The config.update below pins the
# tests to the CPU even where JAX_PLATFORMS names an accelerator.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Optional dev dependency: without hypothesis the property suite cannot
# even collect, which used to fail every marker-filtered run (e.g. the
# perf-smoke gate) on a collection error unrelated to the filter.
collect_ignore = []
try:
    import hypothesis  # noqa: F401
except ImportError:
    collect_ignore.append("test_property.py")


@pytest.fixture()
def tmp_data_file(tmp_path):
    """A 4MB deterministic test file on the real filesystem (ext4 here, so
    O_DIRECT works)."""
    from nvme_strom_tpu.testing import make_test_file
    path = str(tmp_path / "data.bin")
    make_test_file(path, 4 << 20)
    return path


@pytest.fixture(autouse=True)
def _reset_config():
    """Isolate config mutations between tests (atomic restore: per-key
    set() can trip cross-variable invariants depending on key order).
    The flight recorder caches trace_policy at configure() time, so it is
    re-synced and cleared alongside the restore; the residency cache
    caches cache_bytes the same way and also holds cross-test slabs, so
    it is emptied and re-synced too (cache_bytes defaults to 0 = off)."""
    from nvme_strom_tpu.cache import residency_cache
    from nvme_strom_tpu.config import config
    from nvme_strom_tpu.trace import recorder
    snap = config.snapshot()
    yield
    config.restore(snap)
    recorder.configure()
    recorder.clear()
    residency_cache.clear()
    residency_cache.configure()
    # the device tier caches hbm_cache_bytes the same way (and holds
    # device arrays across tests otherwise); restore turns it back off
    from nvme_strom_tpu.serving.hbm_tier import hbm_tier
    hbm_tier.configure()
    # the integrity domain caches the integrity mode at configure() time
    from nvme_strom_tpu.integrity import domain
    domain.configure()
