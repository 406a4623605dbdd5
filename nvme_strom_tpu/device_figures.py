"""Planning figures of the devices this repo knows, keyed by ``device_kind``.

The planner needs two properties of the accelerator it drives:

* ``h2d_gbps`` — the host→device copy rate in GiB/s (the unit of the
  live meters), which decides whether a
  packed column crosses the link packed (``scan/planner.decide_pushdown``);
* ``groupby_f32_pallas_speedup`` — XLA time over Pallas time of a float32
  GROUP BY at the scan batch width, which decides whether float GROUP BY
  takes the Pallas kernel (``ops/groupby.groupby_kernel_auto``).

Every entry names its source.  A kind that is not here is an error: the
planner never plans one device with another's figures.  The SSD rate is
not a device figure — it belongs to the host's disk and comes from config
or the live meter (``scan/planner.transport_rates``).
"""

from __future__ import annotations

import errno
from dataclasses import dataclass
from typing import Optional

from .api import StromError

__all__ = ["DeviceFigures", "FIGURES", "device_figures"]


@dataclass(frozen=True)
class DeviceFigures:
    h2d_gbps: float
    groupby_f32_pallas_speedup: float
    source: str


FIGURES = {
    # chip_smoke.py on one TPU v5e, PR 21 (its informational lines).
    # h2d: 1 GiB of a page-aligned host buffer in staged 16 MiB slices
    # through hbm.staging.h2d_transfer, median of 5 (10.6127-12.7522
    # GiB/s, run 9); one unpinned 1 GiB device_put read only 1.2297-
    # 1.3740 GiB/s (runs 2, 5, 6).  float32 GROUP BY at 2048 pages:
    # XLA 73.254 ms vs Pallas 0.796 ms (run 2)
    "TPU v5 lite": DeviceFigures(
        h2d_gbps=12.66436413329446,
        groupby_f32_pallas_speedup=91.97030498094549,
        source="chip_smoke.py on one TPU v5e, PR 21"),
    "cpu": DeviceFigures(
        h2d_gbps=1.06, groupby_f32_pallas_speedup=0.851,
        source="test setting for the CPU backend, not a measurement: "
               "the values keep the CPU tests' planning decisions"),
}


def device_figures(kind: Optional[str] = None) -> DeviceFigures:
    """The figures of *kind* (default: this process's first device)."""
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    got = FIGURES.get(kind)
    if got is None:
        raise StromError(errno.ENODEV,
                         f"no planning figures for device kind {kind!r} "
                         f"(known: {sorted(FIGURES)}); measure them with "
                         f"chip_smoke.py and add them to "
                         f"nvme_strom_tpu/device_figures.py, or set "
                         f"pushdown_h2d_gbps")
    return got
