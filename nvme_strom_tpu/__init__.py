"""nvme_strom_tpu — TPU-native SSD→HBM direct-loading framework.

A brand-new framework with the capabilities of NVMe-Strom (SSD→GPU
peer-to-peer DMA; reference at charles-achilefu/nvme-strom), rebuilt
idiomatically for TPU: a native async I/O engine (io_uring / O_DIRECT) feeds
pinned host staging buffers that stream into TPU HBM through PJRT, with
JAX/XLA/Pallas consuming the data in place.  See SURVEY.md for the layer map
and BASELINE.json for performance targets.

Public surface:

* :mod:`~nvme_strom_tpu.api` — UAPI-equivalent command/result types.
* :mod:`~nvme_strom_tpu.engine` — sessions, sources, buffers, planner.
* :mod:`~nvme_strom_tpu.stripe` — RAID-0 stripe remapping.
* :mod:`~nvme_strom_tpu.testing` — loopback fake backends for CI.
"""

from .api import (BufferInfo, DmaTaskState, FileInfo, FsKind, MemCopyResult,
                  StatInfo, StromError)
from .config import config
from .engine import (DmaBuffer, PlainSource, SegmentedSource, Session, Source,
                     StripedSource, check_file, open_source)
from .stats import stats
from .stripe import StripeMap

__version__ = "0.1.0"

__all__ = [
    "BufferInfo", "DmaBuffer", "DmaTaskState", "FileInfo", "FsKind",
    "MemCopyResult", "PlainSource", "SegmentedSource", "Session", "Source",
    "StatInfo", "StripeMap", "StripedSource", "StromError", "check_file",
    "config", "open_source", "stats", "__version__",
]
