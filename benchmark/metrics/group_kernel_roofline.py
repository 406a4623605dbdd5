"""group_kernel_roofline: the grouped expression kernel's share of its
roofline.

Kernel time: the device time of every run of the program named
``jit_group_efn`` (the per-batch grouped expression-aggregate kernel
that ``ops/groupby.make_group_efn`` builds for TPC-H Q1) in the traced
window; the key-discovery gather of the same queries is another
program and not counted.  Least time: the bytes the kernel must move,
from :func:`group_bytes`, at the chip's HBM bandwidth from peaks.json.
The byte bound is the only one that can be stated: the chip publishes no
int64 rate (int64 arithmetic runs as pairs of 32-bit vector
operations), so the share says how far the kernel is from streaming its
input at HBM speed, not which unit bounds it.  Layer: kernels.
"""

KERNEL = "jit_group_efn"
WORD = 8              # int64 counts and sums


def group_bytes(pages: int, page_bytes: int, runs: int,
                out_words: int) -> int:
    """Bytes the kernel must move over *pages* pages in *runs* runs:
    every page read once from HBM, plus each run's per-group outputs."""
    return pages * page_bytes + runs * out_words * WORD


def read(run):
    t = run.trace
    if t is None or run.peaks is None or t.n_serve == 0:
        return None
    kernel_s = t.module_time(KERNEL)
    runs = t.module_count(KERNEL)
    if kernel_s <= 0:
        return None
    f = run.facts
    nbytes = group_bytes(t.n_serve * f["pages_per_request"], f["page_bytes"],
                         runs, f["group_out_words"])
    return nbytes / run.peaks["hbm_bytes_per_s"] / kernel_s * 100.0
