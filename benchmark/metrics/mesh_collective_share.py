"""mesh_collective_share.<part>: the share of the scan kernel's device
time spent in cross-chip collectives.

Kernel time: the device time of every run of the program named
``jit_efn`` (the fused Q6 scan kernel, partitioned by XLA over the
mesh) in the traced window, summed over the devices.  Collective time:
the device time of the ops inside those runs whose HLO name is a
collective (``all-reduce``, ``all-gather``, ``reduce-scatter``,
``collective-permute``, ``all-to-all``, each with any ``-start``/
``-done`` half and instance suffix: on a v5e the four chips' int64
partial sums meet in one tuple ``all-reduce.<n>``), summed over the
same devices.  A single-chip kernel has none, so the share reads 0 there.
Layer: kernels.
"""

import re

KERNEL = "jit_efn"
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)([.-]|$)")


def collective_s(op_s: dict, kernel: str = KERNEL) -> float:
    """Device seconds of the collective ops inside *kernel*'s runs
    (``op_s`` keys are ``<program>/<op>``)."""
    total = 0.0
    for key, s in op_s.items():
        owner, _, op = key.partition("/")
        if owner == kernel and COLLECTIVE.match(op):
            total += s
    return total


def read(run):
    t = run.trace
    if t is None:
        return None
    kernel_s = t.module_time(KERNEL)
    if kernel_s <= 0:
        return None
    return collective_s(t.op_s) / kernel_s
