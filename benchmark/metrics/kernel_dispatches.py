"""kernel_dispatches.<part>: scan-kernel calls (``nr_kernel_dispatch``:
one per page batch, on the VFS and the direct path alike) moved over the
traced part, per query the trace served (its ``bench.serve`` spans).
Layer: scan."""


def read(run):
    t = run.trace
    if t is None or t.n_serve == 0 or "nr_kernel_dispatch" not in \
            run.counters:
        return None
    return run.counters["nr_kernel_dispatch"] / t.n_serve
