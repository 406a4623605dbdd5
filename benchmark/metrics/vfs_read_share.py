"""vfs_read_share.<part>: the caller's time reading page batches of the
table through the buffered (VFS) path (``clk_vfs_read``, the ``vfs_read``
stage of ``Query._vfs_scan``) moved over the window, as a share of the
window.  Layer: scan."""


def read(run):
    if "clk_vfs_read" not in run.counters:
        return None
    return run.counters["clk_vfs_read"] / 1e9 / run.window_s
