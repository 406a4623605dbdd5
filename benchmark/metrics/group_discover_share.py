"""group_discover_share.<part>: the caller's time in the value-keyed GROUP
BY's key discovery (``clk_group_discover``, the ``group_discover`` stage
of ``Query._discover_group_keys``; the discovery scan's ``vfs_read`` and
``staging_retire`` stages nest in it) moved over the window, as a share
of the window.  Layer: scan."""


def read(run):
    if "clk_group_discover" not in run.counters:
        return None
    return run.counters["clk_group_discover"] / 1e9 / run.window_s
