"""mesh_submit_share.<part>: the caller's time submitting a mesh scan
step's engine reads for every shard (``clk_mesh_submit``, the
``mesh_submit`` stage of ``parallel/stream.ShardedBatchStream``; the
engine's own ``ioctl_memcpy_submit`` and ``pagecache_copy`` nest in it)
moved over the traced part, as a share of its length.  Layer: engine."""


def read(run):
    if "clk_mesh_submit" not in run.counters:
        return None
    return run.counters["clk_mesh_submit"] / 1e9 / run.window_s
