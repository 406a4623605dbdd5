"""mesh_wait_share.<part>: the caller's time waiting on a mesh scan step's
shard reads (``clk_mesh_wait``, the ``mesh_wait`` stage of
``parallel/stream.ShardedBatchStream``: each poll and blocking wait of
its completion fan-in) moved over the traced part, as a share of its
length.  Layer: engine."""


def read(run):
    if "clk_mesh_wait" not in run.counters:
        return None
    return run.counters["clk_mesh_wait"] / 1e9 / run.window_s
