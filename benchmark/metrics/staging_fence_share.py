"""staging_fence_share.<part>: the caller's time waiting for the device
to finish reading a staging buffer before reusing it
(``clk_staging_fence``) moved over the window, as a share of the
window.  Layer: staging/landing."""


def read(run):
    if "clk_staging_fence" not in run.counters:
        return None
    return run.counters["clk_staging_fence"] / 1e9 / run.window_s
