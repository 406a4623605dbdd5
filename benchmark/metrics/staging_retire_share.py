"""staging_retire_share.<part>: the caller's time issuing each staged
batch host -> device and its landing (``clk_staging_retire``) moved over
the window, as a share of the window.  Layer: staging/landing."""


def read(run):
    if "clk_staging_retire" not in run.counters:
        return None
    return run.counters["clk_staging_retire"] / 1e9 / run.window_s
