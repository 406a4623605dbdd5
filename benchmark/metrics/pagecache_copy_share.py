"""pagecache_copy_share.<part>: the caller's time copying write-back
chunks out of the page cache (``clk_pagecache_copy``, the
``pagecache_copy`` stage inside ``Session.memcpy_ssd2ram``) moved over
the window, as a share of the window.  Layer: engine."""


def read(run):
    if "clk_pagecache_copy" not in run.counters:
        return None
    return run.counters["clk_pagecache_copy"] / 1e9 / run.window_s
