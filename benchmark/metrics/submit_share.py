"""submit_share.<part>: the caller's time submitting reads
(``clk_ioctl_memcpy_submit``, the ``ioctl_memcpy_submit`` stage of
``Session.memcpy_ssd2ram``: planning, residency probes, handing requests
to the lanes) less the page-cache copies nested in it
(``clk_pagecache_copy``), moved over the window, as a share of the
window.  Layer: engine."""


def read(run):
    c = run.counters
    if "clk_ioctl_memcpy_submit" not in c or "clk_pagecache_copy" not in c:
        return None
    return ((c["clk_ioctl_memcpy_submit"] - c["clk_pagecache_copy"])
            / 1e9 / run.window_s)
