"""engine_wait_share.<part>: the caller's time blocked in
``Session.memcpy_wait`` until a read task completes
(``clk_ioctl_memcpy_wait``) moved over the window, as a share of the
window.  Layer: engine."""


def read(run):
    if "clk_ioctl_memcpy_wait" not in run.counters:
        return None
    return run.counters["clk_ioctl_memcpy_wait"] / 1e9 / run.window_s
