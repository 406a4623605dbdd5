"""load_setup_share.<part>: the caller's time in ``load_file_to_device``'s
own set-up and tear-down (destination, staging ring, the final fence,
unmap: ``clk_load_setup``) moved over the window, as a share of the
window.  Layer: staging/landing."""


def read(run):
    if "clk_load_setup" not in run.counters:
        return None
    return run.counters["clk_load_setup"] / 1e9 / run.window_s
