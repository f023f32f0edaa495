"""Share of the window that no policy or router span covers: the event
loop's own bookkeeping (``serving.simulator``), the simulator's
construction for each pass, and the router's calibration hook."""


def read(run):
    if run.window_s <= 0:
        return None
    return 100.0 * (run.window_s - run.probe.covered) / run.window_s
