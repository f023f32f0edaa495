"""Terminal invocations (completed, shed, timed out or OOM-killed) in
the window, the stopped pass included, per second of window."""


def read(run):
    return run.terminal / run.window_s if run.window_s > 0 else None
