"""Share of the traced window in the agent arena's copies between host
and device: the program's ``arena.h2d`` and ``arena.d2h`` spans together
(bench/program.py). Taken together because dispatch and copies to the
device are asynchronous, so a read back absorbs the wait for the copies
and the kernel before it."""

from bench.program import TRANSFERS, calls, seconds, window


def read(run):
    p = window()
    if p is None or run.window_s <= 0 or not calls(p, TRANSFERS):
        return None
    return 100.0 * seconds(p, TRANSFERS) / run.window_s
