"""Device dispatches of the agent arena's kernels per terminal
invocation in the traced window: the program's ``arena.dispatch/*``
counters summed (bench/program.py). Each dispatch is one host round
trip: copies in, a launch, reads back."""

from bench.program import dispatches, window


def read(run):
    p = window()
    if p is None or not run.terminal:
        return None
    n = dispatches(p)
    return n / run.terminal if n else None
