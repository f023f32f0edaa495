"""Share of the traced window in the agent arena's kernel launches: the
program's ``arena.launch`` spans, each a jitted or eager kernel call
until it returns (bench/program.py). A compile inside the window would
show here."""

from bench.program import LAUNCH, calls, seconds, window


def read(run):
    p = window()
    if p is None or run.window_s <= 0 or not calls(p, (LAUNCH,)):
        return None
    return 100.0 * seconds(p, (LAUNCH,)) / run.window_s
