"""Share of the traced window in the agent arena's own host work: the
self seconds of its four public calls' program spans, each call's time
less its copy, launch and read-back spans (bench/program.py). That is
cost matrices, stacking, padding, slot look-ups and pass building. With
the transfer and launch shares it makes up the arena's outermost time."""

from bench.program import ARENA_CALLS, arena_host_s, calls, window


def read(run):
    p = window()
    if p is None or run.window_s <= 0 or not calls(p, ARENA_CALLS):
        return None
    return 100.0 * arena_host_s(p) / run.window_s
