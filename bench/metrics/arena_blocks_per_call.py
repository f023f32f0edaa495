"""Device blocks an agent-arena call launches on, over the traced
window: the program's ``arena.dispatch/*`` counters (one launch per
16-row block staged) over the calls of ``arena.h2d`` (one copy-in per
flush pass and per predict call; bench/program.py). Agents spread over
more blocks, or passes that touch more functions, read higher. None
where no arena call copied in."""

from bench.program import calls, dispatches, window

H2D = ("arena.h2d",)


def read(run):
    p = window()
    if p is None or not calls(p, H2D):
        return None
    return dispatches(p) / calls(p, H2D)
