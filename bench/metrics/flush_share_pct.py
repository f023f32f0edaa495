"""Share of the window spent in the agent arena's deferred-update flush
(``ArenaEngine.flush``)."""


def read(run):
    if run.window_s <= 0 or not run.probe.calls["arena.flush"]:
        return None
    return 100.0 * run.probe.seconds["arena.flush"] / run.window_s
