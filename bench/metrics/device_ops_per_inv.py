"""Device operations in the traced window per terminal invocation in it
(the window is traced whole)."""


def read(run):
    t = run.trace
    if not t or not t.get("devices") or not run.terminal:
        return None
    return t["ops"] / run.terminal
