"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals / window)."""


def read(run):
    t = run.trace
    if not t or not t.get("devices") or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
