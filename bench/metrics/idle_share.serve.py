"""Device: the share of the window in which no operation ran on the
device, 1 - (union of device-operation intervals) / window, in the
serve cells.  Device trace."""


def read(run):
    if run.kind != "serve":
        return None
    s = run.trace_summary()
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
