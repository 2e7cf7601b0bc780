"""Serving engine (admission and slots): the 95th percentile, over the
requests due in the window and admitted, of the time from when a request
was due to the start of the tick that admitted it.  Host clock."""
from bench.record import p95


def read(run):
    if run.kind != "serve":
        return None
    lo, hi = run.window
    waits = [r["admit"] - r["due"] for r in run.requests
             if r["admit"] is not None and lo <= r["due"] < hi]
    return p95(waits) * 1e3 if waits else None
