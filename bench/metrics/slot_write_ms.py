"""Serving engine, admission: the mean device time of one slot write, the
engine's ``jit_write_slot`` program, which runs once per admission and
copies the whole cache with the new request's slot in it.  Over the
traced window.  Device trace."""
PROGRAM = "jit_write_slot("


def read(run):
    if run.kind != "serve" or not run.trace or not run.trace.get("programs"):
        return None
    events = run.trace["programs"][sorted(run.trace["programs"])[0]]
    runs = [d for name, _, d in events if name.startswith(PROGRAM)]
    return sum(runs) / len(runs) / 1e6 if runs else None
