"""Serving engine, host loop: the median device-idle time before a decode
step, over the ticks that admitted nothing and follow another tick.  It
runs from the end of the last program the device ran before the tick's
``jit_decode_step`` (the previous tick's sampling) to that program's
start: the host fetches the sampled tokens, retires, builds the next
token array and dispatches meanwhile.  Device trace."""
import bisect

from bench.record import median

PROGRAM = "jit_decode_step("


def read(run):
    if run.kind != "serve" or not run.trace or not run.trace.get("programs"):
        return None
    events = sorted(run.trace["programs"][sorted(run.trace["programs"])[0]],
                    key=lambda e: e[1])
    starts = [s for _, s, _ in events]
    gaps = []
    for prev, tick in zip(run.ticks, run.ticks[1:]):
        if tick.admitted:
            continue
        i = bisect.bisect_left(starts, tick.start * 1e9)
        while i < len(events) and not events[i][0].startswith(PROGRAM):
            i += 1
        if i == 0 or i == len(events) or starts[i] > tick.end * 1e9:
            continue
        _, before, dur = events[i - 1]
        if before < prev.start * 1e9:       # no program in the tick before
            continue
        gaps.append(max(0.0, starts[i] - (before + dur)))
    return median(gaps) / 1e6 if gaps else None
