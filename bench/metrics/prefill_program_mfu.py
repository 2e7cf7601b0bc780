"""Prefill program (the engine's ``jit_prefill``): model FLOPs of the
prompts it ran over the device time of its runs in the traced window, at
the chip's peak.  Each run is paired with the admission that dispatched
it, the latest one to begin before it (the request's ``t_admit`` stamp,
taken as the engine's ``repro.admit`` span opens).  Unlike
``prefill_mfu`` it leaves out the slot write and whatever else an
admitting tick runs.  Device trace."""
import bisect

PROGRAM = "jit_prefill("


def read(run):
    if run.kind != "serve" or not run.trace or not run.trace.get("programs"):
        return None
    admits = sorted((r["req"].t_admit * 1e9, r["prompt_len"])
                    for r in run.requests
                    if getattr(r.get("req"), "t_admit", None) is not None)
    starts = [t for t, _ in admits]
    events = run.trace["programs"][sorted(run.trace["programs"])[0]]
    paired, seconds, flops = set(), 0.0, 0.0
    for name, start, dur in events:
        i = bisect.bisect_right(starts, start) - 1
        if not name.startswith(PROGRAM) or i < 0 or i in paired:
            continue
        paired.add(i)
        seconds += dur / 1e9
        flops += run.ref.prefill_flops(run.dims, admits[i][1])
    if seconds <= 0:
        return None
    return 100.0 * flops / (seconds * run.peaks["bf16_flops"])
