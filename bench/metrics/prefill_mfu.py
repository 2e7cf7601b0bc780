"""Prefill (``models/api.prefill`` through admission): model FLOPs of the
prompts admitted in the window, over the admission time at the chip's
peak.  A tick's admission time is its device-busy time beyond the median
of the ticks that admitted nothing.  Device trace."""
from bench.record import median


def read(run):
    ticks = run.tick_busy() if run.kind == "serve" else None
    if not ticks:
        return None
    plain = [b for t, b in ticks if not t.admitted]
    if not plain:
        return None
    base = median(plain)
    admitting = [(t, b) for t, b in ticks if t.admitted]
    seconds = sum(max(0.0, b - base) for _, b in admitting)
    flops = sum(run.ref.prefill_flops(run.dims, n)
                for t, _ in admitting for n in t.admitted)
    if seconds <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (seconds * run.peaks["bf16_flops"])
