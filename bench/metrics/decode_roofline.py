"""Decode step (``models/api.decode_step``): over the ticks that admitted
nothing, the least time the chip could take for their decode steps, over
their device-busy time.  A step's least time is the larger of its FLOPs at
the peak and its bytes at the peak bandwidth; the bytes are every weight
once, the live sequences' cached keys and values (or state) read, and the
new ones written, counted from shapes and live lengths (the reference
module's ``decode_cost``).  Device trace."""


def read(run):
    ticks = run.tick_busy() if run.kind == "serve" else None
    ticks = [(t, b) for t, b in ticks or () if not t.admitted and t.live]
    busy = sum(b for _, b in ticks)
    if busy <= 0:
        return None
    p = run.peaks
    least = 0.0
    for t, _ in ticks:
        flops, nbytes = run.ref.decode_cost(run.dims, t.live)
        least += max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / busy
