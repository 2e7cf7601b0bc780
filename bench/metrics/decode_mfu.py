"""Decode step: model FLOPs of the tokens decoded in the ticks that
admitted nothing, over their device-busy time at the chip's peak.
Device trace."""


def read(run):
    ticks = run.tick_busy() if run.kind == "serve" else None
    ticks = [(t, b) for t, b in ticks or () if not t.admitted and t.live]
    busy = sum(b for _, b in ticks)
    if busy <= 0:
        return None
    flops = sum(run.ref.decode_cost(run.dims, t.live)[0] for t, _ in ticks)
    return 100.0 * flops / (busy * run.peaks["bf16_flops"])
