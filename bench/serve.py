"""Serving cells: an open loop of requests through ``repro.serve.Engine``.

The window drives the engine's own entry points, ``submit`` and ``tick``.
A request is submitted once it is due; every token it has when a tick
returns is stamped with that tick's end, which is when a caller of the
engine could first see it.  Requests due in the window finish in a drain
after it, of at most ``drain_s``; one still unfinished then has failed.

Correctness: once the window has closed and the engine is freed, a sample
of the finished requests drawn from the seed, the longest among them, is
run through the configuration's plain float32 reference: the prompt and
every served token but the last.  Each served token's reference logit
lies some gap below the reference's best at its position; the widest gap
is held to the config's limit.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import record, traffic
from bench.trace import Spans

clock = time.perf_counter


class Loop:
    """The open loop over one engine."""

    def __init__(self, engine, spans: Spans):
        self.engine = engine
        self.span = spans
        self.ticks: list[record.Tick] = []
        self.inflight = []
        self.info = {}

    def submit(self, req, due_abs: float) -> None:
        from repro.serve import Request
        r = Request(rid=req.rid, prompt=req.prompt, max_new=req.max_new)
        with self.span("bench.submit"):
            self.engine.submit(r)
        self.inflight.append(r)
        self.info[r.rid] = {"due": due_abs, "admit": None, "times": [],
                            "prompt_len": len(req.prompt), "req": r}

    def tick(self) -> None:
        eng = self.engine
        queued = list(eng.queue)
        live = [len(r.prompt) + len(r.generated) - 1
                for r in eng.active.values()]
        start = clock()
        with self.span("bench.tick"):
            eng.tick()
        end = clock()
        admitted = [r for r in queued if r.slot is not None]
        for r in admitted:
            self.info[r.rid]["admit"] = start
        still = []
        for r in self.inflight:
            times = self.info[r.rid]["times"]
            times.extend([end] * (len(r.generated) - len(times)))
            if not r.done:
                still.append(r)
        self.inflight = still
        self.ticks.append(record.Tick(start, end,
                                      [len(r.prompt) for r in admitted],
                                      live))

    def busy(self) -> bool:
        return bool(self.engine.queue or self.engine.active)

    def wait_until(self, t: float) -> None:
        with self.span("bench.wait"):
            time.sleep(max(0.0, t - clock()))


def run_window(loop: Loop, schedule, seconds: float, drain_s: float,
               capture=None):
    """Drive the open loop for ``seconds``, then drain; ``capture`` (a
    ``trace.Capture``) profiles the start of the window.  Returns the
    window's (open, close) and the drain's end, host clock."""
    n = len(schedule)
    i = 0
    if capture is not None:
        capture.start()
    t_open = clock()
    close = t_open + seconds
    while True:
        now = clock()
        if capture is not None:
            capture.poll(now)
        if now >= close:
            break
        while i < n and t_open + schedule[i].due <= now:
            loop.submit(schedule[i], t_open + schedule[i].due)
            i += 1
        if loop.busy():
            loop.tick()
        else:
            loop.wait_until(min(close, t_open + schedule[i].due)
                            if i < n else close)
    t_close = clock()
    if capture is not None:
        capture.stop()
    for req in schedule[i:]:            # due in the window, sent late
        loop.submit(req, t_open + req.due)
    deadline = clock() + drain_s
    while loop.inflight and clock() < deadline:
        loop.tick()
    return (t_open, t_close), clock()


def latencies(loop: Loop, drain_end: float):
    """TTFT of each request (s, from when it was due), every gap between
    consecutive tokens, and the number that never finished.  A request
    that never finished has the time to the drain's end as its TTFT when
    it has no token: the least it could have been."""
    ttft, gaps, failed = [], [], 0
    for rec in loop.info.values():
        times, req = rec["times"], rec["req"]
        if not req.done:
            failed += 1
        ttft.append((times[0] if times else drain_end) - rec["due"])
        gaps.extend(np.diff(times).tolist())
    return ttft, gaps, failed


def warm_up(engine, mix: dict, vocab: int, seed: int) -> None:
    """Every prompt length of the grid and every slot, two tokens each."""
    from repro.serve import Request
    for req in traffic.warmup_requests(mix, engine.slots, vocab, seed):
        engine.submit(Request(rid=req.rid, prompt=req.prompt,
                              max_new=req.max_new))
    engine.run()
    engine.finished.clear()


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------
def check_sample(loop: Loop, k: int, seed: int) -> list:
    """``k`` finished requests drawn from the seed, the longest among
    them: (prompt, served tokens) pairs."""
    done = sorted((rec["req"] for rec in loop.info.values()
                   if rec["req"].done), key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.generated),
                                       -r.rid))
    rest = [r for r in done if r is not longest]
    rng = traffic.rng_for(seed, 4)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    chosen = [longest] + [rest[j] for j in sorted(pick)]
    return [(np.asarray(r.prompt, np.int32), list(r.generated))
            for r in chosen]


def reference_gaps(ref, conf: dict, w, sample, shape, mode: str = "f32",
                   pick: str = "served"):
    """Per sampled request, the gap below the float32 reference's best of
    each token: the served one (``pick="served"``), or the one that the
    reference at ``mode`` puts first (``pick="mode"``).  Every sequence is
    padded to ``shape`` = (positions, served tokens), the most the mix can
    send, so that the reference compiles once."""
    import jax.numpy as jnp
    out = []
    for prompt, served in sample:
        s, n = len(prompt), len(served)
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        seq = np.pad(seq, (0, shape[0] - len(seq)))
        rows = np.pad(np.arange(s - 1, s - 1 + n), (0, shape[1] - n),
                      mode="edge")
        logits = ref.logits_at(w, conf, seq, rows, "f32")
        if pick == "served":
            tok = np.pad(np.asarray(served, np.int32), (0, len(rows) - n),
                         mode="edge")
        else:
            tok = np.asarray(jnp.argmax(
                ref.logits_at(w, conf, seq, rows, mode), axis=-1))
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, jnp.asarray(tok)[:, None],
                                  axis=-1)[:, 0]
        out.append(np.asarray(best - got)[:n])
    return out


def drive(ctx):
    """Set-up and the window: the weights from the seed, the engine warmed
    on every shape, the open loop and its drain.  Returns the loop (which
    holds the engine) and what the window recorded."""
    from repro.serve import Engine

    conf, mix, seed = ctx.conf, ctx.mix, ctx.seed
    cfg = ctx.prog.arch_config(conf)
    eng_cfg = mix["engine"]
    engine = Engine(cfg, ctx.prog.program_weights(conf, cfg, seed),
                    slots=eng_cfg["slots"], max_seq=eng_cfg["max_seq"])
    warm_up(engine, mix, cfg.vocab, seed)
    schedule = traffic.serve_schedule(mix, seed, ctx.seconds, cfg.vocab)
    spans = Spans()
    loop = Loop(engine, spans)
    del engine
    compiles0 = ctx.meter.compiles
    capture = None
    if ctx.trace:
        from bench import trace as tr
        capture = tr.Capture(min(ctx.seconds, mix["trace_seconds"]))
    gc.collect()
    gc.disable()
    try:
        window, drain_end = run_window(loop, schedule, ctx.seconds,
                                       mix["drain_s"], capture)
    finally:
        gc.enable()
    return {"loop": loop, "schedule": schedule, "window": window,
            "drain_end": drain_end, "spans": spans, "trace": capture,
            "compiles": ctx.meter.compiles - compiles0}


def max_gap(ctx, sample, mode: str = "f32", pick: str = "served") -> float:
    """The widest gap over ``sample``, on reference weights made anew."""
    w = ctx.ref.make_weights(ctx.conf, ctx.seed)
    shape = (ctx.mix["engine"]["max_seq"], ctx.mix["output_len"]["max"])
    gaps = reference_gaps(ctx.ref, ctx.conf, w, sample, shape, mode, pick)
    return max((float(g.max()) for g in gaps), default=float("inf"))


def run(ctx) -> dict:
    conf, mix = ctx.conf, ctx.mix
    d = drive(ctx)
    loop, window, compiles = d["loop"], d["window"], d["compiles"]
    mem = ctx.memory_peak()
    ttft, gaps, failed = latencies(loop, d["drain_end"])
    run_rec = record.Run(
        "serve", ctx.ref, ctx.ref.dims(conf), ctx.peaks, window,
        ticks=loop.ticks, requests=list(loop.info.values()))
    if d["trace"] is not None:             # per-layer: the traced part
        run_rec.trace = d["trace"].read(d["spans"].recorded)
        run_rec.window = d["trace"].window
    sample = check_sample(loop, mix["check"]["requests"], ctx.seed)
    del d, loop
    gc.collect()                        # the engine and its weights go
    gap = max_gap(ctx, sample)
    limit = conf["bench"]["checks"]["serve_max_logit_gap"]
    checks = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "compiles_in_window": {"value": compiles, "limit": 0},
    }
    correct = bool(np.isfinite(gap)) and gap <= limit and compiles == 0
    if ctx.trace:
        metrics = record.read_metrics(run_rec, ctx.metrics)
        summary = run_rec.trace_summary()
    else:
        values = {"setup_s": window[0] - ctx.t0,
                  "ttft_p95_ms": record.p95(ttft) * 1e3,
                  "itl_p95_ms": record.p95(gaps) * 1e3 if gaps else None}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in ctx.metrics if values.get(m["name"]) is not None}
        summary = {}
    return {"correct": correct, "attempted": len(run_rec.requests),
            "failed": failed, "metrics": metrics, "memory_peak_bytes": mem,
            "summary": summary, "checks": checks}
