#!/usr/bin/env python3
"""Readings that set a cell's correctness limits; run on the chip, never
by the benchmark's own runs.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 10]

For each seed, in one process: the program's readings, as a run of the
cell takes them, and the control's: the reference itself put in the
program's place and computed in float8 (the nearest precision below the
bfloat16 the configuration states).  For a training cell also the planted
fault "half of the batch left out, the mean taken over the rest", read on
the reference.  One JSON line per seed.  A limit lies above the largest
program reading and below the smallest control (or fault) reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run as harness  # bench/run.py, which puts the checkout on sys.path


def serve_readings(ctx, faults: bool = True) -> dict:
    from bench import serve
    d = serve.drive(ctx)
    sample = serve.check_sample(d["loop"], ctx.mix["check"]["requests"],
                                ctx.seed)
    failed = serve.latencies(d["loop"], d["drain_end"])[2]
    del d
    gc.collect()
    out = {"program": {"max_logit_gap": serve.max_gap(ctx, sample)},
           "tokens_checked": sum(len(s) for _, s in sample),
           "failed": failed}
    if faults:
        out["control"] = {"max_logit_gap": serve.max_gap(ctx, sample, "fp8",
                                                         "mode")}
    return out


def train_readings(ctx, faults: bool = True) -> dict:
    from bench import train
    trainer = train.Trainer(ctx)
    read = trainer.first_steps(ctx.mix["check_steps"])
    del trainer
    gc.collect()
    n = ctx.mix["check_steps"]
    want = train.reference_steps(ctx.ref, ctx.conf, ctx.mix, ctx.seed, n)

    def as_read(r):
        return {"losses": r[0], "grad": r[1], "change": r[2]}

    out = {}
    if faults:
        fp8 = train.reference_steps(ctx.ref, ctx.conf, ctx.mix, ctx.seed, n,
                                    mode="fp8")
        half = train.reference_steps(ctx.ref, ctx.conf, ctx.mix, ctx.seed,
                                     n, rows=slice(0, ctx.mix["batch"] // 2))
        out = {"control": train.compare(as_read(fp8), want),
               "half_batch": train.compare(as_read(half), want)}
    worst = {}
    for name, key, keep in (("grad", "grad", None),
                            ("change", "change", train.moving_leaves(want[1]))):
        gaps = train.leaf_gaps(read[key], want[1 if key == "grad" else 2],
                               keep)
        top = sorted(gaps.items(), key=lambda kv: -kv[1])[:4]
        worst[name] = [[f"{k[0]}[{k[1]}]", v, read[key][k],
                        want[1 if key == "grad" else 2][k]] for k, v in top]
    return dict(out, program=train.compare(read, want), worst_leaves=worst,
                losses={"program": read["losses"], "reference": want[0]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault-seeds", type=int, default=1 << 30,
                    help="read the control and the faults on this many of "
                         "the seeds, the first ones; the program on all")
    args = ap.parse_args(argv)
    from bench import device
    bench = harness.load_benchmark()
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    try:
        devices = device.require_tpu(cell["chips"])
    except device.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    device.enable_compile_cache(str(harness.CACHE_DIR))
    meter = device.CompileMeter()
    conf = harness.load_config(cell["config"])
    from bench import traffic
    mix = traffic.load_mix(cell["traffic"])
    ref, prog = harness.family(conf)
    readings = serve_readings if mix["kind"] == "serve" else train_readings
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ctx = harness.Context(cell, conf, mix, ref, prog, seed, args.seconds,
                              False, [], device.peaks(devices[0].device_kind),
                              meter, lambda: 0, t)
        out = dict(readings(ctx, i < args.fault_seeds), seed=seed,
                   seconds=time.perf_counter() - t)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
