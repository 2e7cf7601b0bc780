#!/usr/bin/env python3
"""The rate sweep that finds a serving cell's knee; run on the chip once,
when a cell is defined, never by the benchmark's own runs.

    python3 bench/sweep.py --workload <cell> --rates 4,6,8 --seconds 30

Runs the cell's traffic at each offered rate in turn (one process, one
seed) and prints one JSON line per rate: the tails, the tokens completed
per second, the requests still queued or decoding when the window closed
(a backlog that grows with the rate is past the knee) and the drain's
length.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import sys

import run as harness  # bench/run.py, which puts the checkout on sys.path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from bench import device, record, serve, traffic
    cell = next(c for c in harness.load_benchmark()["workloads"]
                if c["name"] == args.workload)
    try:
        devices = device.require_tpu(cell["chips"])
    except device.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    device.enable_compile_cache(str(harness.CACHE_DIR))
    meter = device.CompileMeter()
    conf = harness.load_config(cell["config"])
    ref, prog = harness.family(conf)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(traffic.load_mix(cell["traffic"]))
        mix["arrivals"]["rate_per_s"] = rate
        ctx = harness.Context(cell, conf, mix, ref, prog, args.seed,
                              args.seconds, False, [],
                              device.peaks(devices[0].device_kind), meter,
                              lambda: 0, 0.0)
        d = serve.drive(ctx)
        loop, (t_open, t_close) = d["loop"], d["window"]
        ttft, gaps, failed = serve.latencies(loop, d["drain_end"])
        in_window = sum(sum(1 for t in rec["times"] if t <= t_close)
                        for rec in loop.info.values())
        backlog = sum(1 for rec in loop.info.values()
                      if not rec["times"] or rec["times"][-1] > t_close)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(loop.info),
            "failed": failed,
            "ttft_p50_ms": record.median(ttft) * 1e3,
            "ttft_p95_ms": record.p95(ttft) * 1e3,
            "itl_p50_ms": record.median(gaps) * 1e3,
            "itl_p95_ms": record.p95(gaps) * 1e3,
            "tokens_per_s_in_window": in_window / (t_close - t_open),
            "unfinished_at_close": backlog,
            "drain_s": d["drain_end"] - t_close,
            "compiles_in_window": d["compiles"]}), flush=True)
        del d, loop
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
