"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Prints ``name,value,derived`` CSV:
  table1/*      — paper Table 1 reproduction (geomean us + ratios)
  trajectory/*  — §4.4 discovery curve (best-so-far per generation)
  scientist/*   — campaign throughput: submissions/hour + cache hit rate
                  for workers ∈ {1, 3} (also writes BENCH_scientist.json)
  micro/*       — kernel microbenchmarks (interpret wall-clock + v5e est.)
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer generations for the search benchmarks")
    args = ap.parse_args(argv)
    gens = 6 if args.fast else 20

    rows = []
    from benchmarks import (kernel_micro, scientist_throughput, table1,
                            trajectory)
    t1, _ = table1.run(generations=gens)
    rows += t1
    tr, _ = trajectory.run(generations=max(4, gens // 2))
    rows += tr
    st, _ = scientist_throughput.run(generations=max(4, gens // 3))
    rows += st
    rows += kernel_micro.run()

    print("name,value,derived")
    for name, value, derived in rows:
        v = f"{value:.4f}" if isinstance(value, float) else str(value)
        print(f"{name},{v},{derived}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
