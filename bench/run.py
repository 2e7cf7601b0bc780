#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the TPU this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the weights on the device from the seed, warms up every
shape the cell's traffic uses and counts as ``setup_s`` (process start to
the window).  The window then runs for ``--seconds``.  With ``--trace 0``
the cell's end-to-end metrics are reported; with ``--trace 1`` the window
runs under the profiler and its per-layer metrics are.  Afterwards what
the window produced is compared with the configuration's plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), and last ``checks``, each number compared beside its
limit; the same numbers are the last lines of standard error.  Without a
TPU, or with fewer than the cell needs, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

EXIT_NO_CHIP = 2
#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = ROOT / ".jax_cache"


@dataclasses.dataclass
class Context:
    """What a cell's module (``bench.serve`` or ``bench.train``) is given."""
    cell: dict
    conf: dict                      # the config file, as run
    mix: dict                       # the traffic file
    ref: Any                        # bench.models.<family>_ref
    prog: Any                       # bench.models.<family>_program
    seed: int
    seconds: float
    trace: bool
    metrics: list                   # the metric entries this run reports
    peaks: dict
    meter: Any                      # device.CompileMeter
    memory_peak: Callable[[], int]
    t0: float


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(name: str, root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "bench" / "configs" / f"{name}.json")
                      .read_text())


def family(conf: dict):
    fam = conf["bench"]["family"]
    return (importlib.import_module(f"bench.models.{fam}_ref"),
            importlib.import_module(f"bench.models.{fam}_program"))


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: end-to-end ones
    without the trace, per-layer ones with it."""
    entries = bench["per_layer" if trace else "end_to_end"]
    return [m for m in entries
            if cell["name"] in m.get("workloads", [cell["name"]])]


def run_cell(args, devices, peaks: dict, meter, root: pathlib.Path = ROOT,
             t0: float = T0) -> dict:
    """One run of a cell on ``devices``; returns the result line's dict."""
    from bench import device, traffic
    bench = load_benchmark(root)
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[args.workload]
    conf = load_config(cell["config"], root)
    if "checks" not in conf["bench"]:
        raise SystemExit(f"config {cell['config']!r} has no correctness "
                         "limits read on the chip: it is not a cell yet")
    mix = traffic.load_mix(cell["traffic"])
    ref, prog = family(conf)
    kinds = {"serve": "bench.serve", "train": "bench.train"}
    module = importlib.import_module(kinds[mix["kind"]])
    ctx = Context(cell, conf, mix, ref, prog, args.seed, args.seconds,
                  bool(args.trace), cell_metrics(bench, cell, args.trace),
                  peaks, meter, lambda: device.memory_peak_bytes(devices),
                  t0)
    out = module.run(ctx)
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": dict(device.describe(devices),
                           memory_peak_bytes=out["memory_peak_bytes"])}
    s = out["summary"]
    if args.trace and s:
        line["device"]["busy_s"] = s["busy_s"]
        line["device"]["window_s"] = s["window_s"]
        line["breakdown"] = {"device_ops": s["device_ops"],
                             "idle_gaps": s["idle_gaps"]}
    line["checks"] = out["checks"]
    return line


def print_result(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = next((c for c in load_benchmark()["workloads"]
                 if c["name"] == args.workload), None)
    if cell is None:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 1
    from bench import device
    try:
        devices = device.require_tpu(cell["chips"])
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    device.enable_compile_cache(str(CACHE_DIR))
    meter = device.CompileMeter()
    line = run_cell(args, devices, device.peaks(devices[0].device_kind),
                    meter)
    print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
