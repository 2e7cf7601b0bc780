#!/usr/bin/env python3
"""A cell's traced run, read with the program's own spans and named scopes.

    python3 bench/program_trace.py --workload <cell> --seed <n>

Runs the cell as ``bench/run.py --seconds <run_seconds> --trace 1`` does and
prints its result line, the trace read by this module's ``extract`` and
``reduce`` in place of ``bench/trace.py``'s (``reading``):

- ``extract`` also keeps the program's ``repro.*`` host spans (``repro.obs``)
  with their stats, on the benchmark's clock, as ``program_spans``; and, for
  the operations that ran in a program whose compiled HLO it is given, their
  named scope (``jax.named_scope``) from that HLO's ``op_name`` metadata, as
  ``op_scopes``: the v5e's op events carry no scope.
- ``reduce`` labels each idle gap by the innermost span of either kind,
  splits the window's idle time by span (``breakdown.idle_by_span``),
  suffixes each operation with its scope and counts the first device's runs
  of each program (``breakdown.programs``: runs, seconds).

The HLO is what XLA dumps of the decode and train step programs as they
compile; JAX's compilation cache is off here, so that each of them does.
Needs a TPU: exits 2 without one.  ``extract`` and ``reduce`` take the same
arguments as ``bench/trace.py``'s, to replace them as they are.
"""
from __future__ import annotations

import argparse
import bisect
import contextlib
import glob
import os
import pathlib
import re
import shutil
import sys
import tempfile
from collections import defaultdict
from unittest import mock

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import record  # noqa: E402
from bench import trace as tr  # noqa: E402

PROGRAM_PREFIX = "repro."
#: the named scopes the model and the optimizer put on their operations
SCOPES = ("embed", "attention", "kv_cache", "ffn", "head", "loss", "adamw")
#: the programs whose HLO is dumped: their operations get a scope
HLO_MODULES = "jit_(decode_step|train_step)"
WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")      # jvp(embed) -> embed
HLO_OP = re.compile(
    r'^\s*(?:ROOT )?%([\w.-]+) = .*?metadata=\{op_name="([^"]*)"', re.M)


# ---------------------------------------------------------------------------
# Scopes, from the compiled HLO
# ---------------------------------------------------------------------------
def scope_of(path: str) -> str | None:
    """The innermost of ``SCOPES`` in an op's name path, transformations
    unwrapped: "jit(f)/transpose(jvp(loss))/while/body/head/dot" -> head."""
    found = None
    for part in path.split("/"):
        while (m := WRAPPED.match(part)) is not None:
            part = m.group(1)
        if part in SCOPES:
            found = part
    return found


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> scope, from a compiled program's HLO text and
    the ``op_name`` metadata of its instructions."""
    out = {}
    for name, path in HLO_OP.findall(hlo_text):
        scope = scope_of(path)
        if scope:
            out[name] = scope
    return out


def dump_flags(directory: str) -> str:
    """``XLA_FLAGS`` that dump the compiled HLO of ``HLO_MODULES`` as text
    into ``directory``; read at the backend's start."""
    return (f"--xla_dump_to={directory} --xla_dump_hlo_as_text "
            f"--xla_dump_hlo_module_re={HLO_MODULES}")


def dumped_hlo(directory: str) -> dict:
    """{module name: compiled HLO text} of the programs XLA dumped into
    ``directory``."""
    out = {}
    for path in sorted(glob.glob(os.path.join(
            directory, "module_*after_optimizations.txt"))):
        text = pathlib.Path(path).read_text()
        out[text.split(",", 1)[0].split()[-1]] = text
    return out


def op_scopes(trace: dict, scopes: dict) -> dict:
    """Op name -> scope, for the ops in ``trace`` that ran inside a run of
    a program in ``scopes`` ({program name: {instruction: scope}})."""
    out = {}
    for plane, ops in trace["ops"].items():
        runs = sorted(trace["programs"].get(plane, []), key=lambda e: e[1])
        starts = [s for _, s, _ in runs]
        for name, start, _ in ops:
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or name in out:
                continue
            scope = scopes.get(program_name(runs[i][0]), {}).get(name)
            if scope:
                out[name] = scope
    return out


# ---------------------------------------------------------------------------
# The trace and its reduction
# ---------------------------------------------------------------------------
def extract(profile, recorded, window_ns, hlo: dict | None = None) -> dict:
    """``trace.extract`` of ``profile``, plus the program's spans,
    ``program_spans: [[name, start_ns, dur_ns, {stats}], ...]`` on the same
    clock, and ``op_scopes: {op name: scope}`` for the kept operations that
    ran inside a program of ``hlo`` ({program name: its HLO text})."""
    out = tr.extract(profile, recorded, window_ns)
    if not out:
        return out
    host = [e for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    ours = sorted([[e.name, e.start_ns, e.duration_ns] for e in host
                   if e.name.startswith(tr.SPAN_PREFIX)], key=lambda s: s[1])
    off = tr.match_offset(ours, recorded)
    out["program_spans"] = sorted(
        ([e.name, e.start_ns + off, e.duration_ns, dict(list(e.stats))]
         for e in host if e.name.startswith(PROGRAM_PREFIX)),
        key=lambda s: s[1])
    out["op_scopes"] = op_scopes(out, {k: hlo_scopes(v)
                                       for k, v in (hlo or {}).items()})
    return out


def all_spans(trace: dict) -> list:
    """Both kinds of span, [name, start, dur], in order of start."""
    return sorted(trace.get("spans", []) +
                  [s[:3] for s in trace.get("program_spans", [])],
                  key=lambda s: s[1])


def split_by_span(spans, a: float, b: float) -> dict:
    """[a, b] split by the innermost span open over each part of it."""
    cuts = sorted({a, b} | {t for _, s, d in spans for t in (s, s + d)
                            if a < t < b})
    out = defaultdict(float)
    for lo, hi in zip(cuts, cuts[1:]):
        out[tr.label(spans, (lo + hi) / 2)] += hi - lo
    return out


def program_name(name: str) -> str:
    """"jit_prefill(123456)" -> "jit_prefill"."""
    return name.split("(", 1)[0]


def reduce(trace: dict, lo: float, hi: float) -> dict:
    """``trace.reduce`` with each gap labelled by the innermost span of
    either kind, the idle time split by span, each op suffixed with its
    scope, and the first device's programs by name (runs, seconds)."""
    out = tr.reduce(trace, lo, hi)
    if not out:
        return out
    spans = all_spans(trace)
    first = sorted(trace["programs"])[0]
    gaps = tr.Busy(trace["programs"][first]).gaps(lo, hi)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:tr.TOP]
    out["idle_gaps"] = [[tr.label(spans, (a + b) / 2), (b - a) / 1e9]
                        for a, b in longest]
    idle = defaultdict(float)
    for a, b in gaps:
        for name, t in split_by_span(spans, a, b).items():
            idle[name] += t
    out["idle_by_span"] = {n: t / 1e9 for n, t in
                           sorted(idle.items(), key=lambda kv: -kv[1])}
    scopes = trace.get("op_scopes", {})
    out["device_ops"] = [[f"{n} [{scopes[n]}]" if n in scopes else n, t]
                         for n, t in out["device_ops"]]
    progs = defaultdict(lambda: [0, 0.0])
    for name, s, d in trace["programs"][first]:
        if lo <= s < hi:
            p = progs[program_name(name)]
            p[0] += 1
            p[1] += d / 1e9
    out["programs"] = dict(sorted(progs.items(), key=lambda kv: -kv[1][1]))
    return out


@contextlib.contextmanager
def reading(hlo_dir: str | None = None):
    """Within it the harness reads its traces with ``extract`` and
    ``reduce``, the programs' HLO from XLA's dump in ``hlo_dir``.  Yields a
    dict that then holds the last ``trace`` read and its ``summary``."""
    seen = {}

    def read(capture, recorded):
        if capture.profile is None:
            return {}
        lo, hi = capture.window
        hlo = dumped_hlo(hlo_dir) if hlo_dir else None
        seen["trace"] = extract(capture.profile, recorded,
                                (lo * 1e9, hi * 1e9), hlo)
        return seen["trace"]

    def trace_summary(run):
        if not run.trace:
            return {}
        lo, hi = run.window
        seen["summary"] = reduce(run.trace, lo * 1e9, hi * 1e9)
        return seen["summary"]

    with mock.patch.object(tr.Capture, "read", read), \
            mock.patch.object(record.Run, "trace_summary", trace_summary):
        yield seen


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    hlo_dir = tempfile.mkdtemp(prefix="bench-hlo-")
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (os.environ.get("XLA_FLAGS"), dump_flags(hlo_dir)) if f)
    try:
        from bench import device
        from bench import run as bench_run
        bench = bench_run.load_benchmark()
        cell = next(c for c in bench["workloads"]
                    if c["name"] == args.workload)
        try:
            devices = device.require_tpu(cell["chips"])
        except device.NoChip as e:
            print(f"program_trace: {e}", file=sys.stderr)
            return bench_run.EXIT_NO_CHIP
        import jax
        jax.config.update("jax_enable_compilation_cache", False)
        run_args = argparse.Namespace(workload=args.workload, seed=args.seed,
                                      seconds=bench["run_seconds"], trace=1)
        with reading(hlo_dir) as seen:
            line = bench_run.run_cell(
                run_args, devices, device.peaks(devices[0].device_kind),
                device.CompileMeter())
        summary = seen.get("summary", {})
        if "breakdown" in line:
            line["breakdown"]["idle_by_span"] = summary.get("idle_by_span")
            line["breakdown"]["programs"] = summary.get("programs")
        bench_run.print_result(line)
        return 0
    finally:
        shutil.rmtree(hlo_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
