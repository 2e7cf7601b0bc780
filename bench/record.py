"""What a run recorded, as the per-layer metric readers see it, and the
readers' discovery: ``bench/metrics/<name>.py`` defines ``read(run)``,
which returns the metric's value or None where the run holds nothing for
it to read (a metric left out, never a 0)."""
from __future__ import annotations

import dataclasses
import importlib.util
import math
import pathlib
from typing import Any, Optional

from bench import trace as tr

HERE = pathlib.Path(__file__).resolve().parent


def p95(values) -> float:
    """The 95th percentile by nearest rank: the smallest value with at
    least 95% of all values at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    return float(v[max(0, math.ceil(0.95 * len(v)) - 1)])


def median(values) -> float:
    v = sorted(values)
    n = len(v)
    return float(v[n // 2] if n % 2 else (v[n // 2 - 1] + v[n // 2]) / 2)


@dataclasses.dataclass
class Tick:
    start: float                    # host clock, s
    end: float
    admitted: list                  # prompt lengths admitted in this tick
    live: list                      # cached lengths of the slots decoded


@dataclasses.dataclass
class Step:
    start: float
    end: float
    tokens: int


@dataclasses.dataclass
class Run:
    kind: str                       # "serve" | "train"
    ref: Any                        # the config's reference module
    dims: Any
    peaks: dict
    window: tuple                   # (open, close), host clock, s
    ticks: list = dataclasses.field(default_factory=list)
    requests: list = dataclasses.field(default_factory=list)
    steps: list = dataclasses.field(default_factory=list)
    seq_len: int = 0
    trace: Optional[dict] = None    # trace.extract's dict
    _busy: Any = None

    def _device(self) -> Optional[tr.Busy]:
        """The first device's program runs, from the trace; None without."""
        if not self.trace or not self.trace.get("programs"):
            return None
        if self._busy is None:
            first = sorted(self.trace["programs"])[0]
            self._busy = tr.Busy(self.trace["programs"][first])
        return self._busy

    def tick_busy(self) -> list:
        """(tick, device-busy seconds) of the ticks inside the window and
        before the trace's last program run; empty without a trace."""
        busy = self._device()
        if busy is None or not busy.merged:
            return []
        lo = self.window[0]
        hi = min(self.window[1], busy.merged[-1][1] / 1e9)
        return [(t, busy.within(t.start * 1e9, t.end * 1e9) / 1e9)
                for t in self.ticks if lo <= t.start and t.end <= hi]

    def trace_summary(self) -> dict:
        if not self.trace:
            return {}
        lo, hi = self.window
        return tr.reduce(self.trace, lo * 1e9, hi * 1e9)


def reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(run: Run, metrics: list) -> dict:
    """``{name: {"value", "unit"}}`` for each metric entry whose reader
    finds something to read."""
    out = {}
    for m in metrics:
        value = reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
