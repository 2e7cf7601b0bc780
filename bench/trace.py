"""From the profiler's trace to busy time, idle share and the breakdown.

``Capture`` runs the profiler over the start of a window and reads its
``.xplane.pb``.
``extract`` keeps of it only what the reduction reads, as a plain dict on
the benchmark's own clock (ns of ``time.perf_counter_ns``):

    {"programs": {device plane: [[name, start_ns, dur_ns], ...]},
     "ops": {device plane: [[op name, start_ns, dur_ns], ...]},
     "spans": [[span name, start_ns, dur_ns], ...]}

``programs`` holds each device's "XLA Modules" line: one event per program
run on the device.  A device is busy while one of its programs runs.
``ops`` holds the "XLA Ops" line, its events nested inside those programs,
for the first ``OPS_SECONDS`` of the window only: a decode step runs some
ten thousand of them, too many to read for a whole window.  ``spans``
holds the benchmark's own host spans (names starting ``bench.``).  The
trace's clock is put onto the benchmark's by matching those spans.

``reduce`` turns that dict and a window into busy seconds, the operations
that took most time and the longest idle gaps, each labelled by the
benchmark span that was open at its middle.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import shutil
import tempfile
import time
from collections import defaultdict

SPAN_PREFIX = "bench."
PROGRAM_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
OPS_SECONDS = 2.0
TOP = 10


class Spans:
    """The benchmark's host spans: kept for matching against the trace,
    and written into the profiler's trace while one is taken."""

    def __init__(self):
        self.recorded = []          # (name, start_ns)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(name):
            self.recorded.append((name, time.perf_counter_ns()))
            yield


class Capture:
    """The profiler over the first ``seconds`` of a window: a whole window
    of serving overflows the device tracer's buffer, which then drops the
    rest in silence.  ``window`` is the traced (start, stop), host clock."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.window = None
        self.profile = None
        self._dir = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # annotations only
        self._dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self.window = (time.perf_counter(), None)

    def poll(self, now: float) -> None:
        if self.active and now >= self.window[0] + self.seconds:
            self.stop()

    @property
    def active(self) -> bool:
        return self.window is not None and self.window[1] is None

    def stop(self) -> None:
        if not self.active:
            return
        import jax
        from jax.profiler import ProfileData
        self.window = (self.window[0], time.perf_counter())
        try:
            jax.profiler.stop_trace()
            paths = sorted(glob.glob(os.path.join(
                self._dir, "**", "*.xplane.pb"), recursive=True))
            if paths:
                self.profile = ProfileData.from_file(paths[-1])
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)

    def read(self, recorded) -> dict:
        """``extract`` of the profile on the benchmark's clock."""
        if self.profile is None:
            return {}
        lo, hi = self.window
        return extract(self.profile, recorded, (lo * 1e9, hi * 1e9))


def short_name(name: str) -> str:
    """An HLO instruction's name without its text: "%fusion.12 = ..."
    -> "fusion.12"."""
    return name.split(" = ", 1)[0].lstrip("%")


def match_offset(spans, recorded) -> float | None:
    """The benchmark's clock minus the trace's, in ns, as the median over
    the spans both recorded: the k-th span of a name in the trace is the
    k-th the benchmark recorded under that name."""
    by_name = defaultdict(list)
    for name, start, _ in spans:
        by_name[name].append(start)
    seen = defaultdict(int)
    diffs = []
    for name, start in recorded:
        k = seen[name]
        seen[name] += 1
        if k < len(by_name[name]):
            diffs.append(start - by_name[name][k])
    if not diffs:
        return None
    diffs.sort()
    return float(diffs[len(diffs) // 2])


def extract(profile, recorded, window_ns) -> dict:
    """The plain dict of ``profile`` on the benchmark's clock, or {} where
    the spans cannot be matched.  ``recorded``: the benchmark's (name,
    start_ns) spans; ``window_ns``: the window's (open, close)."""
    spans = []
    device_planes = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            device_planes.append(plane)
    off = match_offset(sorted(spans, key=lambda s: s[1]), recorded)
    if off is None:
        return {}
    ops_end = window_ns[0] + OPS_SECONDS * 1e9
    out = {"programs": {}, "ops": {},
           "spans": sorted([[n, s + off, d] for n, s, d in spans],
                           key=lambda s: s[1])}
    for plane in device_planes:
        lines = {ln.name: ln for ln in plane.lines}
        if PROGRAM_LINE in lines:
            out["programs"][plane.name] = [
                [e.name, e.start_ns + off, e.duration_ns]
                for e in lines[PROGRAM_LINE].events]
        elif OP_LINE in lines:          # no program line: outermost ops
            progs = out["programs"][plane.name] = []
            for e in lines[OP_LINE].events:
                start = e.start_ns + off
                if not progs or start >= progs[-1][1] + progs[-1][2]:
                    progs.append([short_name(e.name), start, e.duration_ns])
        if OP_LINE in lines:
            ops = out["ops"][plane.name] = []
            for e in lines[OP_LINE].events:
                start = e.start_ns + off
                if start >= ops_end:
                    break
                if start + e.duration_ns > window_ns[0]:
                    ops.append([short_name(e.name), start, e.duration_ns])
    return out


def merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Busy:
    """Busy intervals of one device, answering busy time in any span."""

    def __init__(self, events):
        self.merged = merge([[s, s + d] for _, s, d in events])
        self.starts = [m[0] for m in self.merged]

    def within(self, lo, hi) -> float:
        i = max(bisect.bisect_right(self.starts, lo) - 1, 0)
        total = 0.0
        for s, e in self.merged[i:]:
            if s >= hi:
                break
            total += max(0.0, min(e, hi) - max(s, lo))
        return total

    def gaps(self, lo, hi):
        """The idle (start, end) intervals inside [lo, hi]."""
        out, t = [], lo
        i = max(bisect.bisect_right(self.starts, lo) - 1, 0)
        for s, e in self.merged[i:]:
            if s >= hi:
                break
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out


def label(spans, t) -> str:
    """The innermost benchmark span open at ``t``."""
    best = None
    for name, s, d in spans:
        if s > t:
            break
        if s + d >= t and (best is None or d <= best[1]):
            best = (name, d)
    return best[0] if best else "outside any span"


def self_times(ops) -> dict:
    """Name -> time spent in the operation itself, not in the operations
    nested inside it (a loop's body, a fusion's parts)."""
    out = defaultdict(float)
    stack = []                          # [name, end, self time]
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            n, _, t = stack.pop()
            out[n] += t
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    for n, _, t in stack:
        out[n] += t
    return out


def reduce(trace: dict, lo: float, hi: float) -> dict:
    """Busy seconds averaged over the devices, the window's length, the
    operations that took most time (over the first ``OPS_SECONDS``), and
    the first device's longest idle gaps, each with its span."""
    if not trace.get("programs"):
        return {}
    busy = {k: Busy(v) for k, v in trace["programs"].items()}
    busy_s = sum(b.within(lo, hi) for b in busy.values()) / len(busy)
    op_time = defaultdict(float)
    for ops in trace.get("ops", {}).values():
        for name, t in self_times(ops).items():
            op_time[name] += t / len(trace["ops"])
    gaps = busy[sorted(busy)[0]].gaps(lo, hi)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": busy_s / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label(trace["spans"], (a + b) / 2), (b - a) / 1e9]
                      for a, b in longest],
    }
