"""The traffic generator and the serving arithmetic, driving the real
``Engine`` at reduced widths on the CPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_testkit import ROOT, TINY_CHAT, context, tiny_conf
from bench import record, serve, traffic


def test_schedule_is_a_pure_function_of_the_seed():
    a = traffic.serve_schedule(TINY_CHAT, 2**33 + 17, 10.0, 512)
    b = traffic.serve_schedule(TINY_CHAT, 2**33 + 17, 10.0, 512)
    c = traffic.serve_schedule(TINY_CHAT, 5, 10.0, 512)
    key = lambda s: [(r.due, r.prompt.tolist(), r.max_new) for r in s]
    assert key(a) == key(b)
    assert key(a) != key(c)


def test_every_seed_gets_the_same_work_in_its_own_order():
    """Every seed gets the one schedule of the mix (arrivals and lengths)
    with its own prompt tokens; another ``order_seed`` offers the same set
    of gaps and lengths in another order."""
    a = traffic.serve_schedule(TINY_CHAT, 1, 10.0, 512)
    b = traffic.serve_schedule(TINY_CHAT, 2, 10.0, 512)
    assert len(a) == len(b) == int(TINY_CHAT["arrivals"]["rate_per_s"] * 10)
    sched = lambda s: [(r.due, len(r.prompt), r.max_new) for r in s]
    assert sched(a) == sched(b)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]
    other = dict(TINY_CHAT, arrivals=dict(TINY_CHAT["arrivals"], order_seed=2))
    c = traffic.serve_schedule(other, 1, 10.0, 512)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    # the gaps between arrivals are one set, permuted: only the one gap
    # after the last arrival is left out of each schedule's differences
    ga = np.diff([r.due for r in a]).round(9).tolist()
    gc = np.diff([r.due for r in c]).round(9).tolist()
    assert len(set(ga) & set(gc)) >= len(ga) - 1
    assert ga != gc
    assert a[0].due == 0.0 and a[-1].due < 10.0


def test_prompt_lengths_are_snapped_to_the_grid():
    spec = json.loads(
        (ROOT / "bench/traffic/azure-conv-20slots.json").read_text())
    grid = traffic.length_grid(spec["prompt_len"])
    assert len(grid) == 16 and len(set(grid)) == 16
    assert grid[0] == 128 and grid[-1] == 3072 and all(grid % 32 == 0)
    sched = traffic.serve_schedule(spec, 3, 51.0, 1000)
    assert {len(r.prompt) for r in sched} <= set(grid.tolist())
    out = [r.max_new for r in sched]
    assert min(out) >= 8 and max(out) <= 1024
    # the source's medians survive the quantiles, the clipping and the grid
    assert np.median(out) == 129
    assert 0.9 < np.median([len(r.prompt) for r in sched]) / 1020 < 1.1
    assert max(len(r.prompt) + r.max_new for r in sched) <= \
        spec["engine"]["max_seq"]


def test_p95_is_by_nearest_rank_over_all_values():
    v = list(range(1, 101))
    assert record.p95(v) == 95
    assert record.p95([3.0]) == 3.0
    assert record.p95(list(range(1, 21))) == 19


class _Req:
    def __init__(self, generated, done):
        self.generated, self._done = generated, done

    @property
    def done(self):
        return self._done


def test_ttft_counts_from_the_due_time_and_unfinished_requests_fail():
    loop = serve.Loop.__new__(serve.Loop)
    loop.info = {
        0: {"due": 1.0, "times": [1.5, 1.6, 1.8], "req": _Req([1, 2, 3], True)},
        1: {"due": 2.0, "times": [2.9], "req": _Req([4], False)},
        2: {"due": 3.0, "times": [], "req": _Req([], False)},
    }
    ttft, gaps, failed = serve.latencies(loop, drain_end=10.0)
    assert ttft == pytest.approx([0.5, 0.9, 7.0])
    assert gaps == pytest.approx([0.1, 0.2])
    assert failed == 2


@pytest.fixture(scope="module")
def served():
    conf = tiny_conf()
    out = serve.run(context(conf, TINY_CHAT, seed=2**32 + 3, seconds=2.0))
    return out


def test_serve_run_on_the_engine_is_correct_and_complete(served):
    out = served
    assert out["correct"], out["checks"]
    assert out["attempted"] == int(TINY_CHAT["arrivals"]["rate_per_s"] * 2)
    assert out["failed"] == 0
    assert out["checks"]["compiles_in_window"]["value"] == 0
    m = out["metrics"]
    assert m["ttft_p95_ms"]["value"] > 0 and m["itl_p95_ms"]["value"] >= 0
    assert m["setup_s"]["value"] > 0


def test_cli_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         cell["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
