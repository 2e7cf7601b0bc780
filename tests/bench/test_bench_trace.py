"""The reduction from a profiler trace to busy time, idle share and the
breakdown, on a small trace recorded on a TPU v5e (five engine ticks of
the qwen2.5-3b chat cell, one of them admitting a prompt), and the table
of peaks."""
import json
import pathlib

import pytest

from bench_testkit import FAKE_PEAKS
from bench import device, record, trace

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_v5e.json"


@pytest.fixture(scope="module")
def recorded():
    return json.loads(FIXTURE.read_text())


def _union(events, lo, hi):
    """Busy time by brute force: 1 us cells covered by any event."""
    covered = set()
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        covered.update(range(int(a // 1000), int(-(-b // 1000))))
    return len(covered) * 1000


def test_busy_is_the_union_of_program_runs(recorded):
    lo, hi = recorded["window_ns"]
    (events,) = recorded["programs"].values()
    busy = trace.Busy(events).within(lo, hi)
    assert 0 < busy < hi - lo
    assert busy == pytest.approx(_union(events, lo, hi), rel=0.02)
    s = trace.reduce(recorded, lo, hi)
    assert s["busy_s"] == pytest.approx(busy / 1e9)
    assert s["window_s"] == pytest.approx((hi - lo) / 1e9)


def test_idle_gaps_are_labelled_by_the_span_open_over_them(recorded):
    lo, hi = recorded["window_ns"]
    s = trace.reduce(recorded, lo, hi)
    names = {n for n, _, _ in recorded["spans"]} | {"outside any span"}
    assert s["idle_gaps"] and len(s["idle_gaps"]) <= trace.TOP
    assert all(name in names for name, _ in s["idle_gaps"])
    secs = [g for _, g in s["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    (events,) = recorded["programs"].values()
    idle = sum(b - a for a, b in trace.Busy(events).gaps(lo, hi))
    assert idle / 1e9 == pytest.approx(s["window_s"] - s["busy_s"])


def test_operations_are_ranked_by_their_own_time(recorded):
    lo, hi = recorded["window_ns"]
    ops = trace.reduce(recorded, lo, hi)["device_ops"]
    assert ops and all(" = " not in n for n, _ in ops)
    (events,) = recorded["ops"].values()
    total = sum(trace.self_times(events).values())
    assert total == pytest.approx(_union(events, 0, float("inf")), rel=0.02)


def test_nested_operations_count_their_own_time_once():
    ops = [["loop", 0, 100], ["a", 10, 20], ["b", 40, 30], ["c", 45, 5],
           ["d", 200, 10]]
    t = trace.self_times(ops)
    assert t == {"loop": 50, "a": 20, "b": 25, "c": 5, "d": 10}
    assert trace.merge([[0, 5], [3, 9], [12, 13]]) == [[0, 9], [12, 13]]
    assert trace.short_name("%fusion.12 = bf16[2]{0} fusion(...)") == \
        "fusion.12"


def test_device_readers_use_the_trace(recorded):
    from bench.models import qwen2_ref
    conf = json.loads((pathlib.Path(__file__).parents[2] /
                       "bench/configs/qwen2.5-3b.json").read_text())
    lo, hi = recorded["window_ns"]
    ticks = [record.Tick(a / 1e9, b / 1e9, adm, live)
             for a, b, adm, live in recorded["ticks"]]
    run = record.Run("serve", qwen2_ref, qwen2_ref.dims(conf),
                     device.PEAKS["TPU v5 lite"], (lo / 1e9, hi / 1e9),
                     ticks=ticks, trace=recorded)
    for name in ("decode_roofline", "decode_mfu", "prefill_mfu",
                 "idle_share.serve"):
        v = record.reader(name)(run)
        assert v is not None and 0 < v < 100, name
    assert record.reader("train_mfu")(run) is None
    run.trace = None
    assert record.reader("decode_roofline")(run) is None


def test_unknown_device_kind_is_an_error():
    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        device.peaks("TPU v9 imaginary")
    assert "bf16_flops" in FAKE_PEAKS
