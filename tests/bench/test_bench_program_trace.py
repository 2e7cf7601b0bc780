"""The engine's named programs, spans and admission stamps as the benchmark
reads them: the three per-layer readers that use them, on a small trace
recorded on a TPU v5e with this engine, and ``bench/program_trace.py``'s
reduction with the program's spans and scopes.  The seven readers that
came before read the older fixture as they did.

``fixtures/trace_v5e_engine.json`` is five ticks of the
``qwen2.5-3b.serve-chat`` cell's traced window, one of them admitting a
prompt: ``extract``'s dict cut to those ticks and to the ops of the last
decode step (none fell inside the first 2 s the harness keeps ops for),
with the ticks (``record.Tick``: start and end ns, prompt lengths admitted,
live lengths) and the requests (``loop.info`` with ``Request.t_admit``)."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from bench_testkit import TINY_CHAT, context, tiny_conf
from bench import device, program_trace as pt, record, serve, trace as tr
from bench.models import qwen2_ref

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
CONF = json.loads((pathlib.Path(__file__).parents[2] /
                   "bench/configs/qwen2.5-3b.json").read_text())
NEW = ("prefill_program_mfu", "slot_write_ms", "decode_host_gap_ms")
#: the readers' values on ``trace_v5e.json`` before this engine's programs
#: had names
BEFORE = {
    "queue_wait_p95_ms": None,
    "prefill_mfu": 28.620514619338298,
    "decode_roofline": 36.01915795557626,
    "decode_mfu": 0.6837287366414098,
    "train_mfu": None,
    "idle_share.serve": 9.546045545993664,
    "idle_share.train": None,
}
#: read by hand off ``trace_v5e_engine.json``: the one ``jit_write_slot``
#: run, the one ``jit_prefill`` run (a 384-token prompt, 17.39 ms), and the
#: idle time before the three plain ticks' decode steps (1.977, 1.831 and
#: 2.062 ms)
FIX = {"slot_ms": 9.648045, "prefill_mfu": 62.85328023448355,
       "plain_ticks": 3, "gap_ms": 1.977216, "idlest": "repro.sample"}


class _Req:
    """The stamp of a served request, as ``repro.serve.Request`` has it."""

    def __init__(self, r):
        self.rid, self.t_admit = r["rid"], r["t_admit"]


def _run(recorded) -> record.Run:
    lo, hi = recorded["window_ns"]
    ticks = [record.Tick(a / 1e9, b / 1e9, adm, live)
             for a, b, adm, live in recorded["ticks"]]
    requests = [dict(r, req=_Req(r)) for r in recorded.get("requests", [])]
    return record.Run("serve", qwen2_ref, qwen2_ref.dims(CONF),
                      device.PEAKS["TPU v5 lite"], (lo / 1e9, hi / 1e9),
                      ticks=ticks, requests=requests, trace=recorded)


@pytest.fixture(scope="module")
def old():
    return json.loads((FIXTURES / "trace_v5e.json").read_text())


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_earlier_readers_read_the_old_fixture_as_before(old, name):
    got = record.reader(name)(_run(old))
    if BEFORE[name] is None:
        assert got is None
    else:
        assert got == pytest.approx(BEFORE[name], rel=1e-12)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_unnamed_programs(old, name):
    # the older engine's programs were jit__lambda and it kept no stamps
    assert record.reader(name)(_run(old)) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_without_a_trace(old, name):
    run = _run(old)
    run.trace = None
    assert record.reader(name)(run) is None
    run = _run(old)
    run.kind = "train"
    assert record.reader(name)(run) is None


def test_scope_is_the_innermost_named_scope_unwrapped():
    assert pt.scope_of("jit(train_step)/transpose(jvp(loss))/while/body/"
                       "closed_call/checkpoint/head/dot_general") == "head"
    assert pt.scope_of("jit(train_step)/jvp(embed)/gather") == "embed"
    assert pt.scope_of("jit(decode_step)/while/body/attention/kv_cache/"
                       "dynamic_update_slice") == "kv_cache"
    assert pt.scope_of("jit(train_step)/jit(clip)/max") is None
    assert pt.program_name("jit_write_slot(1234)") == "jit_write_slot"


HLO = """HloModule jit_decode_step, entry_computation_layout={()}
  %fusion.1 = bf16[8]{0} fusion(%p.0), kind=kLoop, calls=%f.1, metadata={op_name="jit(decode_step)/while/body/closed_call/attention/dot_general" stack_frame_id=2}
  %copy.3 = bf16[8]{0} copy(%fusion.1)
  ROOT %fusion.2 = bf16[8]{0} fusion(%copy.3), kind=kLoop, calls=%f.2, metadata={op_name="jit(decode_step)/jvp(ffn)/mul"}
"""


def test_ops_take_the_scope_of_the_program_they_ran_in():
    scopes = pt.hlo_scopes(HLO)
    assert scopes == {"fusion.1": "attention", "fusion.2": "ffn"}
    plane = "/device:TPU:0"
    trace = {"programs": {plane: [["jit_decode_step(1)", 0, 100],
                                  ["jit_prefill(2)", 200, 100]]},
             "ops": {plane: [["fusion.1", 210, 20], ["fusion.1", 10, 20],
                             ["copy.3", 30, 5], ["fusion.2", 40, 50]]},
             "spans": [["bench.tick", 0, 300]]}
    # the prefill's fusion.1 is another op: only the decode step's HLO names
    trace["op_scopes"] = pt.op_scopes(trace, {"jit_decode_step": scopes})
    assert trace["op_scopes"] == {"fusion.1": "attention", "fusion.2": "ffn"}
    got = dict(pt.reduce(trace, 0, 300)["device_ops"])
    assert set(got) == {"fusion.1 [attention]", "fusion.2 [ffn]", "copy.3"}
    assert got["fusion.2 [ffn]"] == pytest.approx(50e-9)


DUMPING = """
import jax, jax.numpy as jnp
def decode_step(x):
    with jax.named_scope("ffn"):
        return jnp.sin(x) @ x
def other(x):
    return x + 1
jax.jit(decode_step)(jnp.ones((8, 8))).block_until_ready()
jax.jit(other)(jnp.ones(3)).block_until_ready()
"""


def test_dumped_hlo_is_keyed_by_the_module_name(tmp_path):
    # XLA reads its flags once, at the backend's start: a process of its own
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=pt.dump_flags(str(tmp_path)))
    subprocess.run([sys.executable, "-c", DUMPING], env=env, check=True,
                   timeout=120)
    hlo = pt.dumped_hlo(str(tmp_path))
    assert set(hlo) == {"jit_decode_step"}
    assert "ffn" in set(pt.hlo_scopes(hlo["jit_decode_step"]).values())


def test_program_spans_land_on_the_benchmark_clock():
    ctx = context(tiny_conf(), dict(TINY_CHAT), seed=11, seconds=2.0,
                  trace=True)
    d = serve.drive(ctx)
    with pt.reading() as seen:
        got = d["trace"].read(d["spans"].recorded)
    assert seen["trace"] is got
    loop = d["loop"]
    ps = got["program_spans"]
    names = {n for n, *_ in ps}
    assert {"repro.admit", "repro.prefill", "repro.write_slot",
            "repro.first_token", "repro.decode", "repro.sample",
            "repro.retire"} <= names
    ticks = [(t.start * 1e9, t.end * 1e9) for t in loop.ticks]
    for name, s, d, stats in ps:
        if name == "repro.decode":          # inside a tick, as dispatched
            assert any(a - 2e5 <= s and s + d <= b + 2e5 for a, b in ticks)
            assert stats["live"] >= 1
    admits = {st["rid"]: st for n, _, _, st in ps if n == "repro.admit"}
    for rec in loop.info.values():
        r = rec["req"]
        if r.rid in admits:
            assert admits[r.rid]["tokens"] == rec["prompt_len"]
            assert admits[r.rid]["slot"] == r.slot


# ---------------------------------------------------------------------------
# On the engine's own v5e fixture
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def engine_trace():
    return json.loads((FIXTURES / "trace_v5e_engine.json").read_text())


def _first(recorded):
    (events,) = recorded["programs"].values()
    return sorted(events, key=lambda e: e[1])


def test_engine_programs_are_named(engine_trace):
    names = {pt.program_name(n) for n, _, _ in _first(engine_trace)}
    assert {"jit_prefill", "jit_write_slot", "jit_decode_step"} <= names
    assert not any("lambda" in n or n == "jit_scatter" for n in names)


def test_slot_write_ms_is_the_write_slot_run(engine_trace):
    (run,) = [d for n, _, d in _first(engine_trace)
              if n.startswith("jit_write_slot(")]
    got = record.reader("slot_write_ms")(_run(engine_trace))
    assert got == pytest.approx(run / 1e6) == pytest.approx(FIX["slot_ms"])


def test_prefill_program_mfu_counts_the_prefill_run_alone(engine_trace):
    (run,) = [d for n, _, d in _first(engine_trace)
              if n.startswith("jit_prefill(")]
    (req,) = [r for r in engine_trace["requests"]
              if engine_trace["window_ns"][0] <= r["t_admit"] * 1e9]
    flops = qwen2_ref.prefill_flops(qwen2_ref.dims(CONF), req["prompt_len"])
    peak = device.PEAKS["TPU v5 lite"]["bf16_flops"]
    want = 100 * flops / (run / 1e9 * peak)
    got = record.reader("prefill_program_mfu")(_run(engine_trace))
    assert got == pytest.approx(want) == pytest.approx(FIX["prefill_mfu"])
    # the older reader charges the slot write and the rest to the prefill
    assert got > record.reader("prefill_mfu")(_run(engine_trace))


def test_decode_host_gap_is_the_idle_before_each_plain_decode(engine_trace):
    events = _first(engine_trace)
    gaps = []
    for i, (name, start, _) in enumerate(events):
        if name.startswith("jit_decode_step(") and i > 0:
            tick = next(t for t in engine_trace["ticks"]
                        if t[0] <= start <= t[1])
            prev = engine_trace["ticks"].index(tick) - 1
            if prev >= 0 and not tick[2]:
                gaps.append(start - events[i - 1][1] - events[i - 1][2])
    assert len(gaps) == FIX["plain_ticks"]
    got = record.reader("decode_host_gap_ms")(_run(engine_trace))
    assert got == pytest.approx(record.median(gaps) / 1e6)
    assert got == pytest.approx(FIX["gap_ms"])


def test_admission_spans_nest_and_share_the_request(engine_trace):
    ps = engine_trace["program_spans"]
    (admit,) = [s for s in ps if s[0] == "repro.admit"]
    _, a0, ad, st = admit
    inside = [(n, s[3]) for s in ps if a0 <= s[1] and s[1] + s[2] <= a0 + ad
              for n in [s[0]] if n != "repro.admit"]
    rid = st["rid"]
    assert [n for n, _ in inside] == ["repro.prefill", "repro.write_slot",
                                      "repro.first_token"]
    assert all(x["rid"] == rid for _, x in inside)
    (req,) = [r for r in engine_trace["requests"] if r["rid"] == rid]
    assert st["tokens"] == req["prompt_len"]
    # the admission stamp is on the clock of the trace
    assert abs(req["t_admit"] * 1e9 - a0) < 50e3
    assert req["admit"] <= req["t_admit"]   # inside the harness's tick


def test_gaps_are_labelled_by_the_engine_spans(engine_trace):
    lo, hi = engine_trace["window_ns"]
    s = pt.reduce(engine_trace, lo, hi)
    names = ({n for n, *_ in engine_trace["spans"]} |
             {n for n, *_ in engine_trace["program_spans"]} |
             {"outside any span"})
    assert {n for n, _ in s["idle_gaps"]} <= names
    assert any(n.startswith("repro.") for n, _ in s["idle_gaps"])
    assert sum(s["idle_by_span"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    assert max(s["idle_by_span"], key=s["idle_by_span"].get) == FIX["idlest"]
    assert s["programs"]["jit_write_slot"][0] == 1


def test_reading_reads_a_whole_run_and_puts_the_harness_back():
    read, summary = tr.Capture.read, record.Run.trace_summary
    ctx = context(tiny_conf(), dict(TINY_CHAT), seed=12, seconds=1.0,
                  trace=True, metrics=[])
    with pt.reading() as seen:
        out = serve.run(ctx)
    assert out["correct"]
    names = {n for n, *_ in seen["trace"]["program_spans"]}
    assert {"repro.decode", "repro.sample", "repro.retire"} <= names
    assert (tr.Capture.read, record.Run.trace_summary) == (read, summary)
