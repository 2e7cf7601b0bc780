"""Continuous-batching engine: generations match a sequential reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import api
from repro.serve import Engine, Request


def _greedy_reference(cfg, params, prompt, max_new):
    toks = list(prompt)
    for _ in range(max_new):
        batch = {"tokens": jnp.asarray(toks, jnp.int32)[None]}
        logits, _ = api.prefill(params, cfg, batch, len(toks))
        toks.append(int(jnp.argmax(logits[0])))
    return toks[len(prompt):]


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "mamba2-2.7b"])
def test_engine_matches_sequential_reference(arch):
    cfg = dataclasses.replace(configs.get_reduced(arch),
                              param_dtype="float32")
    params = api.init_params(cfg, jax.random.key(3))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, int(rng.integers(4, 12)))
               .astype(np.int32) for _ in range(3)]

    eng = Engine(cfg, params, slots=2, max_seq=64)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=4))
    finished = sorted(eng.run(), key=lambda r: r.rid)
    assert len(finished) == 3
    for req in finished:
        want = _greedy_reference(cfg, params, list(req.prompt), 4)
        assert req.generated == want, (req.rid, req.generated, want)


def test_slots_reused():
    cfg = dataclasses.replace(configs.get_reduced("qwen2.5-3b"),
                              param_dtype="float32")
    params = api.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, slots=1, max_seq=32)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=np.array([1, 2, 3], np.int32),
                           max_new=2))
    finished = eng.run()
    assert len(finished) == 3


# ---------------------------------------------------------------------------
# Spans, program names, the jitted slot write and the admission stamp
# ---------------------------------------------------------------------------
def _tiny_engine(slots=2, max_seq=32):
    cfg = dataclasses.replace(configs.get_reduced("qwen2.5-3b"),
                              param_dtype="float32")
    return Engine(cfg, api.init_params(cfg, jax.random.key(1)),
                  slots=slots, max_seq=max_seq)


def _profiled_events(fn, tmp_path):
    """``repro.*`` host events of a profiler trace around ``fn()``:
    (name, start_ns, end_ns, stats) in order of start."""
    import glob
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        {k: v for k, v in e.stats}) for e in line.events
                       if e.name.startswith("repro."))
    return sorted(out, key=lambda e: e[1])


def test_engine_spans_reach_the_profiler_trace(tmp_path):
    eng = _tiny_engine()
    prompts = {4: np.arange(1, 6, dtype=np.int32),
               9: np.arange(3, 10, dtype=np.int32)}
    for rid, p in prompts.items():
        eng.submit(Request(rid=rid, prompt=p, max_new=3))
    eng.tick()                              # compile outside the trace
    first = _profiled_events(eng.run, tmp_path / "first")
    eng.submit(Request(rid=11, prompt=prompts[4], max_new=2))
    second = _profiled_events(eng.run, tmp_path / "second")
    events = first + second                 # each on its own trace's clock

    names = [n for n, *_ in events]
    assert {"repro.decode", "repro.sample", "repro.retire"} <= set(names)
    admits = [e for e in events if e[0] == "repro.admit"]
    assert [a[3] for a in admits] == [{"rid": 11, "slot": 0, "tokens": 5}]
    _, a0, a1, _ = admits[0]
    inside = [(n, st) for n, s, e, st in second if a0 <= s and e <= a1
              and n != "repro.admit"]
    assert inside == [("repro.prefill", {"rid": 11, "tokens": 5,
                                         "attn_steps": 1}),
                      ("repro.write_slot", {"rid": 11, "slot": 0}),
                      ("repro.first_token", {"rid": 11})]
    lives = [st["live"] for n, _, _, st in events if n == "repro.decode"]
    assert lives == [st["live"] for n, _, _, st in events
                     if n == "repro.sample"]
    finished = sum(st["finished"] for n, _, _, st in events
                   if n == "repro.retire")
    assert finished == 3                    # rids 4, 9 and 11


def test_engine_programs_have_stable_names():
    eng = _tiny_engine()
    batch = {"tokens": jnp.zeros((1, 5), jnp.int32)}
    _, cache1 = eng._prefill(eng.params, batch)
    lowered = {
        "jit_prefill": eng._prefill.lower(eng.params, batch),
        "jit_decode_step": eng._decode.lower(
            eng.params, eng.cache, jnp.zeros((eng.slots,), jnp.int32)),
        "jit_write_slot": eng._write_slot.lower(eng.cache, cache1,
                                                np.int32(1)),
    }
    for name, low in lowered.items():
        assert low.as_text().startswith(f"module @{name} "), name


def test_jitted_slot_write_equals_the_eager_one():
    eng = _tiny_engine(slots=3)
    rng = np.random.default_rng(0)
    cache = {k: jnp.asarray(rng.standard_normal(v.shape).astype(v.dtype)
                            if v.dtype != jnp.int32 else
                            rng.integers(0, 9, v.shape, np.int32))
             for k, v in eng.cache.items()}
    _, cache1 = eng._prefill(
        eng.params, {"tokens": jnp.asarray(rng.integers(1, 50, (1, 7)),
                                           jnp.int32)})
    compiled = []
    for slot in range(3):
        # the destination is donated: each call writes into its own copy
        got = eng._write_slot(jax.tree.map(jnp.copy, cache), cache1,
                              np.int32(slot))
        compiled.append(eng._write_slot._cache_size())
        for k, dst in cache.items():
            ax = 0 if k == "len" else 1
            idx = [slice(None)] * dst.ndim
            idx[ax] = slice(slot, slot + 1)
            want = dst.at[tuple(idx)].set(cache1[k])
            assert got[k].dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want))
    assert compiled[0] == compiled[-1]          # the slot is traced


def test_engine_donates_its_cache_and_generates_as_without_donation():
    """`jit_decode_step` and `jit_write_slot` take the engine's cache over:
    after a tick the cache it held before is deleted, and the tokens are
    those of the same programs jitted without donation."""
    prompts = [np.arange(a, b, dtype=np.int32)
               for a, b in [(1, 6), (3, 12), (2, 9)]]
    outputs = []
    for donate in (True, False):
        eng = _tiny_engine()
        if not donate:
            eng._decode = jax.jit(eng._decode.__wrapped__)
            eng._write_slot = jax.jit(eng._write_slot.__wrapped__)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new=5))
        before = eng.cache
        eng.tick()                  # admits two: two slot writes, one step
        assert all(x.is_deleted() == donate
                   for x in jax.tree.leaves(before))
        outputs.append([r.generated for r in
                        sorted(eng.run(), key=lambda r: r.rid)])
    assert outputs[0] == outputs[1]
    assert [len(g) for g in outputs[0]] == [5, 5, 5]


def test_requests_are_stamped_as_their_admission_begins():
    import time
    eng = _tiny_engine()
    reqs = [Request(rid=i, prompt=np.arange(1, 4 + i, dtype=np.int32),
                    max_new=2 + i) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    assert all(r.t_admit is None for r in reqs)
    t0 = time.perf_counter()
    eng.tick()                      # two slots: rids 0 and 1 are admitted
    t1 = time.perf_counter()
    assert t0 <= reqs[0].t_admit <= reqs[1].t_admit <= t1
    assert reqs[2].t_admit is None
    assert len(eng.run()) == 3
    # rid 2 waits for rid 0's slot, freed after its second token
    assert t1 <= reqs[2].t_admit
    assert [len(r.generated) for r in reqs] == [r.max_new for r in reqs]
