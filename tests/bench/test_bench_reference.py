"""The program against the benchmark's plain float32 references, on the CPU
at reduced widths, on weights made from a seed.

Tolerances: the program stores and computes in bfloat16 (8 significant
bits, relative rounding 2^-9 per operation) while the reference computes
in float32 on the same bfloat16 weights.  Over two layers the logits then
differ by about 1% (relative L2); 3% leaves room and still fails the
float8 control (3 significant bits), which each test also checks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testkit import CONFIGS, TINY_TRAIN, tiny_conf
from bench import run, train, traffic

LOGITS_RTOL = 3e-2
#: loss of one step: a mean over 128 tokens of bf16 logits
LOSS_RTOL = 1e-2
#: a leaf's gradient norm: bf16 activations and gradients, about 1%
GRAD_NORM_RTOL = 5e-2


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module", params=CONFIGS)
def model(request):
    conf = tiny_conf(request.param)
    ref, prog = run.family(conf)
    cfg = prog.arch_config(conf)
    return conf, ref, prog, cfg


def test_prefill_and_cached_decode_match_reference(model):
    from repro.models import api
    conf, ref, prog, cfg = model
    seed, s0, steps = 11, 32, 8
    params = prog.program_weights(conf, cfg, seed)
    toks = traffic.rng_for(seed, 9).integers(0, cfg.vocab, s0 + steps)
    logits, cache = api.prefill(params, cfg,
                                {"tokens": jnp.asarray(toks[None, :s0])}, 64)
    got = [np.asarray(logits[0], np.float32)]
    for t in range(s0, s0 + steps - 1):
        logits, cache = api.decode_step(params, cfg, cache,
                                        jnp.asarray(toks[t:t + 1]))
        got.append(np.asarray(logits[0], np.float32))
    w = ref.make_weights(conf, seed)
    rows = np.arange(s0 - 1, s0 + steps - 1)
    want = np.asarray(ref.logits_at(w, conf, toks, rows))
    for i, g in enumerate(got):
        assert rel_l2(g, want[i]) < LOGITS_RTOL, i
    control = np.asarray(ref.logits_at(w, conf, toks, rows, "fp8"))
    assert rel_l2(control, want) > LOGITS_RTOL


def test_reference_is_causal_and_deterministic(model):
    conf, ref, _, cfg = model
    w = ref.make_weights(conf, 3)
    toks = np.arange(40) % cfg.vocab
    a = np.asarray(ref.logits_at(w, conf, toks, np.arange(20)))
    toks2 = toks.copy()
    toks2[30:] = 7
    b = np.asarray(ref.logits_at(w, conf, toks2, np.arange(20)))
    np.testing.assert_array_equal(a, b)
    w2 = ref.make_weights(conf, 3)
    for x, y in zip(jax.tree.leaves(w), jax.tree.leaves(w2)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_train_loss_and_gradients_match_reference(model):
    from repro.models import api
    conf, ref, prog, cfg = model
    seed = 21
    params = prog.program_weights(conf, cfg, seed)
    b = traffic.train_batch(seed, 0, 2, 64, cfg.vocab)
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    (loss, _), grads = jax.value_and_grad(
        lambda p: api.loss_fn(p, cfg, batch), has_aux=True)(params)
    got = train.flat(train.leaf_norms(prog.from_program(grads, cfg.vocab)))

    def ref_read(mode):
        w = jax.tree.map(lambda x: x.astype(jnp.float32),
                         ref.make_weights(conf, seed))
        loss, g = train.ref_grads(w, ref.dims(conf), b["tokens"],
                                  b["labels"], ref, mode)
        return loss, train.flat(train.leaf_norms(g))

    ref_loss, want = ref_read("f32")
    assert abs(float(loss) - ref_loss) / ref_loss < LOSS_RTOL
    assert train.norm_gap(got, want) < GRAD_NORM_RTOL
    _, control = ref_read("fp8")
    assert train.norm_gap(control, want) > train.norm_gap(got, want)


def test_reference_steps_follow_adamw(model):
    """One reference step from the optimizer written out agrees with the
    program's step run on float32 weights (no bf16 rounding of the
    update), leaf by leaf."""
    from repro.optim import adamw
    from repro.train import make_train_step
    conf, ref, prog, cfg = model
    seed = 4
    o = TINY_TRAIN["optimizer"]
    losses, grad, change = train.reference_steps(ref, conf, TINY_TRAIN,
                                                 seed, 2)
    f32 = jax.tree.map(lambda x: x.astype(jnp.float32),
                       prog.program_weights(conf, cfg, seed))
    import dataclasses
    cfg32 = dataclasses.replace(cfg, param_dtype="float32")
    step = jax.jit(make_train_step(
        cfg32, peak_lr=o["peak_lr"], warmup_steps=o["warmup_steps"],
        total_steps=o["total_steps"],
        opt_cfg=adamw.AdamWConfig(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                                  weight_decay=o["weight_decay"],
                                  clip_norm=o["clip_norm"])))
    p, opt = f32, adamw.init(f32)
    got_losses = []
    for k in range(2):
        bt = traffic.train_batch(seed, k, 2, 64, cfg.vocab)
        p, opt, m = step(p, opt, {k2: jnp.asarray(v) for k2, v in bt.items()},
                         np.int32(k))
        got_losses.append(float(m["loss"]))
    got_change = train.flat(train.leaf_norms(prog.from_program(
        jax.tree.map(lambda a, b: a - b, p, f32), cfg.vocab)))
    np.testing.assert_allclose(got_losses, losses, rtol=LOSS_RTOL)
    assert train.norm_gap(got_change, change,
                          train.moving_leaves(grad)) < GRAD_NORM_RTOL
