"""Train-step semantics: microbatch accumulation parity, donation safety,
deterministic resume math."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import api
from repro.optim import adamw
from repro.train import make_prefill_step, make_train_step


def _setup(mb_vocab_seed=0):
    cfg = dataclasses.replace(configs.get_reduced("qwen2.5-3b"),
                              param_dtype="float32")
    params = api.init_params(cfg, jax.random.key(mb_vocab_seed))
    opt = adamw.init(params)
    batch = api.make_batch(cfg, 4, 64)
    return cfg, params, opt, batch


def test_microbatched_step_matches_full_batch():
    cfg, params, opt, batch = _setup()
    s1 = jax.jit(make_train_step(cfg, peak_lr=1e-3, total_steps=10))
    s2 = jax.jit(make_train_step(cfg, peak_lr=1e-3, total_steps=10,
                                 microbatches=2))
    p1, o1, m1 = s1(params, opt, batch, jnp.int32(0))
    p2, o2, m2 = s2(params, opt, batch, jnp.int32(0))
    # microbatch losses are means over slices; grads averaged — parity up
    # to f32 reduction order
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-4)


def test_microbatched_prefill_matches_full():
    cfg, params, _, batch = _setup()
    f1 = jax.jit(make_prefill_step(cfg, 96))
    f2 = jax.jit(make_prefill_step(cfg, 96, microbatches=2))
    l1, c1 = f1(params, batch)
    l2, c2 = f2(params, batch)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=1e-4, rtol=1e-4)
    for k in c1:
        np.testing.assert_allclose(np.asarray(c1[k]), np.asarray(c2[k]),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=k)


def test_two_steps_deterministic():
    cfg, params, opt, batch = _setup()
    step = jax.jit(make_train_step(cfg, peak_lr=1e-3, total_steps=10))
    pa, oa, _ = step(params, opt, batch, jnp.int32(0))
    cfg2, params2, opt2, batch2 = _setup()
    pb, ob, _ = step(params2, opt2, batch2, jnp.int32(0))
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _scopes_in(compiled_text: str) -> set:
    """The named scopes in a compiled program's op names, with JAX's
    transformation wrappers taken off: "transpose(jvp(loss))" -> loss."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', compiled_text):
        for part in path.split("/"):
            while (m := re.match(r"^[\w.-]+\((.*)\)$", part)):
                part = m.group(1)
            out.add(part)
    return out


@pytest.mark.parametrize("program,scopes", [
    ("train", {"embed", "attention", "ffn", "head", "loss", "adamw"}),
    ("prefill", {"embed", "attention", "kv_cache", "ffn", "head"}),
    ("decode", {"embed", "attention", "kv_cache", "ffn", "head"}),
])
def test_named_scopes_reach_the_compiled_programs(program, scopes):
    cfg = configs.get_reduced("qwen2.5-3b")
    params = api.abstract_params(cfg)
    batch = jax.eval_shape(lambda: api.make_batch(cfg, 2, 32))
    if program == "train":
        opt = jax.eval_shape(adamw.init, params)
        low = jax.jit(make_train_step(cfg)).lower(params, opt, batch,
                                                  jnp.int32(0))
    elif program == "prefill":
        low = jax.jit(make_prefill_step(cfg, 64)).lower(params, batch)
    else:
        cache = jax.eval_shape(lambda: api.init_cache(cfg, 2, 64))
        low = jax.jit(lambda p, c, t: api.decode_step(p, cfg, c, t)).lower(
            params, cache, jax.ShapeDtypeStruct((2,), jnp.int32))
    assert scopes <= _scopes_in(low.compile().as_text())
