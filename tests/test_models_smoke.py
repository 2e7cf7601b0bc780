"""Per-arch smoke tests: every assigned architecture's reduced config runs a
train step (finite loss, finite grads) and — where applicable — a
prefill+decode that agrees with the full forward pass."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import api
from repro.optim import adamw
from repro.train import make_train_step

ARCHS = list(configs.ARCH_IDS)


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_finite(arch):
    cfg = configs.get_reduced(arch)
    params = api.init_params(cfg, jax.random.key(0))
    opt = adamw.init(params)
    batch = api.make_batch(cfg, 2, 64)
    step = jax.jit(make_train_step(cfg, peak_lr=1e-3, total_steps=10))
    params, opt, metrics = step(params, opt, batch, jnp.int32(0))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    for leaf in jax.tree.leaves(params):
        assert np.all(np.isfinite(np.asarray(leaf, dtype=np.float32)))


@pytest.mark.parametrize("arch", ARCHS)
def test_output_shapes(arch):
    cfg = configs.get_reduced(arch)
    params = api.init_params(cfg, jax.random.key(0))
    batch = api.make_batch(cfg, 2, 64)
    logits, cache = jax.jit(
        lambda p, b: api.prefill(p, cfg, b, 96))(params, batch)
    assert logits.shape == (2, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits)))
    if cfg.encoder_only:
        assert cache is None


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if not configs.get_reduced(a).encoder_only
                                  and configs.get_reduced(a).inputs == "tokens"])
def test_prefill_decode_matches_forward(arch):
    """Greedy continuation via (prefill + decode_step) must match running
    the full sequence through the forward pass (f32 params for tightness)."""
    cfg = dataclasses.replace(configs.get_reduced(arch),
                              param_dtype="float32")
    if cfg.moe is not None:  # drops in prefill-but-not-decode break parity
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=100.0))
    params = api.init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(0)
    b, s = 2, 48
    toks = rng.integers(0, cfg.vocab, (b, s + 1)).astype(np.int32)

    # full forward logits at position s-1 predict token at s
    full = {"tokens": jnp.asarray(toks)}
    logits_full, _ = api.prefill(params, cfg, full, s + 1)  # last position

    # prefill on the first s tokens, then decode token s
    pre = {"tokens": jnp.asarray(toks[:, :s])}
    logits_pre, cache = api.prefill(params, cfg, pre, s + 8)
    logits_dec, cache = api.decode_step(
        params, cfg, cache, jnp.asarray(toks[:, s]))

    np.testing.assert_allclose(
        np.asarray(logits_dec), np.asarray(logits_full),
        atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "deepseek-v2-236b"])
def test_decode_step_writes_only_the_new_entries(arch):
    """Slots at lengths 0, 7 and max_seq - 1 over a cache whose unused
    positions hold noise: each slot's logits are a prefill's over its
    history and the new token, its new K/V land at (layer, slot, length),
    and every other cache position keeps its value, bit for bit."""
    cfg = dataclasses.replace(configs.get_reduced(arch),
                              param_dtype="float32")
    if cfg.moe is not None:  # drops in prefill-but-not-decode break parity
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=100.0))
    params = api.init_params(cfg, jax.random.key(2))
    max_seq, lens = 16, [0, 7, 15]
    rng = np.random.default_rng(0)
    hist = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]
    new_tok = rng.integers(0, cfg.vocab, len(lens)).astype(np.int32)

    prefill = jax.jit(lambda t: api.prefill(params, cfg, {"tokens": t[None]},
                                            max_seq))
    cache = {k: rng.standard_normal(v.shape).astype(np.float32)
             for k, v in api.init_cache(cfg, len(lens), max_seq).items()
             if k != "len"}
    for slot, h in enumerate(hist):
        if len(h):
            _, one = prefill(h)
            for k, c in cache.items():
                c[:, slot, :len(h)] = np.asarray(one[k])[:, 0, :len(h)]
    cache["len"] = np.asarray(lens, np.int32)
    logits, out = jax.jit(lambda c, t: api.decode_step(params, cfg, c, t))(
        cache, jnp.asarray(new_tok))

    np.testing.assert_array_equal(np.asarray(out["len"]),
                                  np.asarray(lens) + 1)
    want = {k: c.copy() for k, c in cache.items() if k != "len"}
    for slot, n in enumerate(lens):
        full_logits, full = prefill(np.append(hist[slot], new_tok[slot]))
        np.testing.assert_allclose(np.asarray(logits[slot]),
                                   np.asarray(full_logits[0]),
                                   atol=2e-3, rtol=2e-3)
        for k, w in want.items():
            got = np.asarray(out[k])[:, slot, n]
            np.testing.assert_allclose(got, np.asarray(full[k])[:, 0, n],
                                       atol=1e-5, rtol=1e-5)
            w[:, slot, n] = got
    for k, w in want.items():
        np.testing.assert_array_equal(np.asarray(out[k]), w)


def test_moe_capacity_dropless_at_decode():
    from repro.models.config import MoEConfig
    from repro.models.moe import _capacity
    cfg = MoEConfig(n_experts=8, top_k=2, d_ff_expert=16, router_groups=4)
    assert _capacity(2, cfg) == 4     # Tg*k: exact-dropless when tiny


def test_mrope_decode_runs():
    cfg = dataclasses.replace(configs.get_reduced("qwen2-vl-72b"),
                              param_dtype="float32")
    params = api.init_params(cfg, jax.random.key(0))
    batch = api.make_batch(cfg, 2, 32)
    _, cache = api.prefill(params, cfg, batch, 48)
    logits, cache = api.decode_step(params, cfg, cache,
                                    jnp.array([1, 2], jnp.int32))
    assert np.all(np.isfinite(np.asarray(logits)))


def test_encoder_has_no_decode():
    cfg = configs.get_reduced("hubert-xlarge")
    with pytest.raises(ValueError, match="encoder-only"):
        api.decode_step(None, cfg, None, None)


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "recurrentgemma-9b"])
def test_subquadratic_long_decode_state_is_constant_size(arch):
    """long_500k viability: cache size must not grow with max_seq."""
    cfg = configs.get_reduced(arch)
    c1 = api.init_cache(cfg, 1, 1_024)
    c2 = api.init_cache(cfg, 1, 65_536)
    s1 = sum(x.size for k, x in c1.items() if k != "len")
    s2 = sum(x.size for k, x in c2.items() if k != "len")
    if cfg.family == "ssm":
        assert s1 == s2
    else:  # rglru: only the fixed window grows caches, already capped
        assert s2 <= s1 * (cfg.rglru.window / min(1024, cfg.rglru.window))
