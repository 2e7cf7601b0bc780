"""The XLA-path flash attention (custom VJP) vs oracle: values + grads."""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import ref
from repro.models import api
from repro.models.common import attention_plan, blockwise_attention

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _qkv(rng, b, hq, hkv, s, d, dv=None):
    dv = dv or d
    q = jnp.asarray(rng.standard_normal((b, hq, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, dv)), jnp.float32)
    return q, k, v


def _cases(s, masks):
    """Each mask at length ``s`` under 32-wide chunks, then at lengths whose
    divisor chunk under 128 / 256 collapses to 32 (so they run padded):
    160 pads the queries alone, 288 the queries and the keys."""
    return ([pytest.param(c, w, s, 32, 32, id=f"{c}-{w}") for c, w in masks]
            + [pytest.param(c, w, n, 128, 256, id=f"{c}-{w}-S{n}")
               for n in (160, 288) for c, w in masks])


@pytest.mark.parametrize("causal,window,s,q_chunk,k_chunk", _cases(
    128, [(True, None), (False, None), (True, 48)]))
def test_forward_matches(rng, causal, window, s, q_chunk, k_chunk):
    q, k, v = _qkv(rng, 2, 4, 2, s, 32)
    got = blockwise_attention(q, k, v, causal=causal, window=window,
                              q_chunk=q_chunk, k_chunk=k_chunk)
    want = ref.attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal,window,s,q_chunk,k_chunk", _cases(
    64, [(True, None), (True, 48), (False, None)]))
def test_gradients_match(rng, causal, window, s, q_chunk, k_chunk):
    q, k, v = _qkv(rng, 1, 2, 1, s, 32)

    def f(fn):
        return lambda q, k, v: jnp.sum(
            jnp.sin(fn(q, k, v).astype(jnp.float32)))

    ours = jax.grad(f(lambda q, k, v: blockwise_attention(
        q, k, v, causal=causal, window=window, q_chunk=q_chunk,
        k_chunk=k_chunk)),
        argnums=(0, 1, 2))(q, k, v)
    theirs = jax.grad(f(lambda q, k, v: ref.attention(
        q, k, v, causal=causal, window=window)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ours, theirs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_mla_style_dv_neq_dq(rng):
    q, k, v = _qkv(rng, 1, 4, 4, 64, 48, dv=32)
    got = blockwise_attention(q, k, v, causal=True, q_chunk=32, k_chunk=32)
    assert got.shape == (1, 4, 64, 32)
    want = _naive(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _naive(q, k, v):
    d = q.shape[-1]
    s = q.shape[2]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    mask = np.tril(np.ones((s, s), bool))
    logits = jnp.where(mask, logits, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), v)


def test_kv_len_masking(rng):
    q, k, v = _qkv(rng, 2, 2, 2, 64, 32)
    kv_len = jnp.array([40, 64], jnp.int32)
    got = blockwise_attention(q, k, v, causal=True, kv_len=kv_len,
                              q_chunk=32, k_chunk=32)
    want_full = ref.attention(q, k, v, causal=True)
    # rows before kv_len see only valid keys == plain causal result there
    np.testing.assert_allclose(np.asarray(got[0, :, :40]),
                               np.asarray(want_full[0, :, :40]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got[1]),
                               np.asarray(want_full[1]), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_kv_len_masking_on_a_padded_length(rng, causal):
    """288 under 128 / 256 runs padded to 384; keys past ``kv_len`` stay
    hidden from every row, as in a plain attention over the valid keys."""
    q, k, v = _qkv(rng, 2, 2, 2, 288, 32)
    kv_len = jnp.array([200, 288], jnp.int32)
    got = blockwise_attention(q, k, v, causal=causal, kv_len=kv_len,
                              q_chunk=128, k_chunk=256)
    for i, n in enumerate((200, 288)):
        qi, ki, vi = q[i:i + 1], k[i:i + 1, :, :n], v[i:i + 1, :, :n]
        if causal:              # rows past kv_len are not checked
            qi = qi[:, :, :n]
            want = ref.attention(qi, ki, vi, causal=True)
        else:                   # every row over the valid keys alone
            logits = jnp.einsum("bhqd,bhkd->bhqk", qi, ki) / np.sqrt(32)
            want = jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(logits, -1), vi)
        np.testing.assert_allclose(np.asarray(got[i:i + 1, :, :qi.shape[2]]),
                                   np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("s,q_chunk,k_chunk,want", [
    (4096, 512, 1024, (4096, 4096, 512, 1024, 20)),
    (3072, 512, 1024, (3072, 3072, 512, 1024, 12)),
    (1024, 512, 1024, (1024, 1024, 512, 1024, 2)),
    (384, 512, 1024, (384, 384, 384, 384, 1)),
    (160, 32, 32, (160, 160, 32, 32, 15)),
    (96, 128, 256, (96, 96, 96, 96, 1)),
])
def test_tiled_lengths_keep_their_chunks_and_run_unpadded(s, q_chunk,
                                                          k_chunk, want):
    """A length that splits into tiles of at least min(chunks, 256), or fits
    one chunk, keeps the largest dividing chunks and gets no pad op."""
    assert tuple(attention_plan(s, s, q_chunk, k_chunk)) == want
    q = jax.ShapeDtypeStruct((1, 2, s, 32), jnp.float32)
    text = jax.jit(lambda q, k, v: blockwise_attention(
        q, k, v, q_chunk=q_chunk, k_chunk=k_chunk)).lower(q, q, q).as_text()
    assert "stablehlo.pad" not in text


def test_collapsing_lengths_run_padded():
    assert tuple(attention_plan(1056, 1056, 512, 1024)) == (
        1280, 1280, 256, 256, 15)
    assert tuple(attention_plan(864, 864, 512, 1024)) == (
        1024, 864, 512, 864, 2)
    q = jax.ShapeDtypeStruct((1, 2, 288, 32), jnp.float32)
    text = jax.jit(lambda q, k, v: blockwise_attention(
        q, k, v, q_chunk=128, k_chunk=256)).lower(q, q, q).as_text()
    assert "stablehlo.pad" in text


def test_serving_grid_plans_at_most_100_steps_per_layer():
    """Every prompt length of the serving cell's grid, under qwen2.5-3b's
    chunks: at most 100 flash-loop steps, padded by less than a tile."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.traffic import length_grid
    mix = json.loads((ROOT / "bench/traffic/azure-conv-20slots.json")
                     .read_text())
    cfg = configs.get_config("qwen2.5-3b")
    t = min(cfg.attn_q_chunk, cfg.attn_k_chunk, 256)
    grid = [int(n) for n in length_grid(mix["prompt_len"])]
    assert len(grid) == 16 and max(grid) == 3072
    for s in grid:
        plan = attention_plan(s, s, cfg.attn_q_chunk, cfg.attn_k_chunk)
        assert api.attn_steps(cfg, s) == plan.steps <= 100, (s, plan)
        assert 0 <= plan.sq - s < t and 0 <= plan.sk - s < t, (s, plan)
        assert min(plan.q_chunk, plan.k_chunk) >= min(t, s), (s, plan)
