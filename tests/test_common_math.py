"""Numerics of the shared layers: RoPE/M-RoPE, RMSNorm, chunked xent,
decode attention."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.models.common import (
    apply_mrope, apply_rope, chunked_softmax_xent, decode_attention_xla,
    rms_norm,
)


def test_rope_preserves_norm(rng):
    x = jnp.asarray(rng.standard_normal((2, 4, 16, 32)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    y = apply_rope(x, pos, 1e4)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)


def test_rope_relative_property(rng):
    """<rope(q,i), rope(k,j)> depends only on i - j."""
    q = jnp.asarray(rng.standard_normal((1, 1, 1, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 1, 32)), jnp.float32)

    def dot_at(i, j):
        qi = apply_rope(q, jnp.array([[i]], jnp.int32), 1e4)
        kj = apply_rope(k, jnp.array([[j]], jnp.int32), 1e4)
        return float(jnp.sum(qi * kj))

    assert abs(dot_at(5, 3) - dot_at(12, 10)) < 1e-4
    assert abs(dot_at(5, 3) - dot_at(6, 3)) > 1e-6


def test_mrope_reduces_to_rope_for_equal_components(rng):
    x = jnp.asarray(rng.standard_normal((2, 2, 8, 24)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
    pos3 = jnp.stack([pos, pos, pos], 0)
    np.testing.assert_allclose(np.asarray(apply_mrope(x, pos3, 1e4)),
                               np.asarray(apply_rope(x, pos, 1e4)),
                               atol=1e-5)


def test_rms_norm(rng):
    x = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32) * 7
    y = rms_norm(x, jnp.zeros((32,)), 1e-6)
    rms = np.sqrt(np.mean(np.asarray(y) ** 2, -1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-3)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 4).map(lambda i: 2 ** i))
def test_chunked_xent_matches_direct(n_chunks):
    t, d, v = 32, 16, 64
    key = jax.random.key(0)
    x = jax.random.normal(key, (t, d), jnp.float32)
    emb = jax.random.normal(jax.random.key(1), (v, d), jnp.float32)
    labels = jax.random.randint(jax.random.key(2), (t,), 0, v, jnp.int32)
    nll, denom = chunked_softmax_xent(x, emb, labels,
                                      chunk=t // n_chunks)
    logits = x @ emb.T
    direct = -jax.nn.log_softmax(logits)[jnp.arange(t), labels].sum()
    np.testing.assert_allclose(float(nll), float(direct), rtol=1e-5)
    assert float(denom) == t


def test_chunked_xent_grads_match(rng):
    t, d, v = 16, 8, 32
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    emb = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, t), jnp.int32)

    def f_chunk(x, emb):
        nll, _ = chunked_softmax_xent(x, emb, labels, chunk=4)
        return nll

    def f_direct(x, emb):
        return -jax.nn.log_softmax(x @ emb.T)[jnp.arange(t), labels].sum()

    g1 = jax.grad(f_chunk, argnums=(0, 1))(x, emb)
    g2 = jax.grad(f_direct, argnums=(0, 1))(x, emb)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_decode_attention_of_the_new_token_equals_writing_it_first(rng):
    """bfloat16, slots at lengths 0, 5 and S - 1: attending to the cached
    [0, kv_len) plus the token's own K/V gives what writing them at kv_len
    and attending to kv_len + 1 positions gives."""
    b, s, hkv, g, d = 3, 16, 2, 4, 32
    bf16 = jnp.bfloat16
    q, k, v = (jnp.asarray(rng.standard_normal(shape), bf16) for shape in
               [(b, hkv * g, d), (b, s, hkv, d), (b, s, hkv, d)])
    k1, v1 = (jnp.asarray(rng.standard_normal((b, hkv, d)), bf16)
              for _ in range(2))
    kv_len = jnp.array([0, 5, s - 1], jnp.int32)
    slots = jnp.arange(b)
    want = decode_attention_xla(q, k.at[slots, kv_len].set(k1),
                                v.at[slots, kv_len].set(v1), kv_len + 1)
    got = decode_attention_xla(q, k, v, kv_len, new=(k1, v1))
    assert got.dtype == want.dtype == bf16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2 ** -7, atol=2 ** -7)
    # the slot at 0 sees only its own value
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32),
        np.asarray(jnp.repeat(v1[0], g, axis=0), np.float32), rtol=2 ** -7)
