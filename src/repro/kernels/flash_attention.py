"""Flash attention Pallas kernels (prefill + single-token decode).

TPU-native tiling: the (q, k) score tile lives in VMEM, the running softmax
statistics in VMEM scratch, and the grid pipelines HBM->VMEM block fetches.
Supports causal masking, GQA (grouped KV heads) and local (sliding-window)
attention — the latter is what makes ``recurrentgemma``'s 2048-window layers
linear in sequence length.

Block sizes are exposed as parameters so the Kernel Scientist can tune
them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import default_interpret

NEG_INF = -1e30


def _flash_body(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    block_q: int,
    block_k: int,
    k_steps: int,
    causal: bool,
    window,
):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # block-level visibility: skip blocks strictly above the causal diagonal
    # or strictly outside the local window.
    visible = True
    if causal:
        visible = jnp.logical_and(visible, k_start <= q_start + block_q - 1)
    if window is not None:
        visible = jnp.logical_and(visible, k_start + block_k - 1 > q_start - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        # rows with no visible key yet keep m == NEG_INF; exp must stay 0 there
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_new))
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == k_steps - 1)
    def _store():
        l = l_ref[...]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool | None = None,
):
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D); Hq % Hkv == 0 (GQA)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    scale = scale if scale is not None else 1.0 / (d**0.5)
    grid = (b, hq, s // block_q, s // block_k)

    body = functools.partial(
        _flash_body,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        k_steps=s // block_k,
        causal=causal,
        window=window,
    )
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bb, h, iq, ik: (bb, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bb, h, iq, ik, g=g: (bb, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bb, h, iq, ik, g=g: (bb, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda bb, h, iq, ik: (bb, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=default_interpret() if interpret is None else interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Decode attention: one new token against a long KV cache
# ---------------------------------------------------------------------------
def _decode_body(
    len_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    block_k: int,
    k_steps: int,
):
    bb, ik = pl.program_id(0), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = len_ref[bb]
    k_start = ik * block_k

    @pl.when(k_start < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (g, d) — the GQA query group
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (g, bk)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < kv_len, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_new))
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == k_steps - 1)
    def _store():
        l = l_ref[...]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attention(
    q,
    k,
    v,
    kv_len,
    *,
    scale: float | None = None,
    block_k: int = 512,
    interpret: bool | None = None,
):
    """q: (B, Hq, D); k, v: (B, Hkv, S, D); kv_len: (B,) int32 valid lengths.

    The GQA group (Hq // Hkv queries sharing one KV head) forms the row block,
    so the MXU sees a (g, d) x (d, bk) matmul per step instead of a degenerate
    single-row product.
    """
    b, hq, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    g = hq // hkv
    block_k = min(block_k, s)
    assert s % block_k == 0
    scale = scale if scale is not None else 1.0 / (d**0.5)
    qg = q.reshape(b, hkv, g, d)
    grid = (b, hkv, s // block_k)

    body = functools.partial(
        _decode_body, scale=scale, block_k=block_k, k_steps=s // block_k
    )
    out = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # index maps get the scalar-prefetch ref as a trailing arg
                pl.BlockSpec((1, 1, g, d), lambda bb, h, ik, _len: (bb, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, d), lambda bb, h, ik, _len: (bb, h, ik, 0)),
                pl.BlockSpec((1, 1, block_k, d), lambda bb, h, ik, _len: (bb, h, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d), lambda bb, h, ik, _len: (bb, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=default_interpret() if interpret is None else interpret,
    )(kv_len.astype(jnp.int32), qg, k, v)
    return out.reshape(b, hq, d)
