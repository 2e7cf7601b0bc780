"""Shared model machinery: norms, rotary embeddings, blockwise attention,
chunked cross-entropy.  Pure JAX/XLA — the Pallas kernels in
``repro.kernels`` are drop-in replacements for the hot paths on real TPUs;
the XLA formulations here are what the multi-pod dry-run lowers.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, scale, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))
            ).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE + Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, H, S, D); positions: (B, S) int32."""
    freqs = rope_freqs(x.shape[-1], theta)                       # (D/2,)
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)                  # (B,1,S,D/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions, theta: float, sections=(1, 2, 2)):
    """Qwen2-VL multimodal RoPE: positions (3, B, S) for (t, h, w); the D/2
    frequency pairs are split between the three components in `sections`
    proportion (16/24/24 in the released model ~ 1:1.5:1.5)."""
    d2 = x.shape[-1] // 2
    total = sum(sections)
    splits = [d2 * s // total for s in sections]
    splits[-1] = d2 - sum(splits[:-1])
    freqs = rope_freqs(x.shape[-1], theta)                       # (D/2,)
    comp = jnp.repeat(
        jnp.arange(3), jnp.asarray(splits), total_repeat_length=d2)  # (D/2,)
    pos = positions.astype(jnp.float32)                          # (3, B, S)
    # pick the position component per frequency pair
    pos_per_freq = pos[comp]                                     # (D/2, B, S)
    angles = jnp.transpose(pos_per_freq, (1, 2, 0))[:, None] * freqs  # (B,1,S,D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blockwise attention (XLA path).
#
# FLOP-exact flash attention: instead of scanning all (q_chunk, k_chunk)
# pairs and masking (which doubles causal HLO FLOPs and poisons the roofline
# compute term), we enumerate only the *visible* chunk pairs statically and
# lax.scan over that list.  Causal gives ~S^2/2, a local window gives O(S).
# ---------------------------------------------------------------------------
def _visible_pairs(nq: int, nk: int, q_chunk: int, k_chunk: int,
                   causal: bool, window: Optional[int], offset: int):
    """Static list of (qi, ki) chunk pairs with any visible element.
    `offset` is the absolute position of query 0 (for cached decode)."""
    pairs = []
    for qi in range(nq):
        q_lo = offset + qi * q_chunk
        q_hi = q_lo + q_chunk - 1
        for ki in range(nk):
            k_lo = ki * k_chunk
            k_hi = k_lo + k_chunk - 1
            if causal and k_lo > q_hi:
                continue
            if window is not None and k_hi <= q_lo - window:
                continue
            pairs.append((qi, ki))
    return pairs


NEG_INF = -1e30


def _split_pairs(pairs, q_chunk, k_chunk, causal, window, q_offset,
                 has_kv_len):
    """Interior blocks need NO positional mask (TPU-flash structure: masking
    only on causal/window boundary blocks).  Keeping the interior scan
    mask-free also stops XLA hoisting a stacked all-pairs mask tensor out of
    the loop (observed as a 10 GiB pred buffer on qwen1.5 train_4k)."""
    full, masked = [], []
    for qi, ki in pairs:
        q_lo = q_offset + qi * q_chunk
        q_hi = q_lo + q_chunk - 1
        k_lo, k_hi = ki * k_chunk, ki * k_chunk + k_chunk - 1
        needs = has_kv_len
        if causal and k_hi > q_lo:
            needs = True
        if window is not None and k_lo <= q_hi - window:
            needs = True
        (masked if needs else full).append((qi, ki))
    return full, masked


def _block_logits_masked(s, qs, ks, q_chunk, k_chunk, scale, causal, window,
                         q_offset, kv_len):
    q_pos = q_offset + qs + jax.lax.broadcasted_iota(
        jnp.int32, (q_chunk, k_chunk), 0)
    k_pos = ks + jax.lax.broadcasted_iota(
        jnp.int32, (q_chunk, k_chunk), 1)
    mask = jnp.ones((q_chunk, k_chunk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)
    if kv_len is not None:
        valid = (ks + jnp.arange(k_chunk)[None, :]) < kv_len[:, None]
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    return s


@functools.lru_cache(maxsize=None)
def _make_blockwise(b, h, sq, sk, d, dv, q_chunk, k_chunk, scale,
                    causal, window, q_offset, has_kv_len, dtype_name):
    """FLOP-exact flash attention over the statically-visible chunk pairs,
    with a hand-written (flash) VJP: the forward saves only (q, k, v, out,
    m, l) — O(S) residuals — and the backward recomputes each score block,
    exactly like the Pallas/TPU flash kernels do.  Without this, AD of the
    pair-scan stores O(pairs * S) carries and blows per-device HBM.

    Operates on (B, H, S, D) with KV pre-expanded to H query heads (the
    expansion is per-device cheap once H is sharded over 'model'; its
    gather-VJP sums the group gradient back to the KV heads)."""
    nq, nk = sq // q_chunk, sk // k_chunk
    pairs = _visible_pairs(nq, nk, q_chunk, k_chunk, causal, window, q_offset)
    full_pairs, masked_pairs = _split_pairs(
        pairs, q_chunk, k_chunk, causal, window, q_offset, has_kv_len)

    def logits(qc, kc, qs, ks, kv_len, apply_mask):
        s = jnp.einsum("bhqd,bhkd->bhqk", qc, kc,
                       preferred_element_type=jnp.float32) * scale
        if apply_mask:
            s = _block_logits_masked(s, qs, ks, q_chunk, k_chunk, scale,
                                     causal, window, q_offset, kv_len)
        return s

    def fwd_impl(q, k, v, kv_len):
        acc0 = jnp.zeros((b, h, sq, dv), jnp.float32)
        m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, sq), jnp.float32)

        def step(carry, pair, apply_mask):
            acc, m, l = carry
            qs, ks = pair[0] * q_chunk, pair[1] * k_chunk
            qc = jax.lax.dynamic_slice_in_dim(q, qs, q_chunk, axis=2)
            kc = jax.lax.dynamic_slice_in_dim(k, ks, k_chunk, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(v, ks, k_chunk, axis=2)
            s = logits(qc, kc, qs, ks, kv_len, apply_mask)
            m_prev = jax.lax.dynamic_slice_in_dim(m, qs, q_chunk, axis=2)
            l_prev = jax.lax.dynamic_slice_in_dim(l, qs, q_chunk, axis=2)
            acc_prev = jax.lax.dynamic_slice_in_dim(acc, qs, q_chunk, axis=2)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[..., None]))
            alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                              jnp.exp(m_prev - m_new))
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            acc_new = acc_prev * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v.dtype), vc,
                preferred_element_type=jnp.float32)
            return (jax.lax.dynamic_update_slice_in_dim(acc, acc_new, qs, 2),
                    jax.lax.dynamic_update_slice_in_dim(m, m_new, qs, 2),
                    jax.lax.dynamic_update_slice_in_dim(l, l_new, qs, 2)), None

        carry = (acc0, m0, l0)
        for plist, msk in ((full_pairs, False), (masked_pairs, True)):
            if plist:
                carry, _ = jax.lax.scan(
                    functools.partial(step, apply_mask=msk), carry,
                    np.asarray(plist, np.int32))
        acc, m, l = carry
        denom = jnp.where(l == 0.0, 1.0, l)
        out = (acc / denom[..., None]).astype(q.dtype)
        return out, (m, l)

    @jax.custom_vjp
    def attn(q, k, v, kv_len):
        return fwd_impl(q, k, v, kv_len)[0]

    def attn_fwd(q, k, v, kv_len):
        out, (m, l) = fwd_impl(q, k, v, kv_len)
        return out, (q, k, v, kv_len, out, m, l)

    def attn_bwd(res, do):
        q, k, v, kv_len, out, m, l = res
        og = out.astype(jnp.float32)
        dog = do.astype(jnp.float32)
        denom = jnp.where(l == 0.0, 1.0, l)
        delta = jnp.sum(og * dog, axis=-1)                     # (B,H,S)
        dq0 = jnp.zeros((b, h, sq, d), jnp.float32)
        dk0 = jnp.zeros(k.shape, jnp.float32)
        dv0 = jnp.zeros(v.shape, jnp.float32)

        def step(carry, pair, apply_mask):
            dq, dk, dv_ = carry
            qs, ks = pair[0] * q_chunk, pair[1] * k_chunk
            qc = jax.lax.dynamic_slice_in_dim(q, qs, q_chunk, axis=2)
            kc = jax.lax.dynamic_slice_in_dim(k, ks, k_chunk, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(v, ks, k_chunk, axis=2)
            mc = jax.lax.dynamic_slice_in_dim(m, qs, q_chunk, axis=2)
            lc = jax.lax.dynamic_slice_in_dim(denom, qs, q_chunk, axis=2)
            dc = jax.lax.dynamic_slice_in_dim(delta, qs, q_chunk, axis=2)
            doc = jax.lax.dynamic_slice_in_dim(dog, qs, q_chunk, axis=2)
            s = logits(qc, kc, qs, ks, kv_len, apply_mask)
            p = jnp.where(s <= NEG_INF / 2, 0.0,
                          jnp.exp(s - mc[..., None])) / lc[..., None]
            dvc = jnp.einsum("bhqk,bhqd->bhkd", p, doc)
            dp = jnp.einsum("bhqd,bhkd->bhqk", doc, vc.astype(jnp.float32))
            ds = p * (dp - dc[..., None]) * scale
            dqc = jnp.einsum("bhqk,bhkd->bhqd", ds, kc.astype(jnp.float32))
            dkc = jnp.einsum("bhqk,bhqd->bhkd", ds, qc.astype(jnp.float32))
            dq = jax.lax.dynamic_update_slice_in_dim(
                dq, jax.lax.dynamic_slice_in_dim(dq, qs, q_chunk, 2) + dqc,
                qs, 2)
            dk = jax.lax.dynamic_update_slice_in_dim(
                dk, jax.lax.dynamic_slice_in_dim(dk, ks, k_chunk, 2) + dkc,
                ks, 2)
            dv_ = jax.lax.dynamic_update_slice_in_dim(
                dv_, jax.lax.dynamic_slice_in_dim(dv_, ks, k_chunk, 2) + dvc,
                ks, 2)
            return (dq, dk, dv_), None

        carry = (dq0, dk0, dv0)
        for plist, msk in ((full_pairs, False), (masked_pairs, True)):
            if plist:
                carry, _ = jax.lax.scan(
                    functools.partial(step, apply_mask=msk), carry,
                    np.asarray(plist, np.int32))
        dq, dk, dv_ = carry
        return (dq.astype(q.dtype), dk.astype(k.dtype),
                dv_.astype(v.dtype), None)

    attn.defvjp(attn_fwd, attn_bwd)
    return attn


class AttentionPlan(NamedTuple):
    """How `blockwise_attention` tiles one call: the lengths it runs
    (padded), its chunks, and its serial flash-loop steps (visible pairs)."""
    sq: int
    sk: int
    q_chunk: int
    k_chunk: int
    steps: int


def _run_len(s: int, chunk: int, t: int) -> int:
    """``s``, or the next multiple of ``t`` where the largest chunk that
    divides ``s`` would fall below ``t`` (a length past ``chunk`` with a
    small factor of two, e.g. 1056 under 512 gives 32)."""
    if s <= chunk or math.gcd(chunk, s) >= t:
        return s
    return -(-s // t) * t


@functools.lru_cache(maxsize=1024)
def attention_plan(sq: int, sk: int, q_chunk: int, k_chunk: int, *,
                   causal: bool = True, window: Optional[int] = None,
                   q_offset: int = 0) -> AttentionPlan:
    """Tiles of at least ``t = min(q_chunk, k_chunk, 256)``: each length is
    padded to a multiple of ``t`` where its divisor chunk would be smaller;
    the chunks are then the largest that divide the (padded) lengths.
    (On a TPU v5e, qwen2.5-3b's prefill of 1056–1632 tokens ran 9–19%
    faster with 256 than with 128: each serial step has a fixed cost.)"""
    t = min(q_chunk, k_chunk, 256)
    sq, sk = _run_len(sq, q_chunk, t), _run_len(sk, k_chunk, t)
    q_chunk = math.gcd(min(q_chunk, sq), sq)   # largest dividing chunk
    k_chunk = math.gcd(min(k_chunk, sk), sk)
    steps = len(_visible_pairs(sq // q_chunk, sk // k_chunk, q_chunk,
                               k_chunk, causal, window, q_offset))
    return AttentionPlan(sq, sk, q_chunk, k_chunk, steps)


def _pad_seq(x, s: int):
    return x if x.shape[2] == s else jnp.pad(
        x, ((0, 0), (0, 0), (0, s - x.shape[2]), (0, 0)))


def blockwise_attention(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        q_chunk: int = 512, k_chunk: int = 1024,
                        scale: Optional[float] = None,
                        kv_len=None, q_offset: int = 0):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D), Hq % Hkv == 0.

    kv_len: optional (B,) valid KV prefix lengths (cached decode/prefill).
    q_offset: absolute position of q[0] relative to the KV sequence.

    Tiles follow `attention_plan`.  Padded queries are sliced off the
    output; padded keys come after every real query, so a causal mask hides
    them, and otherwise they are masked as keys past ``kv_len``.
    """
    from repro.dist import partition as _dist

    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]                 # may differ from d (MLA)
    g = hq // hkv
    plan = attention_plan(sq, sk, q_chunk, k_chunk, causal=causal,
                          window=window, q_offset=q_offset)
    if plan.sk > sk and kv_len is None and not (causal
                                                and q_offset + sq <= sk):
        kv_len = jnp.full((b,), sk, jnp.int32)
    q = _pad_seq(q, plan.sq)
    k, v = _pad_seq(k, plan.sk), _pad_seq(v, plan.sk)
    q_chunk, k_chunk = plan.q_chunk, plan.k_chunk
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    # query-head sharding over 'model'; expand KV to query heads so every
    # per-device tensor inside the flash loops carries H/|model| heads (the
    # gather's VJP sums group gradients back onto the KV heads)
    q = _dist.shard_named(q, ("D", "T", "-", "-"))
    if g > 1:
        kv_map = np.arange(hq) // g
        k = k[:, kv_map]
        v = v[:, kv_map]
    k = _dist.shard_named(k, ("D", "T", "-", "-"))
    v = _dist.shard_named(v, ("D", "T", "-", "-"))

    attn = _make_blockwise(b, hq, plan.sq, plan.sk, d, dv, q_chunk, k_chunk,
                           float(scale), causal, window, q_offset,
                           kv_len is not None, str(q.dtype))
    out = attn(q, k, v, kv_len)
    if plan.sq > sq:
        out = out[:, :, :sq]
    return _dist.shard_named(out, ("D", "T", "-", "-"))


def decode_softmax(logits, kv_len, logit_new=None):
    """Float32 softmax of one token's scores against a cache: ``logits``
    (B, ..., S) over the cached positions, those ``>= kv_len`` (B,) masked,
    and, given ``logit_new`` (B, ...), the token's score against its own key
    as one more position.  Returns (probabilities (B, ..., S), the token's
    own (B, ...) or None): the same numbers as writing the token's key at
    ``kv_len`` first and attending to ``kv_len + 1`` positions."""
    s = logits.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (kv_len.shape[0], s), 1)
    valid = (pos < kv_len[:, None]).reshape(
        (kv_len.shape[0],) + (1,) * (logits.ndim - 2) + (s,))
    logits = jnp.where(valid, logits.astype(jnp.float32), NEG_INF)
    if logit_new is None:
        return jax.nn.softmax(logits, axis=-1), None
    logit_new = logit_new.astype(jnp.float32)
    top = jnp.maximum(logits.max(axis=-1), logit_new)
    e = jnp.exp(logits - top[..., None])
    e_new = jnp.exp(logit_new - top)
    total = e.sum(axis=-1) + e_new
    return e / total[..., None], e_new / total


def decode_attention_xla(q, k, v, kv_len, *, new=None, scale=None):
    """One token against a cache in the cache's own layout, so no slab is
    transposed.  q: (B, Hq, D); k, v: (B, S, Hkv, D); the first ``kv_len``
    (B,) positions are attended.  ``new`` = (k, v) of the token itself,
    (B, Hkv, D) each, joins the same softmax (`decode_softmax`), so the
    caller can write it into the cache after reading it."""
    b, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qf = q.reshape(b, hkv, g, d) * scale
    logits = jnp.einsum("bhgd,bthd->bhgt", qf, k,
                        preferred_element_type=jnp.float32)
    logit_new = None if new is None else jnp.einsum(
        "bhgd,bhd->bhg", qf, new[0], preferred_element_type=jnp.float32)
    probs, p_new = decode_softmax(logits, kv_len, logit_new)
    out = jnp.einsum("bhgt,bthd->bhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    if new is not None:
        out += (p_new.astype(v.dtype).astype(jnp.float32)[..., None]
                * new[1][:, :, None, :].astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunked cross-entropy: never materialise the full (T, V) logits.
# ---------------------------------------------------------------------------
def chunked_softmax_xent(x, emb_out, labels, weights=None, chunk: int = 8192):
    """x: (T, D); emb_out: (V, D); labels: (T,) int32; weights: (T,) or None.
    Returns (sum_nll, sum_weight)."""
    t, d = x.shape
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk
    xc = x.reshape(n_chunks, chunk, d)
    lc = labels.reshape(n_chunks, chunk)
    wc = (weights.reshape(n_chunks, chunk) if weights is not None
          else jnp.ones_like(lc, jnp.float32))

    @jax.checkpoint
    def body(carry, inp):
        nll_sum, w_sum = carry
        xb, lb, wb = inp
        with jax.named_scope("head"):
            logits = jnp.einsum("td,vd->tv", xb, emb_out,
                                preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        nll = (lse - gold) * wb
        return (nll_sum + jnp.sum(nll), w_sum + jnp.sum(wb)), None

    (nll_sum, w_sum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, lc, wc))
    return nll_sum, w_sum


# ---------------------------------------------------------------------------
# Parameter init helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, in_axis: int = 0, dtype=jnp.bfloat16):
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


@dataclasses.dataclass
class KeyGen:
    key: jax.Array

    def __call__(self):
        self.key, sub = jax.random.split(self.key)
        return sub
