"""Decoder/encoder transformer covering the dense, MoE and MLA families
(qwen1.5/2.5/3, stablelm, command-r+, qwen2-vl, hubert, deepseek-v2).

Layers are stacked and scanned (compile time independent of depth); each
layer body is optionally rematerialised.  Attention is the FLOP-exact
blockwise formulation from ``common.py``; MLA decode uses the absorbed
matmul identity so the latent cache is never expanded to per-head keys.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist import partition as _dist

from .common import (
    KeyGen, apply_mrope, apply_rope, blockwise_attention, chunked_softmax_xent,
    decode_attention_xla, decode_softmax, dense_init, rms_norm,
)
from .config import ArchConfig
from .moe import init_moe_ffn, moe_ffn


# ---------------------------------------------------------------------------
# Parameter initialisation
# ---------------------------------------------------------------------------
def _init_attention(kg: KeyGen, cfg: ArchConfig, dtype):
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    if cfg.family == "mla":
        m = cfg.mla
        return {
            "wq_a": dense_init(kg(), (d, m.q_lora), dtype=dtype),
            "q_ln": jnp.zeros((m.q_lora,), dtype),
            "wq_b": dense_init(kg(), (m.q_lora, h * (m.d_nope + m.d_rope)),
                               dtype=dtype),
            "wkv_a": dense_init(kg(), (d, m.kv_lora + m.d_rope), dtype=dtype),
            "kv_ln": jnp.zeros((m.kv_lora,), dtype),
            "wk_b": dense_init(kg(), (m.kv_lora, h * m.d_nope), dtype=dtype),
            "wv_b": dense_init(kg(), (m.kv_lora, h * m.v_head_dim), dtype=dtype),
            "wo": dense_init(kg(), (h * m.v_head_dim, d), dtype=dtype),
        }
    p = {
        "wq": dense_init(kg(), (d, h * dh), dtype=dtype),
        "wk": dense_init(kg(), (d, hkv * dh), dtype=dtype),
        "wv": dense_init(kg(), (d, hkv * dh), dtype=dtype),
        "wo": dense_init(kg(), (h * dh, d), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    return p


def _init_dense_ffn(kg: KeyGen, d: int, f: int, dtype):
    return {
        "w_gate": dense_init(kg(), (d, f), dtype=dtype),
        "w_up": dense_init(kg(), (d, f), dtype=dtype),
        "w_down": dense_init(kg(), (f, d), dtype=dtype),
    }


def _init_layer(kg: KeyGen, cfg: ArchConfig, dtype, *, moe_layer: bool):
    p = {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "attn": _init_attention(kg, cfg, dtype),
    }
    if moe_layer:
        p["moe"] = init_moe_ffn(kg, cfg.d_model, cfg.moe, dtype)
    else:
        f = (cfg.moe.d_ff_dense if (cfg.moe and cfg.moe.n_dense_layers)
             else cfg.d_ff)
        p["ffn"] = _init_dense_ffn(kg, cfg.d_model, f, dtype)
    return p


def _stack(init_one, key, n: int):
    """``n`` layers of ``init_one(KeyGen)``, stacked on a leading axis by one
    vmapped init: tracing ``n`` copies compiles slowly at published depth."""
    return jax.vmap(lambda k: init_one(KeyGen(k)))(jax.random.split(key, n))


def init_params(cfg: ArchConfig, key):
    dtype = jnp.dtype(cfg.param_dtype)
    kg = KeyGen(key)
    vp = cfg.vocab_padded
    params = {
        "embed": dense_init(kg(), (vp, cfg.d_model), in_axis=1, dtype=dtype),
        "ln_f": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(kg(), (vp, cfg.d_model), in_axis=1,
                                       dtype=dtype)
    n_dense = cfg.moe.n_dense_layers if cfg.moe else 0
    is_moe = cfg.moe is not None
    if n_dense:
        params["dense_layers"] = _stack(
            lambda g: _init_layer(g, cfg, dtype, moe_layer=False),
            kg(), n_dense)
    params["layers"] = _stack(
        lambda g: _init_layer(g, cfg, dtype, moe_layer=is_moe),
        kg(), cfg.n_layers - n_dense)
    return params


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def _rope(cfg: ArchConfig, x, positions):
    if cfg.mrope:
        return apply_mrope(x, positions, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_theta)


def _split_heads(x, h):
    b, s, hd = x.shape
    return x.reshape(b, s, h, hd // h).transpose(0, 2, 1, 3)   # (B,H,S,dh)


def attention_seq(p, x, positions, cfg: ArchConfig, *, kv_len=None):
    """Full-sequence attention (train / prefill).  Returns (y, (k, v))."""
    b, s, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    if cfg.family == "mla":
        return _mla_seq(p, x, positions, cfg, kv_len=kv_len)
    q = jnp.einsum("bsd,dk->bsk", x, p["wq"])
    k = jnp.einsum("bsd,dk->bsk", x, p["wk"])
    v = jnp.einsum("bsd,dk->bsk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q, k, v = _split_heads(q, h), _split_heads(k, hkv), _split_heads(v, hkv)
    q = _rope(cfg, q, positions)
    k = _rope(cfg, k, positions)
    y = blockwise_attention(
        q, k, v, causal=not cfg.encoder_only, kv_len=kv_len,
        q_chunk=cfg.attn_q_chunk, k_chunk=cfg.attn_k_chunk)
    y = y.transpose(0, 2, 1, 3).reshape(b, s, h * dh)
    return jnp.einsum("bsk,kd->bsd", y, p["wo"]), (k, v)


def attention_decode(p, x, positions, cfg: ArchConfig, cache_k, cache_v,
                     kv_len):
    """x: (B, D) one token at position kv_len; cache_k/v: (B, Smax, Hkv, dh),
    read only.  The token attends to the cached [0, kv_len) and to itself.
    Returns (y, k, v): its own K/V (B, Hkv, dh), for the caller to write at
    kv_len."""
    b, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = (x @ p["wq"])
    k = (x @ p["wk"])
    v = (x @ p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, h, dh)
    k = k.reshape(b, hkv, dh)
    v = v.reshape(b, hkv, dh)
    pos = positions if positions.ndim else positions[None]
    q = _rope(cfg, q[:, :, None, :], pos[..., None] if cfg.mrope
              else pos[:, None])[:, :, 0, :]
    k = _rope(cfg, k[:, :, None, :], pos[..., None] if cfg.mrope
              else pos[:, None])[:, :, 0, :]
    k, v = k.astype(cache_k.dtype), v.astype(cache_v.dtype)  # as stored
    y = decode_attention_xla(q, cache_k, cache_v, kv_len, new=(k, v))
    y = y.reshape(b, h * dh)
    return y @ p["wo"], k, v


# ---------------------------------------------------------------------------
# MLA (deepseek-v2)
# ---------------------------------------------------------------------------
def _mla_q(p, x, positions, cfg):
    m = cfg.mla
    h = cfg.n_heads
    ql = rms_norm(jnp.einsum("...d,dk->...k", x, p["wq_a"]), p["q_ln"],
                  cfg.norm_eps)
    q = jnp.einsum("...k,kh->...h", ql, p["wq_b"])
    if x.ndim == 3:
        b, s, _ = x.shape
        q = q.reshape(b, s, h, m.d_nope + m.d_rope).transpose(0, 2, 1, 3)
        qn, qr = q[..., :m.d_nope], q[..., m.d_nope:]
        qr = apply_rope(qr, positions, cfg.rope_theta)
    else:
        b, _ = x.shape
        q = q.reshape(b, h, m.d_nope + m.d_rope)
        qn, qr = q[..., :m.d_nope], q[..., m.d_nope:]
        qr = apply_rope(qr[:, :, None, :], positions[:, None],
                        cfg.rope_theta)[:, :, 0, :]
    return qn, qr


def _mla_seq(p, x, positions, cfg: ArchConfig, kv_len=None):
    b, s, d = x.shape
    m, h = cfg.mla, cfg.n_heads
    qn, qr = _mla_q(p, x, positions, cfg)                    # (B,H,S,*)
    kv = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"])
    ckv = rms_norm(kv[..., :m.kv_lora], p["kv_ln"], cfg.norm_eps)
    kr = kv[..., m.kv_lora:]                                 # (B,S,dr)
    kr = apply_rope(kr[:, None], positions, cfg.rope_theta)  # (B,1,S,dr)
    kn = jnp.einsum("bsk,kh->bsh", ckv, p["wk_b"]).reshape(
        b, s, h, m.d_nope).transpose(0, 2, 1, 3)
    v = jnp.einsum("bsk,kh->bsh", ckv, p["wv_b"]).reshape(
        b, s, h, m.v_head_dim).transpose(0, 2, 1, 3)
    q = jnp.concatenate([qn, qr], axis=-1)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, (b, h, s, m.d_rope))],
                        axis=-1)
    y = blockwise_attention(q, k, v, causal=True, kv_len=kv_len,
                            q_chunk=cfg.attn_q_chunk,
                            k_chunk=cfg.attn_k_chunk)
    y = y.transpose(0, 2, 1, 3).reshape(b, s, h * m.v_head_dim)
    # cache = the rope'd shared key + normalised latent, per token
    return jnp.einsum("bsk,kd->bsd", y, p["wo"]), (ckv, kr[:, 0])


def mla_decode(p, x, positions, cfg: ArchConfig, cache_ckv, cache_kr, kv_len):
    """Absorbed-matmul MLA decode: the latent cache is attended directly.
    x: (B, D); cache_ckv: (B, Smax, kv_lora); cache_kr: (B, Smax, d_rope),
    read only: as `attention_decode`, the token attends to the cached
    [0, kv_len) and to itself, and its (ckv, kr) are returned for the caller
    to write at kv_len."""
    b, d = x.shape
    m, h = cfg.mla, cfg.n_heads
    qn, qr = _mla_q(p, x, positions, cfg)                    # (B,H,dn),(B,H,dr)
    kv = x @ p["wkv_a"]
    ckv = rms_norm(kv[..., :m.kv_lora], p["kv_ln"], cfg.norm_eps)  # (B,Lr)
    kr = apply_rope(kv[..., m.kv_lora:][:, None, None, :],
                    positions[:, None], cfg.rope_theta)[:, 0, 0, :]
    ckv, kr = ckv.astype(cache_ckv.dtype), kr.astype(cache_kr.dtype)

    wk_b = p["wk_b"].reshape(m.kv_lora, h, m.d_nope)
    q_lat = jnp.einsum("bhn,lhn->bhl", qn, wk_b)             # absorb W_uk
    scale = 1.0 / jnp.sqrt(jnp.float32(m.d_nope + m.d_rope))
    logits = (jnp.einsum("bhl,bsl->bhs", q_lat, cache_ckv)
              + jnp.einsum("bhr,bsr->bhs", qr, cache_kr)) * scale
    logit_new = (jnp.einsum("bhl,bl->bh", q_lat, ckv)
                 + jnp.einsum("bhr,br->bh", qr, kr)) * scale
    probs, p_new = decode_softmax(logits, kv_len, logit_new)
    dtype = cache_ckv.dtype
    latent = (jnp.einsum("bhs,bsl->bhl", probs.astype(dtype), cache_ckv,
                         preferred_element_type=jnp.float32)
              + p_new.astype(dtype).astype(jnp.float32)[..., None]
              * ckv[:, None, :].astype(jnp.float32)).astype(dtype)
    wv_b = p["wv_b"].reshape(m.kv_lora, h, m.v_head_dim)
    y = jnp.einsum("bhl,lhv->bhv", latent, wv_b)             # absorb W_uv
    y = y.reshape(b, h * m.v_head_dim)
    return y @ p["wo"], ckv, kr


# ---------------------------------------------------------------------------
# FFN + layer bodies
# ---------------------------------------------------------------------------
def ffn_dense(p, x):
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    return jnp.einsum("...f,fd->...d",
                      jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u,
                      p["w_down"])


def _layer_seq(lp, x, positions, cfg: ArchConfig, kv_len=None):
    with jax.named_scope("attention"):
        y, kv = attention_seq(lp["attn"],
                              rms_norm(x, lp["ln1"], cfg.norm_eps),
                              positions, cfg, kv_len=kv_len)
    x = x + y
    with jax.named_scope("ffn"):
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        if "moe" in lp:
            b, s, d = h.shape
            y, aux = moe_ffn(lp["moe"], h.reshape(b * s, d), cfg.moe,
                             norm_topk=cfg.moe.n_shared == 0)
            y = y.reshape(b, s, d)
        else:
            y, aux = ffn_dense(lp["ffn"], h), {
                "moe_aux": jnp.zeros((), jnp.float32),
                "moe_z": jnp.zeros((), jnp.float32)}
    return x + y, aux, kv


def _layer_decode(lp, x, positions, cfg: ArchConfig, cache, kv_len):
    """One layer for one token; ``cache`` is the layer's two cache leaves,
    read only.  Returns (x, the token's own entries for those leaves)."""
    with jax.named_scope("attention"):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        decode = mla_decode if cfg.family == "mla" else attention_decode
        y, c0, c1 = decode(lp["attn"], h, positions, cfg, cache[0], cache[1],
                           kv_len)
    x = x + y
    with jax.named_scope("ffn"):
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        if "moe" in lp:
            y, _ = moe_ffn(lp["moe"], h, cfg.moe,
                           norm_topk=cfg.moe.n_shared == 0)
        else:
            y = ffn_dense(lp["ffn"], h)
    return x + y, (c0, c1)


# ---------------------------------------------------------------------------
# Full model: forward / prefill / decode
# ---------------------------------------------------------------------------
def _embed_in(params, cfg: ArchConfig, batch):
    if cfg.inputs == "embeddings":
        return batch["embeds"]
    with jax.named_scope("embed"):
        return params["embed"][batch["tokens"]]


def _positions(cfg: ArchConfig, batch, b, s):
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.mrope:
        pos = jnp.broadcast_to(pos, (3, b, s))
    return pos


def forward(params, cfg: ArchConfig, batch):
    """Returns (hidden (B,S,D), aux dict of scalars, kv caches (L,...))."""
    x = _embed_in(params, cfg, batch)
    b, s, _ = x.shape
    positions = _positions(cfg, batch, b, s)

    def body(lp, x):
        return _layer_seq(lp, x, positions, cfg)

    if cfg.remat:
        body = jax.checkpoint(body)

    aux0 = {"moe_aux": jnp.zeros((), jnp.float32),
            "moe_z": jnp.zeros((), jnp.float32)}

    def scan_fn(carry, lp):
        x, aux = carry
        x = _dist.shard_activation(x)
        x, aux2, kv = body(lp, x)
        return (x, jax.tree.map(jnp.add, aux, aux2)), kv

    carry = (x, aux0)
    kvs = []
    if "dense_layers" in params:
        carry, kv_d = jax.lax.scan(scan_fn, carry, params["dense_layers"])
        kvs.append(kv_d)
    carry, kv_m = jax.lax.scan(scan_fn, carry, params["layers"])
    kvs.append(kv_m)
    x, aux = carry
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    if len(kvs) == 1:
        kv = kvs[0]
    else:
        kv = jax.tree.map(lambda a, b_: jnp.concatenate([a, b_], axis=0),
                          kvs[0], kvs[1])
    return x, aux, kv


def loss_fn(params, cfg: ArchConfig, batch):
    hidden, aux, _ = forward(params, cfg, batch)
    b, s, d = hidden.shape
    unembed = params.get("unembed", params["embed"])
    labels = batch["labels"].reshape(b * s)
    weights = batch.get("loss_weights")
    if weights is not None:
        weights = weights.reshape(b * s)
    with jax.named_scope("loss"):           # holds the head's "head" scope
        nll, denom = chunked_softmax_xent(
            hidden.reshape(b * s, d), unembed, labels, weights,
            chunk=cfg.loss_chunk)
    loss = nll / jnp.maximum(denom, 1.0)
    total = loss + 1e-2 * aux["moe_aux"] + 1e-3 * aux["moe_z"]
    return total, {"nll": loss, "moe_aux": aux["moe_aux"],
                   "moe_z": aux["moe_z"]}


def init_cache(cfg: ArchConfig, batch_size: int, max_seq: int,
               dtype=jnp.bfloat16):
    l = cfg.n_layers
    if cfg.family == "mla":
        m = cfg.mla
        return {
            "ckv": jnp.zeros((l, batch_size, max_seq, m.kv_lora), dtype),
            "kr": jnp.zeros((l, batch_size, max_seq, m.d_rope), dtype),
            "len": jnp.zeros((batch_size,), jnp.int32),
        }
    dh = cfg.head_dim_
    return {
        "k": jnp.zeros((l, batch_size, max_seq, cfg.n_kv_heads, dh), dtype),
        "v": jnp.zeros((l, batch_size, max_seq, cfg.n_kv_heads, dh), dtype),
        "len": jnp.zeros((batch_size,), jnp.int32),
    }


def prefill(params, cfg: ArchConfig, batch, max_seq: int):
    """Full-sequence forward that also builds the KV cache."""
    hidden, _, kv = forward(params, cfg, batch)
    b, s, d = hidden.shape
    unembed = params.get("unembed", params["embed"])
    last = hidden[:, -1, :]
    with jax.named_scope("head"):
        logits = jnp.einsum("bd,vd->bv", last, unembed,
                            preferred_element_type=jnp.float32)
    if cfg.encoder_only:
        return logits, None
    pad = max_seq - s
    with jax.named_scope("kv_cache"):
        if cfg.family == "mla":
            ckv, kr = kv
            cache = {
                "ckv": jnp.pad(ckv, ((0, 0), (0, 0), (0, pad), (0, 0))),
                "kr": jnp.pad(kr, ((0, 0), (0, 0), (0, pad), (0, 0))),
                "len": jnp.full((b,), s, jnp.int32),
            }
        else:
            k, v = kv  # (L, B, Hkv, S, dh) -> (L, B, S, Hkv, dh)
            k = jnp.pad(k.transpose(0, 1, 3, 2, 4),
                        ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v.transpose(0, 1, 3, 2, 4),
                        ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            cache = {"k": k, "v": v, "len": jnp.full((b,), s, jnp.int32)}
    return logits, cache


def decode_step(params, cfg: ArchConfig, cache, tokens, positions=None):
    """One decode step.  tokens: (B,) int32 (or embeds (B, D)).
    Returns (logits (B, V), new cache).

    Each layer reads its cache slab where it lies; the new token's K/V are
    written at (layer, slot, kv_len) after the layer scan, so a donated cache
    is updated in place and never copied whole."""
    if cfg.inputs == "embeddings" and tokens.ndim == 2:
        x = tokens
    else:
        with jax.named_scope("embed"):
            x = params["embed"][tokens]
    kv_len = cache["len"]
    b = x.shape[0]
    if positions is None:
        positions = kv_len
        if cfg.mrope:  # text continuation: t advances, h/w stay 0
            positions = jnp.stack([kv_len, kv_len * 0, kv_len * 0], 0)
    names = ("ckv", "kr") if cfg.family == "mla" else ("k", "v")
    c0, c1 = cache[names[0]], cache[names[1]]

    def scan_fn(x, lp_and_layer):
        lp, layer = lp_and_layer
        x = _dist.shard_activation(x)
        rows = (jax.lax.dynamic_index_in_dim(c0, layer, keepdims=False),
                jax.lax.dynamic_index_in_dim(c1, layer, keepdims=False))
        return _layer_decode(lp, x, positions, cfg, rows, kv_len)

    stacks = [params[k] for k in ("dense_layers", "layers") if k in params]
    new, first = [], 0
    for stack in stacks:
        n = jax.tree.leaves(stack)[0].shape[0]
        x, nc = jax.lax.scan(scan_fn, x, (stack, jnp.arange(first, first + n)))
        new.append(nc)
        first += n
    n0, n1 = (jnp.concatenate(a, 0) for a in zip(*new))   # (L, B, ...)

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    unembed = params.get("unembed", params["embed"])
    with jax.named_scope("head"):
        logits = jnp.einsum("bd,vd->bv", x, unembed,
                            preferred_element_type=jnp.float32)
    with jax.named_scope("kv_cache"):
        slots = jnp.arange(b)
        new_cache = {names[0]: c0.at[:, slots, kv_len].set(n0),
                     names[1]: c1.at[:, slots, kv_len].set(n1),
                     "len": kv_len + 1}
    return logits, new_cache
