"""RecurrentGemma / Griffin: RG-LRU recurrent blocks + local attention, 2:1.

The linear recurrence h_t = a_t * h_{t-1} + b_t runs as a
``jax.lax.associative_scan`` (log-depth — this is what makes ``long_500k``
tractable) and as one fused step at decode.  The (R, R, A) layer pattern is
scanned per super-block, so compile size is one super-block body; the
trailing partial super-block (38 = 12*3 + 2 in the 9B config) is a second,
smaller scan.  Decode keeps a ring-buffer window cache for the local
attention layers — memory is O(window + lru_width), independent of context.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist import partition as _dist

from .common import (
    KeyGen, apply_rope, blockwise_attention, chunked_softmax_xent,
    decode_attention_xla, dense_init, rms_norm,
)
from .config import ArchConfig
from .transformer import _init_attention, _init_dense_ffn, _stack, ffn_dense

_C_RGLRU = 8.0


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_recurrent(kg: KeyGen, cfg: ArchConfig, dtype):
    d = cfg.d_model
    w = cfg.rglru.lru_width or d
    cw = cfg.rglru.conv_width
    return {
        "w_in": dense_init(kg(), (d, w), dtype=dtype),
        "w_gate_branch": dense_init(kg(), (d, w), dtype=dtype),
        "conv_w": dense_init(kg(), (cw, w), dtype=dtype),
        "conv_b": jnp.zeros((w,), dtype),
        "wi_gate": dense_init(kg(), (w, w), dtype=dtype),
        "wr_gate": dense_init(kg(), (w, w), dtype=dtype),
        "lambda_p": jnp.full((w,), 2.0, jnp.float32),
        "w_out": dense_init(kg(), (w, d), dtype=dtype),
    }


def _init_block(kg: KeyGen, cfg: ArchConfig, kind: str, dtype):
    p = {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "ffn": _init_dense_ffn(kg, cfg.d_model, cfg.d_ff, dtype),
    }
    if kind == "attn":
        p["attn"] = _init_attention(kg, cfg, dtype)
    else:
        p["rec"] = _init_recurrent(kg, cfg, dtype)
    return p


def init_params(cfg: ArchConfig, key):
    dtype = jnp.dtype(cfg.param_dtype)
    kg = KeyGen(key)
    pat = cfg.rglru.pattern
    n_super, n_tail = divmod(cfg.n_layers, len(pat))
    params = {
        "embed": dense_init(kg(), (cfg.vocab_padded, cfg.d_model),
                            in_axis=1, dtype=dtype),
        "ln_f": jnp.zeros((cfg.d_model,), dtype),
        "super": _stack(
            lambda g: {f"{kind}_{i}": _init_block(g, cfg, kind, dtype)
                       for i, kind in enumerate(pat)}, kg(), n_super),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(kg(), (cfg.vocab_padded, cfg.d_model),
                                       in_axis=1, dtype=dtype)
    if n_tail:
        params["tail"] = _stack(
            lambda g: {f"{pat[i]}_{i}": _init_block(g, cfg, pat[i], dtype)
                       for i in range(n_tail)}, kg(), 1)
    return params


# ---------------------------------------------------------------------------
# RG-LRU primitive
# ---------------------------------------------------------------------------
def _rglru_coeffs(p, x):
    xf = x.astype(jnp.float32)
    i_gate = jax.nn.sigmoid(jnp.einsum(
        "...w,wk->...k", x, p["wi_gate"]).astype(jnp.float32))
    r_gate = jax.nn.sigmoid(jnp.einsum(
        "...w,wk->...k", x, p["wr_gate"]).astype(jnp.float32))
    log_a = -_C_RGLRU * jax.nn.softplus(p["lambda_p"]) * r_gate
    a = jnp.exp(log_a)
    norm = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    return a, norm * (i_gate * xf)


def rglru_seq(p, x, h0=None):
    """x: (B, S, W) -> (y (B,S,W), final state (B,W) f32)."""
    a, b = _rglru_coeffs(p, x)
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0)

    def op(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(op, (a, b), axis=1)
    return h.astype(x.dtype), h[:, -1]


def rglru_step(p, x, h_prev):
    a, b = _rglru_coeffs(p, x)
    h = a * h_prev + b
    return h.astype(x.dtype), h


def _conv1d_seq(p, x, conv_width: int):
    out = x * p["conv_w"][-1]
    for i in range(1, conv_width):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, : x.shape[1], :]
        out = out + shifted * p["conv_w"][conv_width - 1 - i]
    return out + p["conv_b"]


def _conv1d_step(p, x, buf):
    window = jnp.concatenate([buf, x[:, None, :]], axis=1)  # (B, cw, W)
    out = jnp.einsum("bcw,cw->bw", window, p["conv_w"]) + p["conv_b"]
    return out, window[:, 1:, :]


def recurrent_block_seq(p, x):
    """Returns (y, (final_state f32, conv_tail))."""
    gate = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, p["w_gate_branch"])
                       .astype(jnp.float32)).astype(x.dtype)
    h_in = jnp.einsum("bsd,dw->bsw", x, p["w_in"])
    h = _conv1d_seq(p, h_in, p["conv_w"].shape[0])
    h, final_state = rglru_seq(p, h)
    conv_tail = h_in[:, -(p["conv_w"].shape[0] - 1):, :]
    return jnp.einsum("bsw,wd->bsd", h * gate, p["w_out"]), \
        (final_state, conv_tail)


def recurrent_block_step(p, x, state, conv_buf):
    gate = jax.nn.gelu((x @ p["w_gate_branch"]).astype(jnp.float32)
                       ).astype(x.dtype)
    h = x @ p["w_in"]
    h, conv_buf = _conv1d_step(p, h, conv_buf)
    h, state = rglru_step(p, h, state)
    return (h * gate) @ p["w_out"], state, conv_buf


# ---------------------------------------------------------------------------
# Sequence blocks
# ---------------------------------------------------------------------------
def _attn_seq(bp, x, positions, cfg: ArchConfig):
    b, s, _ = x.shape
    hh, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    p = bp["attn"]
    q = jnp.einsum("bsd,dk->bsk", x, p["wq"]).reshape(b, s, hh, dh)
    k = jnp.einsum("bsd,dk->bsk", x, p["wk"]).reshape(b, s, hkv, dh)
    v = jnp.einsum("bsd,dk->bsk", x, p["wv"]).reshape(b, s, hkv, dh)
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    y = blockwise_attention(q, k, v, causal=True, window=cfg.rglru.window,
                            q_chunk=cfg.attn_q_chunk,
                            k_chunk=cfg.attn_k_chunk)
    y = y.transpose(0, 2, 1, 3).reshape(b, s, hh * dh)
    return jnp.einsum("bsk,kd->bsd", y, p["wo"]), (k, v)


def _block_seq(bp, kind, x, positions, cfg: ArchConfig):
    h = rms_norm(x, bp["ln1"], cfg.norm_eps)
    if kind == "attn":
        y, cache_out = _attn_seq(bp, h, positions, cfg)
    else:
        y, cache_out = recurrent_block_seq(bp["rec"], h)
    x = x + y
    h = rms_norm(x, bp["ln2"], cfg.norm_eps)
    return x + ffn_dense(bp["ffn"], h), cache_out


def _super_body(sp, x, positions, cfg: ArchConfig, pat):
    caches = []
    for i, kind in enumerate(pat):
        key = f"{kind}_{i}"
        if key not in sp:
            continue
        x, c = _block_seq(sp[key], kind, x, positions, cfg)
        caches.append((kind, c))
    return x, caches


def _scan_stack(params_stack, x, positions, cfg, pat, remat):
    def body(x, sp):
        x = _dist.shard_activation(x)
        x, caches = _super_body(sp, x, positions, cfg, pat)
        # split attention / recurrent cache outputs into homogeneous tuples
        attn_c = tuple(c for kd, c in caches if kd == "attn")
        rec_c = tuple(c for kd, c in caches if kd == "rglru")
        return x, (attn_c, rec_c)

    if remat:
        body = jax.checkpoint(body)
    return jax.lax.scan(body, x, params_stack)


def forward(params, cfg: ArchConfig, batch, collect_cache: bool = False):
    x = params["embed"][batch["tokens"]]
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    pat = cfg.rglru.pattern
    x, caches = _scan_stack(params["super"], x, positions, cfg, pat,
                            cfg.remat)
    tail_caches = None
    if "tail" in params:
        n_tail = cfg.n_layers % len(pat)
        x, tail_caches = _scan_stack(params["tail"], x, positions, cfg,
                                     pat[:n_tail], cfg.remat)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    if collect_cache:
        return x, (caches, tail_caches)
    return x


def loss_fn(params, cfg: ArchConfig, batch):
    hidden = forward(params, cfg, batch)
    b, s, d = hidden.shape
    unembed = params.get("unembed", params["embed"])
    nll, denom = chunked_softmax_xent(
        hidden.reshape(b * s, d), unembed, batch["labels"].reshape(b * s),
        None, chunk=cfg.loss_chunk)
    loss = nll / jnp.maximum(denom, 1.0)
    return loss, {"nll": loss}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------
def _counts(cfg: ArchConfig):
    pat = cfg.rglru.pattern
    n_super, n_tail = divmod(cfg.n_layers, len(pat))
    apb = sum(1 for kd in pat if kd == "attn")
    rpb = len(pat) - apb
    tail_a = sum(1 for kd in pat[:n_tail] if kd == "attn")
    tail_r = n_tail - tail_a
    return n_super, apb, rpb, tail_a, tail_r


def init_cache(cfg: ArchConfig, batch_size: int, max_seq: int,
               dtype=jnp.bfloat16):
    n_super, apb, rpb, tail_a, tail_r = _counts(cfg)
    w = cfg.rglru.lru_width or cfg.d_model
    window = min(cfg.rglru.window, max_seq)
    dh, hkv = cfg.head_dim_, cfg.n_kv_heads
    return {
        "k": jnp.zeros((n_super * apb + tail_a, batch_size, window, hkv, dh),
                       dtype),
        "v": jnp.zeros((n_super * apb + tail_a, batch_size, window, hkv, dh),
                       dtype),
        "state": jnp.zeros((n_super * rpb + tail_r, batch_size, w),
                           jnp.float32),
        "conv": jnp.zeros((n_super * rpb + tail_r, batch_size,
                           cfg.rglru.conv_width - 1, w), dtype),
        "len": jnp.zeros((batch_size,), jnp.int32),
    }


def prefill(params, cfg: ArchConfig, batch, max_seq: int):
    hidden, (caches, tail_caches) = forward(params, cfg, batch,
                                            collect_cache=True)
    b, s, d = hidden.shape
    unembed = params.get("unembed", params["embed"])
    logits = jnp.einsum("bd,vd->bv", hidden[:, -1], unembed,
                        preferred_element_type=jnp.float32)
    window = min(cfg.rglru.window, max_seq)

    def ring(k):  # (N, B, Hkv, S, dh) -> windowed ring layout (N,B,win,Hkv,dh)
        if s < window:  # slots [0, s) filled in order; next write at s
            kw = jnp.pad(k, ((0, 0),) * 3 + ((0, window - s), (0, 0)))
            return kw.transpose(0, 1, 3, 2, 4)
        kw = k[:, :, :, -window:, :].transpose(0, 1, 3, 2, 4)
        return jnp.roll(kw, s % window, axis=2)

    def flat(groups):
        """(n_super, per_block, ...) scan output -> (n_super*per_block, ...)"""
        if not groups:
            return None
        stacked = jnp.stack(groups, axis=1) if isinstance(groups, tuple) \
            else groups
        return stacked.reshape((-1,) + stacked.shape[2:])

    attn_c, rec_c = caches
    parts = {"k": [], "v": [], "state": [], "conv": []}
    if attn_c:
        ks = jnp.stack([c[0] for c in attn_c], axis=1)  # (S?,apb,B,hkv,s,dh)
        vs = jnp.stack([c[1] for c in attn_c], axis=1)
        parts["k"].append(ring(ks.reshape((-1,) + ks.shape[2:])))
        parts["v"].append(ring(vs.reshape((-1,) + vs.shape[2:])))
    if rec_c:
        st = jnp.stack([c[0] for c in rec_c], axis=1)
        cv = jnp.stack([c[1] for c in rec_c], axis=1)
        parts["state"].append(st.reshape((-1,) + st.shape[2:]))
        parts["conv"].append(cv.reshape((-1,) + cv.shape[2:]))
    if tail_caches is not None:
        t_attn, t_rec = tail_caches
        if t_attn:
            ks = jnp.stack([c[0] for c in t_attn], axis=1)
            vs = jnp.stack([c[1] for c in t_attn], axis=1)
            parts["k"].append(ring(ks.reshape((-1,) + ks.shape[2:])))
            parts["v"].append(ring(vs.reshape((-1,) + vs.shape[2:])))
        if t_rec:
            st = jnp.stack([c[0] for c in t_rec], axis=1)
            cv = jnp.stack([c[1] for c in t_rec], axis=1)
            parts["state"].append(st.reshape((-1,) + st.shape[2:]))
            parts["conv"].append(cv.reshape((-1,) + cv.shape[2:]))
    cache = {
        "k": jnp.concatenate(parts["k"], 0),
        "v": jnp.concatenate(parts["v"], 0),
        "state": jnp.concatenate(parts["state"], 0),
        "conv": jnp.concatenate(parts["conv"], 0),
        "len": jnp.full((b,), s, jnp.int32),
    }
    return logits, cache


def _decode_block(bp, kind, x, cache_rows, kv_len, window, cfg: ArchConfig):
    h = rms_norm(x, bp["ln1"], cfg.norm_eps)
    if kind == "attn":
        ck, cv = cache_rows
        p = bp["attn"]
        b = x.shape[0]
        hh, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
        slot = kv_len % window
        q = (h @ p["wq"]).reshape(b, hh, dh)
        k = (h @ p["wk"]).reshape(b, hkv, dh)
        v = (h @ p["wv"]).reshape(b, hkv, dh)
        q = apply_rope(q[:, :, None, :], kv_len[:, None],
                       cfg.rope_theta)[:, :, 0, :]
        k = apply_rope(k[:, :, None, :], kv_len[:, None],
                       cfg.rope_theta)[:, :, 0, :]
        upd = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
            c, n[None], i, axis=0))
        ck, cv = upd(ck, k, slot), upd(cv, v, slot)
        n_valid = jnp.minimum(kv_len + 1, window)
        y = decode_attention_xla(q, ck, cv, n_valid)
        y = (y.reshape(b, hh * dh)) @ p["wo"]
        new_rows = (ck, cv)
    else:
        st, cb = cache_rows
        y, st, cb = recurrent_block_step(bp["rec"], h, st, cb)
        new_rows = (st, cb)
    x = x + y
    h = rms_norm(x, bp["ln2"], cfg.norm_eps)
    return x + ffn_dense(bp["ffn"], h), new_rows


def decode_step(params, cfg: ArchConfig, cache, tokens, positions=None):
    x = params["embed"][tokens]
    kv_len = cache["len"]
    pat = cfg.rglru.pattern
    n_super, apb, rpb, tail_a, tail_r = _counts(cfg)
    window = cache["k"].shape[2]

    def regroup(arr, n_blocks, per):
        return arr[: n_blocks * per].reshape((n_blocks, per) + arr.shape[1:])

    xs = (params["super"],
          regroup(cache["k"], n_super, apb), regroup(cache["v"], n_super, apb),
          regroup(cache["state"], n_super, rpb),
          regroup(cache["conv"], n_super, rpb))

    def body(x, xs_sb):
        sp, ck, cv, st, cb = xs_sb
        x = _dist.shard_activation(x)
        ai = ri = 0
        new_k, new_v, new_s, new_c = [], [], [], []
        for i, kind in enumerate(pat):
            rows = ((ck[ai], cv[ai]) if kind == "attn"
                    else (st[ri], cb[ri]))
            x2, new_rows = _decode_block(sp[f"{kind}_{i}"], kind, x, rows,
                                         kv_len, window, cfg)
            x = x2
            if kind == "attn":
                new_k.append(new_rows[0])
                new_v.append(new_rows[1])
                ai += 1
            else:
                new_s.append(new_rows[0])
                new_c.append(new_rows[1])
                ri += 1
        return x, (jnp.stack(new_k), jnp.stack(new_v),
                   jnp.stack(new_s), jnp.stack(new_c))

    x, (nk, nv, ns, nc) = jax.lax.scan(body, x, xs)
    nk = nk.reshape((-1,) + nk.shape[2:])
    nv = nv.reshape((-1,) + nv.shape[2:])
    ns = ns.reshape((-1,) + ns.shape[2:])
    nc = nc.reshape((-1,) + nc.shape[2:])

    if "tail" in params:
        tp = jax.tree.map(lambda a: a[0], params["tail"])
        ai, ri = n_super * apb, n_super * rpb
        tail_k, tail_v, tail_s, tail_c = [], [], [], []
        n_tail = cfg.n_layers % len(pat)
        for i in range(n_tail):
            kind = pat[i]
            rows = ((cache["k"][ai], cache["v"][ai]) if kind == "attn"
                    else (cache["state"][ri], cache["conv"][ri]))
            x, new_rows = _decode_block(tp[f"{kind}_{i}"], kind, x, rows,
                                        kv_len, window, cfg)
            if kind == "attn":
                tail_k.append(new_rows[0])
                tail_v.append(new_rows[1])
                ai += 1
            else:
                tail_s.append(new_rows[0])
                tail_c.append(new_rows[1])
                ri += 1
        if tail_k:
            nk = jnp.concatenate([nk, jnp.stack(tail_k)], 0)
            nv = jnp.concatenate([nv, jnp.stack(tail_v)], 0)
        if tail_s:
            ns = jnp.concatenate([ns, jnp.stack(tail_s)], 0)
            nc = jnp.concatenate([nc, jnp.stack(tail_c)], 0)

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    unembed = params.get("unembed", params["embed"])
    logits = jnp.einsum("bd,vd->bv", x, unembed,
                        preferred_element_type=jnp.float32)
    return logits, {"k": nk, "v": nv, "state": ns, "conv": nc,
                    "len": kv_len + 1}
