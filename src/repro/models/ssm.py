"""Mamba-2 (SSD, state-space duality) — attention-free LM family.

Train/prefill use the chunked SSD algorithm in pure JAX (intra-chunk
quadratic masked-decay matmuls + a small carried inter-chunk state), the
same decomposition the Pallas kernel in ``repro.kernels.ssd`` implements
for real TPUs.  Decode is a single fused recurrence step — O(1) per token,
which is why this family runs the ``long_500k`` cell.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist import partition as _dist

from .common import KeyGen, chunked_softmax_xent, dense_init, rms_norm
from .config import ArchConfig


def _dims(cfg: ArchConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    n_heads = d_in // s.head_dim
    conv_ch = d_in + 2 * s.d_state
    return d_in, n_heads, conv_ch


def _init_layer(kg: KeyGen, cfg: ArchConfig, dtype):
    d = cfg.d_model
    s = cfg.ssm
    d_in, h, conv_ch = _dims(cfg)
    return {
        "ln": jnp.zeros((d,), dtype),
        "w_in": dense_init(kg(), (d, 2 * d_in + 2 * s.d_state + h),
                           dtype=dtype),
        "conv_w": dense_init(kg(), (s.d_conv, conv_ch), dtype=dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32)),
        "d_skip": jnp.ones((h,), jnp.float32),
        "gn": jnp.zeros((d_in,), dtype),      # gated RMSNorm scale
        "w_out": dense_init(kg(), (d_in, d), dtype=dtype),
    }


def init_params(cfg: ArchConfig, key):
    dtype = jnp.dtype(cfg.param_dtype)
    kg = KeyGen(key)
    from .transformer import _stack
    params = {
        "embed": dense_init(kg(), (cfg.vocab_padded, cfg.d_model),
                            in_axis=1, dtype=dtype),
        "ln_f": jnp.zeros((cfg.d_model,), dtype),
        "layers": _stack(lambda g: _init_layer(g, cfg, dtype), kg(),
                         cfg.n_layers),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(kg(), (cfg.vocab_padded, cfg.d_model),
                                       in_axis=1, dtype=dtype)
    return params


# ---------------------------------------------------------------------------
# Chunked SSD (jnp)
# ---------------------------------------------------------------------------
def ssd_chunked(x, dt, a, b, c, chunk: int, state0=None):
    """x: (B,S,H,P); dt: (B,S,H) (already softplus'd); a: (H,) negative;
    b, c: (B,S,N).  Returns (y (B,S,H,P), final_state (B,H,N,P) f32)."""
    import math
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    chunk = math.gcd(min(chunk, s), s)   # largest dividing chunk
    nc = s // chunk

    xf = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]
    la = dt.astype(jnp.float32) * a[None, None, :]      # (B,S,H) log-decay
    xs = xf.reshape(bsz, nc, chunk, h, p)
    las = la.reshape(bsz, nc, chunk, h)
    bs = b.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    cs = c.astype(jnp.float32).reshape(bsz, nc, chunk, n)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = jj <= ii

    def step(state, inp):
        xc, lac, bc, cc = inp                # (B,L,H,P),(B,L,H),(B,L,N)x2
        cum = jnp.cumsum(lac, axis=1)        # (B,L,H) inclusive
        seg = cum[:, :, None, :] - cum[:, None, :, :]      # (B,L,L,H)
        # mask BEFORE exp: the upper triangle is exp(+large) = inf, and
        # inf * 0 poisons the backward pass with NaNs
        seg = jnp.where(tri[None, :, :, None], seg, -jnp.inf)
        lmat = jnp.exp(seg)
        # (B,L,L,H) is the fat intermediate (observed 26.8 GiB/device on
        # mamba2 train_4k at chunk=256): keep heads on the model axis
        lmat = _dist.shard_named(lmat, ("D", "-", "-", "T"))
        scores = jnp.einsum("bln,bmn->blm", cc, bc)        # (B,L,L) shared
        y = jnp.einsum("blm,blmh,bmhp->blhp", scores, lmat, xc)
        # inter-chunk: state contribution
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bln,bhnp->blhp", cc, state)
        # state update
        decay_all = jnp.exp(cum[:, -1])                    # (B,H)
        w = jnp.exp(cum[:, -1:, :] - cum)                  # (B,L,H)
        state = (state * decay_all[..., None, None]
                 + jnp.einsum("bln,blh,blhp->bhnp", bc, w, xc))
        return state, y

    state0 = (jnp.zeros((bsz, h, n, p), jnp.float32) if state0 is None
              else state0)
    xs_t = (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(las, 1, 0),
            jnp.moveaxis(bs, 1, 0), jnp.moveaxis(cs, 1, 0))
    final, ys = jax.lax.scan(step, state0, xs_t)
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, p)
    return y.astype(x.dtype), final


def _conv1d_seq(w, bias, x):
    """Causal depthwise conv.  x: (B, S, C); w: (cw, C)."""
    out = x * w[-1]
    for i in range(1, w.shape[0]):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, : x.shape[1], :]
        out = out + shifted * w[w.shape[0] - 1 - i]
    return out + bias


def _layer_seq(lp, x, cfg: ArchConfig):
    """Returns (x_out, (final_state, conv_tail))."""
    s_cfg = cfg.ssm
    d_in, h, conv_ch = _dims(cfg)
    n = s_cfg.d_state
    hidden = rms_norm(x, lp["ln"], cfg.norm_eps)
    proj = jnp.einsum("bsd,dk->bsk", hidden, lp["w_in"])
    z, xbc, dt_raw = jnp.split(proj, [d_in, d_in + conv_ch], axis=-1)
    conv_tail = xbc[:, -(s_cfg.d_conv - 1):, :]
    xbc = jax.nn.silu(_conv1d_seq(lp["conv_w"], lp["conv_b"], xbc)
                      .astype(jnp.float32)).astype(x.dtype)
    xs, b, c = jnp.split(xbc, [d_in, d_in + n], axis=-1)
    bsz, s, _ = x.shape
    xs = xs.reshape(bsz, s, h, s_cfg.head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"])
    a = -jnp.exp(lp["a_log"])
    y, final_state = ssd_chunked(xs, dt, a, b, c, s_cfg.chunk)
    y = y + (xs.astype(jnp.float32) * lp["d_skip"][None, None, :, None]
             ).astype(y.dtype)
    y = y.reshape(bsz, s, d_in)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 lp["gn"], cfg.norm_eps)
    return x + jnp.einsum("bsk,kd->bsd", y, lp["w_out"]), \
        (final_state, conv_tail)


def forward(params, cfg: ArchConfig, batch, collect_cache: bool = False):
    x = params["embed"][batch["tokens"]]

    def body(x, lp):
        return _layer_seq(lp, _dist.shard_activation(x), cfg)

    if cfg.remat:
        body = jax.checkpoint(body)
    x, caches = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    if collect_cache:
        return x, caches
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
# Serving — O(1) per-token state recurrence
# ---------------------------------------------------------------------------
def init_cache(cfg: ArchConfig, batch_size: int, max_seq: int,
               dtype=jnp.bfloat16):
    s_cfg = cfg.ssm
    d_in, h, conv_ch = _dims(cfg)
    return {
        "state": jnp.zeros((cfg.n_layers, batch_size, h, s_cfg.d_state,
                            s_cfg.head_dim), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, batch_size, s_cfg.d_conv - 1,
                           conv_ch), dtype),
        "len": jnp.zeros((batch_size,), jnp.int32),
    }


def prefill(params, cfg: ArchConfig, batch, max_seq: int):
    hidden, (states, conv_tails) = forward(params, cfg, batch,
                                           collect_cache=True)
    b = hidden.shape[0]
    unembed = params.get("unembed", params["embed"])
    logits = jnp.einsum("bd,vd->bv", hidden[:, -1], unembed,
                        preferred_element_type=jnp.float32)
    cache = {"state": states, "conv": conv_tails,
             "len": jnp.full((b,), hidden.shape[1], jnp.int32)}
    return logits, cache


def _layer_step(lp, x, state, conv_buf, cfg: ArchConfig):
    s_cfg = cfg.ssm
    d_in, h, conv_ch = _dims(cfg)
    n = s_cfg.d_state
    hidden = rms_norm(x, lp["ln"], cfg.norm_eps)
    proj = hidden @ lp["w_in"]
    z, xbc, dt_raw = jnp.split(proj, [d_in, d_in + conv_ch], axis=-1)
    window = jnp.concatenate([conv_buf, xbc[:, None, :]], axis=1)
    conv_out = jnp.einsum("bcw,cw->bw", window, lp["conv_w"]) + lp["conv_b"]
    new_conv = window[:, 1:, :]
    xbc = jax.nn.silu(conv_out.astype(jnp.float32)).astype(x.dtype)
    xs, b, c = jnp.split(xbc, [d_in, d_in + n], axis=-1)
    bsz = x.shape[0]
    xs = xs.reshape(bsz, h, s_cfg.head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"])  # (B,H)
    a = -jnp.exp(lp["a_log"])
    decay = jnp.exp(dt * a[None, :])                                  # (B,H)
    dbx = jnp.einsum("bn,bhp->bhnp", b.astype(jnp.float32),
                     xs.astype(jnp.float32) * dt[..., None])
    state = state * decay[..., None, None] + dbx
    y = jnp.einsum("bn,bhnp->bhp", c.astype(jnp.float32), state)
    y = y + xs.astype(jnp.float32) * lp["d_skip"][None, :, None]
    y = y.reshape(bsz, d_in).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 lp["gn"], cfg.norm_eps)
    return x + y @ lp["w_out"], state, new_conv


def decode_step(params, cfg: ArchConfig, cache, tokens, positions=None):
    x = params["embed"][tokens]

    def body(x, xs):
        lp, st, cb = xs
        x, st, cb = _layer_step(lp, _dist.shard_activation(x), st, cb, cfg)
        return x, (st, cb)

    x, (states, convs) = jax.lax.scan(
        body, x, (params["layers"], cache["state"], cache["conv"]))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    unembed = params.get("unembed", params["embed"])
    logits = jnp.einsum("bd,vd->bv", x, unembed,
                        preferred_element_type=jnp.float32)
    return logits, {"state": states, "conv": convs, "len": cache["len"] + 1}
