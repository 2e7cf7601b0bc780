"""Plain float32 reference for the Mamba-2 architecture (MambaLMHeadModel
with Mamba2 mixers, one group of B and C).

Written from the published description (arXiv:2405.21060 and the
reference implementation's layer), in plain ``jax.numpy``; it imports
nothing of the program under test.  Per layer: RMSNorm; one input
projection to (z, x, B, C, dt); a causal depthwise convolution over
(x, B, C) with bias, then SiLU; dt = softplus(dt + dt_bias), A = -exp(A_log);
the state-space recurrence token by token,

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T,    y_t = C_t h_t + D x_t,

a gated RMSNorm, RMSNorm(y * silu(z)), an output projection and a residual
add; then a final RMSNorm and the head, tied to the embedding.

Products run at ``Precision.HIGHEST``; ``mode="fp8"`` rounds both operands
of every projection to float8 e4m3 first (the control).  Weights come from
the seed in bfloat16 (``dt_bias``, ``A_log`` and ``D`` in float32, as the
published implementation keeps them); RMSNorm weights are offsets from 1.

Also here: the operation and byte counts of the architecture, from its
shapes.  Model FLOPs count the recurrence (linear in length), not the
chunked algorithm's quadratic tiles.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from bench.models.qwen2_ref import einsum, seed_key

NORM_SCALE_STD = 0.05
CONV_BIAS_STD = 0.05
#: the time chunk over which the training reference keeps its state for
#: the backward pass (a memory bound only: it does not change the result)
REMAT_CHUNK = 64


class Dims(NamedTuple):
    layers: int
    d: int
    d_inner: int
    heads: int
    head_dim: int
    d_state: int
    d_conv: int
    vocab: int
    eps: float


def dims(conf: dict) -> Dims:
    a = conf["bench"]["assumed_layer"]
    d = conf["d_model"]
    d_inner = a["expand"] * d
    pad = conf["pad_vocab_size_multiple"]
    vocab = -(-conf["vocab_size"] // pad) * pad
    return Dims(conf["n_layer"], d, d_inner, d_inner // a["headdim"],
                a["headdim"], a["d_state"], a["d_conv"], vocab,
                float(a["norm_epsilon"]))


def _conv_channels(dm: Dims) -> int:
    return dm.d_inner + 2 * dm.d_state


def _in_width(dm: Dims) -> int:
    return 2 * dm.d_inner + 2 * dm.d_state + dm.heads


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=0)
def _weights(dm: Dims, key):
    bf = jnp.bfloat16
    d, di, h, c = dm.d, dm.d_inner, dm.heads, _conv_channels(dm)

    def normal(k, shape, std, dtype=bf):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def layer(k):
        ks = jax.random.split(k, 9)
        dt = jnp.exp(jax.random.uniform(ks[5], (h,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return {
            "norm": normal(ks[0], (d,), NORM_SCALE_STD),
            "in_proj": normal(ks[1], (d, _in_width(dm)), d ** -0.5),
            "conv_w": normal(ks[2], (dm.d_conv, c), dm.d_conv ** -0.5),
            "conv_b": normal(ks[3], (c,), CONV_BIAS_STD),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
            "A_log": jnp.log(jax.random.uniform(ks[6], (h,), jnp.float32,
                                                1.0, 16.0)),
            "D": 1.0 + normal(ks[7], (h,), 0.1, jnp.float32),
            "gnorm": normal(ks[8], (di,), NORM_SCALE_STD),
            "out_proj": normal(ks[4], (di, d), di ** -0.5),
        }

    k_emb, k_norm, k_layers = jax.random.split(key, 3)
    return {
        "embed": normal(k_emb, (dm.vocab, d), d ** -0.5),
        "norm_f": normal(k_norm, (d,), NORM_SCALE_STD),
        "layers": jax.vmap(layer)(jax.random.split(k_layers, dm.layers)),
    }


def make_weights(conf: dict, seed: int):
    """The model's weights from ``seed``, made on the device."""
    return _weights(dims(conf), seed_key(seed))


# ---------------------------------------------------------------------------
# Forward pass of one sequence
# ---------------------------------------------------------------------------
def rms_norm(x, offset, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + offset.astype(jnp.float32))


def _recurrence(x, dt, a, b, c, remat: bool):
    """x: (S, H, P); dt: (S, H); a: (H,); b, c: (S, N).  Token by token."""
    h, p, n = x.shape[1], x.shape[2], b.shape[1]

    def step(state, xs):
        xt, dtt, bt, ct = xs
        state = (state * jnp.exp(dtt * a)[:, None, None]
                 + dtt[:, None, None] * bt[None, :, None] * xt[:, None, :])
        return state, jnp.einsum("n,hnp->hp", ct, state, precision="highest")

    state0 = jnp.zeros((h, n, p), jnp.float32)
    xs = (x, dt, b, c)
    if not remat:
        return jax.lax.scan(step, state0, xs)[1]
    s = x.shape[0]
    k = math.gcd(REMAT_CHUNK, s)
    chunked = jax.tree.map(lambda t: t.reshape((s // k, k) + t.shape[1:]), xs)

    @jax.checkpoint
    def chunk(state, xs):
        return jax.lax.scan(step, state, xs)

    return jax.lax.scan(chunk, state0, chunked)[1].reshape(s, h, p)


def _layer(dm: Dims, mode: str, remat: bool, x, lw):
    s = x.shape[0]
    di, n, c = dm.d_inner, dm.d_state, _conv_channels(dm)
    hdn = rms_norm(x, lw["norm"], dm.eps)
    proj = einsum("sd,dk->sk", hdn, lw["in_proj"], mode)
    z, xbc, dt = proj[:, :di], proj[:, di:di + c], proj[:, di + c:]
    w = lw["conv_w"].astype(jnp.float32)                 # (d_conv, C)
    padded = jnp.pad(xbc, ((dm.d_conv - 1, 0), (0, 0)))
    conv = sum(padded[k:k + s] * w[k] for k in range(dm.d_conv))
    xbc = jax.nn.silu(conv + lw["conv_b"].astype(jnp.float32))
    xs, b, cm = xbc[:, :di], xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    a = -jnp.exp(lw["A_log"])
    xh = xs.reshape(s, dm.heads, dm.head_dim)
    y = _recurrence(xh, dt, a, b, cm, remat)
    y = y + lw["D"][None, :, None] * xh
    y = rms_norm(y.reshape(s, di) * jax.nn.silu(z), lw["gnorm"], dm.eps)
    return x + einsum("sk,kd->sd", y, lw["out_proj"], mode)


def hidden(w, dm: Dims, tokens, mode: str = "f32", remat: bool = False):
    """Final-normed hidden states (S, d); ``remat`` as in ``qwen2_ref``."""
    layers = w["layers"]

    def body(x, i):
        lw = jax.tree.map(lambda t: t[i], layers)
        return _layer(dm, mode, remat, x, lw)

    if remat:
        body = jax.checkpoint(body)
    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(lambda c, i: (body(c, i), None), x,
                        jnp.arange(dm.layers))
    return rms_norm(x, w["norm_f"], dm.eps)


def head(w):
    return w["embed"]


@functools.partial(jax.jit, static_argnums=(1, 4))
def _logits_at(w, dm: Dims, tokens, rows, mode):
    h = hidden(w, dm, tokens, mode)[rows]
    return einsum("td,vd->tv", h, head(w), mode)


def logits_at(w, conf: dict, tokens, rows, mode: str = "f32"):
    """Logits (len(rows), vocab) at positions ``rows`` of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        return _logits_at(w, dims(conf), jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(rows, jnp.int32), mode)


def loss_of_row(w, dm: Dims, tokens, labels, mode: str = "f32",
                chunk: int = 1024):
    """Mean next-token cross-entropy of one row, layers and time chunks
    recomputed in the backward pass, the head in blocks of tokens."""
    h = hidden(w, dm, tokens, mode, remat=True)
    s = h.shape[0]
    chunk = math.gcd(chunk, s)
    emb = head(w)

    @jax.checkpoint
    def block(hb, lb):
        logits = einsum("td,vd->tv", hb, emb, mode)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    total, _ = jax.lax.scan(lambda acc, xs: (acc + block(*xs), None),
                            jnp.zeros((), jnp.float32),
                            (h.reshape(s // chunk, chunk, -1),
                             labels.reshape(s // chunk, chunk)))
    return total / s


# ---------------------------------------------------------------------------
# Operations and bytes, from shapes
# ---------------------------------------------------------------------------
def layer_flops_per_token(dm: Dims) -> float:
    proj = 2.0 * (dm.d * _in_width(dm) + dm.d_inner * dm.d)
    conv = 2.0 * dm.d_conv * _conv_channels(dm)
    scan = 6.0 * dm.heads * dm.d_state * dm.head_dim
    return proj + conv + scan


def param_count(dm: Dims) -> int:
    c = _conv_channels(dm)
    per = (dm.d * _in_width(dm) + dm.d_conv * c + c + 3 * dm.heads
           + dm.d_inner + dm.d_inner * dm.d + dm.d)
    return dm.layers * per + dm.vocab * dm.d + dm.d


def forward_flops(dm: Dims, n_tokens: int) -> float:
    return n_tokens * (dm.layers * layer_flops_per_token(dm)
                       + 2.0 * dm.vocab * dm.d)


def prefill_flops(dm: Dims, prompt_len: int) -> float:
    return (prompt_len * dm.layers * layer_flops_per_token(dm)
            + 2.0 * dm.vocab * dm.d)


def train_flops_per_token(dm: Dims, seq_len: int) -> float:
    return 3.0 * forward_flops(dm, 1)


def state_bytes(dm: Dims, dtype_bytes: int = 2) -> int:
    """One sequence's recurrent state (float32) and convolution window."""
    return dm.layers * (dm.heads * dm.d_state * dm.head_dim * 4
                        + (dm.d_conv - 1) * _conv_channels(dm) * dtype_bytes)


def decode_cost(dm: Dims, live_lengths, dtype_bytes: int = 2):
    """(FLOPs, least bytes) of one decode step for the live sequences:
    every weight read once, each live sequence's state read and written."""
    n = len(live_lengths)
    flops = n * forward_flops(dm, 1)
    nbytes = (param_count(dm) * dtype_bytes
              + 2 * n * state_bytes(dm, dtype_bytes))
    return flops, nbytes
