"""Plain float32 reference for the Qwen2 architecture (Qwen2ForCausalLM).

Written from the published description, in plain ``jax.numpy``; it imports
nothing of the program under test.  Per layer: RMSNorm, attention with
query/key/value biases, rotary embeddings (rotate-half, theta from the
config) and grouped key/value heads, a residual add, RMSNorm, a SwiGLU MLP
and a residual add; then a final RMSNorm and the head, tied to the
embedding where the config says so.

Every matrix product runs at ``Precision.HIGHEST`` with float32 results.
``mode="fp8"`` rounds both operands of every product to float8 e4m3 (one
scale per row or column) first, and in the backward pass the incoming
gradient to e5m2, as float8 training does: that is the control, the nearest
precision below the bfloat16 that the config states.

Weights come from the seed (``make_weights``), in the bfloat16 that the
config states; the reference computes on them in float32.  The program is
handed the same weights, laid out its way, by ``qwen2_program``.  RMSNorm
scales are stored as offsets from 1 (``weight = 1 + offset``), which is
exact in float32 and is how the program stores them.

This module also holds the operation and byte counts of the architecture,
from its shapes: the yardstick of the roofline and utilization metrics.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0             # largest finite float8 e4m3fn
FP8_E5M2_MAX = 57344.0      # largest finite float8 e5m2
NORM_SCALE_STD = 0.05       # spread of the RMSNorm weights around 1
BIAS_STD = 0.05             # query/key/value biases


class Dims(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    eps: float
    theta: float
    tied: bool


def dims(conf: dict) -> Dims:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return Dims(conf["num_hidden_layers"], d, h, conf["num_key_value_heads"],
                conf.get("head_dim") or d // h, conf["intermediate_size"],
                conf["vocab_size"], float(conf["rms_norm_eps"]),
                float(conf["rope_theta"]), bool(conf["tie_word_embeddings"]))


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=0)
def _weights(dm: Dims, key):
    bf = jnp.bfloat16
    d, f, hd = dm.d, dm.ffn, dm.head_dim
    q, kv = dm.heads * hd, dm.kv_heads * hd

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(bf)

    def layer(k):
        ks = jax.random.split(k, 12)
        return {
            "in_norm": normal(ks[0], (d,), NORM_SCALE_STD),
            "q_w": normal(ks[1], (d, q), d ** -0.5),
            "q_b": normal(ks[2], (q,), BIAS_STD),
            "k_w": normal(ks[3], (d, kv), d ** -0.5),
            "k_b": normal(ks[4], (kv,), BIAS_STD),
            "v_w": normal(ks[5], (d, kv), d ** -0.5),
            "v_b": normal(ks[6], (kv,), BIAS_STD),
            "o_w": normal(ks[7], (q, d), q ** -0.5),
            "post_norm": normal(ks[8], (d,), NORM_SCALE_STD),
            "gate": normal(ks[9], (d, f), d ** -0.5),
            "up": normal(ks[10], (d, f), d ** -0.5),
            "down": normal(ks[11], (f, d), f ** -0.5),
        }

    k_emb, k_norm, k_layers, k_head = jax.random.split(key, 4)
    w = {
        "embed": normal(k_emb, (dm.vocab, d), d ** -0.5),
        "norm": normal(k_norm, (d,), NORM_SCALE_STD),
        "layers": jax.vmap(layer)(jax.random.split(k_layers, dm.layers)),
    }
    if not dm.tied:
        w["head"] = normal(k_head, (dm.vocab, d), d ** -0.5)
    return w


def make_weights(conf: dict, seed: int):
    """The model's weights from ``seed``, bfloat16, made on the device."""
    return _weights(dims(conf), seed_key(seed))


# ---------------------------------------------------------------------------
# Products: float32 at HIGHEST, or the float8 control
# ---------------------------------------------------------------------------
def _q8(x, axis, dtype=jnp.float8_e4m3fn, top=FP8_MAX):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _summed(spec: str, other: str, out: str) -> tuple:
    """Axes of ``spec`` that a product with ``other`` into ``out`` sums."""
    return tuple(i for i, c in enumerate(spec) if c in other and c not in out)


def _specs(spec: str):
    ins, so = spec.split("->")
    sa, sb = ins.split(",")
    return sa, sb, so


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec: str, a, b):
    return _einsum_fp8_fwd(spec, a, b)[0]


def _einsum_fp8_fwd(spec, a, b):
    sa, sb, so = _specs(spec)
    qa, qb = _q8(a, _summed(sa, sb, so)), _q8(b, _summed(sb, sa, so))
    return _mm(spec, qa, qb), (qa, qb)


def _einsum_fp8_bwd(spec, res, g):
    """Float8 training's backward pass: the incoming gradient in e5m2, one
    scale per slice along the axes each product sums, against the e4m3
    operands of the forward pass."""
    qa, qb = res
    sa, sb, so = _specs(spec)
    ga = _q8(g, _summed(so, sb, sa), jnp.float8_e5m2, FP8_E5M2_MAX)
    gb = _q8(g, _summed(so, sa, sb), jnp.float8_e5m2, FP8_E5M2_MAX)
    return _mm(f"{so},{sb}->{sa}", ga, qb), _mm(f"{sa},{so}->{sb}", qa, gb)


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def einsum(spec: str, a, b, mode: str = "f32"):
    """``jnp.einsum`` at float32; under ``mode="fp8"`` both operands are
    rounded to e4m3 with one scale per slice along the axes the product
    sums, and in the backward pass the gradient to e5m2 likewise."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        return _einsum_fp8(spec, a, b)
    if mode != "f32":
        raise ValueError(f"unknown precision mode {mode!r}")
    return _mm(spec, a, b)


def rms_norm(x, offset, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + offset.astype(jnp.float32))


def rope(x, positions, theta):
    """Rotate-half rotary embedding.  x: (heads, S, head_dim)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]                            # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


# ---------------------------------------------------------------------------
# Forward pass of one sequence
# ---------------------------------------------------------------------------
def _layer(dm: Dims, mode: str, x, lw):
    s = x.shape[0]
    hd, g = dm.head_dim, dm.heads // dm.kv_heads
    pos = jnp.arange(s)
    h = rms_norm(x, lw["in_norm"], dm.eps)
    q = einsum("sd,dk->sk", h, lw["q_w"], mode) + lw["q_b"].astype(jnp.float32)
    k = einsum("sd,dk->sk", h, lw["k_w"], mode) + lw["k_b"].astype(jnp.float32)
    v = einsum("sd,dk->sk", h, lw["v_w"], mode) + lw["v_b"].astype(jnp.float32)
    q = rope(q.reshape(s, dm.heads, hd).transpose(1, 0, 2), pos, dm.theta)
    k = rope(k.reshape(s, dm.kv_heads, hd).transpose(1, 0, 2), pos, dm.theta)
    v = v.reshape(s, dm.kv_heads, hd).transpose(1, 0, 2)
    k, v = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)  # (H, S, hd)
    scores = einsum("hqd,hkd->hqk", q, k, mode) / math.sqrt(hd)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = einsum("hqk,hkd->hqd", probs, v, mode)
    att = att.transpose(1, 0, 2).reshape(s, dm.heads * hd)
    x = x + einsum("sk,kd->sd", att, lw["o_w"], mode)
    h = rms_norm(x, lw["post_norm"], dm.eps)
    gate = einsum("sd,df->sf", h, lw["gate"], mode)
    up = einsum("sd,df->sf", h, lw["up"], mode)
    return x + einsum("sf,fd->sd", jax.nn.silu(gate) * up, lw["down"], mode)


def hidden(w, dm: Dims, tokens, mode: str = "f32", remat: bool = False):
    """Final-normed hidden states (S, d) of one sequence of token ids.

    ``remat`` recomputes each layer in the backward pass.  The layer then
    takes its weights by index from the stack inside the recomputed
    function, so that the backward pass keeps no copy of them."""
    layers = w["layers"]

    def body(x, i):
        lw = jax.tree.map(lambda t: t[i], layers)
        return _layer(dm, mode, x, lw)

    if remat:
        body = jax.checkpoint(body)
    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(lambda c, i: (body(c, i), None), x,
                        jnp.arange(dm.layers))
    return rms_norm(x, w["norm"], dm.eps)


def head(w):
    return w["embed"] if "head" not in w else w["head"]


@functools.partial(jax.jit, static_argnums=(1, 4))
def _logits_at(w, dm: Dims, tokens, rows, mode):
    h = hidden(w, dm, tokens, mode)[rows]
    return einsum("td,vd->tv", h, head(w), mode)


def logits_at(w, conf: dict, tokens, rows, mode: str = "f32"):
    """Logits (len(rows), vocab) at positions ``rows`` of ``tokens``.

    Under ``jax.default_matmul_precision("highest")`` as well as the
    explicit precision of every product."""
    with jax.default_matmul_precision("highest"):
        return _logits_at(w, dims(conf), jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(rows, jnp.int32), mode)


def loss_of_row(w, dm: Dims, tokens, labels, mode: str = "f32",
                chunk: int = 1024):
    """Mean next-token cross-entropy of one row; layers recomputed in the
    backward pass and the head taken in blocks of ``chunk`` tokens, so
    that a 4k row fits beside the weights.  Neither changes the result."""
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

    def step(acc, xs):
        return acc + block(*xs), None

    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32),
                            (h.reshape(s // chunk, chunk, -1),
                             labels.reshape(s // chunk, chunk)))
    return total / s


# ---------------------------------------------------------------------------
# Operations and bytes, from shapes
# ---------------------------------------------------------------------------
def layer_matmul_params(dm: Dims) -> int:
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    return dm.d * (2 * q + 2 * kv) + 3 * dm.d * dm.ffn


def param_count(dm: Dims) -> int:
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    per = layer_matmul_params(dm) + q + 2 * kv + 2 * dm.d
    return (dm.layers * per + dm.vocab * dm.d * (1 if dm.tied else 2)
            + dm.d)


def forward_flops(dm: Dims, n_tokens: int, context: float) -> float:
    """Model FLOPs of a forward pass over ``n_tokens`` new tokens that each
    attend to ``context`` positions on average (multiply-add = 2)."""
    dense = 2.0 * (dm.layers * layer_matmul_params(dm) + dm.vocab * dm.d)
    attn = 4.0 * dm.layers * dm.heads * dm.head_dim * context
    return n_tokens * (dense + attn)


def prefill_flops(dm: Dims, prompt_len: int) -> float:
    """Causal prefill of one prompt: token t attends to t + 1 positions.
    The head runs for the last position only."""
    dense = 2.0 * dm.layers * layer_matmul_params(dm) * prompt_len
    attn = (4.0 * dm.layers * dm.heads * dm.head_dim
            * prompt_len * (prompt_len + 1) / 2)
    return dense + attn + 2.0 * dm.vocab * dm.d


def train_flops_per_token(dm: Dims, seq_len: int) -> float:
    """Forward and backward (3x forward), causal, no recomputation."""
    return 3.0 * forward_flops(dm, 1, (seq_len + 1) / 2)


def kv_bytes_per_position(dm: Dims, dtype_bytes: int = 2) -> int:
    return dm.layers * 2 * dm.kv_heads * dm.head_dim * dtype_bytes


def decode_cost(dm: Dims, live_lengths, dtype_bytes: int = 2):
    """(FLOPs, least bytes) of one decode step for the live sequences,
    ``live_lengths`` being each one's cached positions before the step:
    every weight read once, the live keys and values read, one new key and
    value per sequence written."""
    n = len(live_lengths)
    ctx = float(np.sum(np.asarray(live_lengths, np.float64) + 1))
    flops = (2.0 * (dm.layers * layer_matmul_params(dm) + dm.vocab * dm.d)
             * n + 4.0 * dm.layers * dm.heads * dm.head_dim * ctx)
    per_pos = kv_bytes_per_position(dm, dtype_bytes)
    weights = param_count(dm) * dtype_bytes
    nbytes = weights + per_pos * float(np.sum(live_lengths)) + per_pos * n
    return flops, nbytes
