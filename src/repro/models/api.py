"""Family-dispatching model API: one interface over all assigned archs.

    init_params(cfg, key)                  -> params pytree
    loss_fn(params, cfg, batch)            -> (scalar loss, metrics)
    prefill(params, cfg, batch, max_seq)   -> (last-token logits, cache)
    decode_step(params, cfg, cache, toks)  -> (logits, new cache)
    init_cache(cfg, batch, max_seq)        -> zeroed cache pytree
    attn_steps(cfg, seq_len)               -> flash-loop steps per layer
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import rglru, ssm, transformer
from .common import attention_plan
from .config import ArchConfig

_FAMS = {
    "dense": transformer, "moe": transformer, "mla": transformer,
    "rglru": rglru, "ssm": ssm,
}


def _mod(cfg: ArchConfig):
    return _FAMS[cfg.family]


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ArchConfig, key):
    # jitted: eagerly, every per-layer tree and the stack built from it
    # would sit on the device together, twice the weights at published width
    return _mod(cfg).init_params(cfg, key)


def abstract_params(cfg: ArchConfig):
    """Parameter shapes without allocation (for the dry-run)."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.key(0))


def loss_fn(params, cfg: ArchConfig, batch):
    return _mod(cfg).loss_fn(params, cfg, batch)


# The embedding tables carry vocab_padded rows; the rows past cfg.vocab are
# no token, so the logits callers see stop at cfg.vocab.
def prefill(params, cfg: ArchConfig, batch, max_seq: int):
    logits, cache = _mod(cfg).prefill(params, cfg, batch, max_seq)
    return logits[..., :cfg.vocab], cache


def decode_step(params, cfg: ArchConfig, cache, tokens, positions=None):
    if cfg.encoder_only:
        raise ValueError(f"{cfg.name} is encoder-only: no decode step")
    logits, cache = _mod(cfg).decode_step(params, cfg, cache, tokens,
                                          positions)
    return logits[..., :cfg.vocab], cache


def init_cache(cfg: ArchConfig, batch_size: int, max_seq: int,
               dtype=jnp.bfloat16):
    return _mod(cfg).init_cache(cfg, batch_size, max_seq, dtype)


def attn_steps(cfg: ArchConfig, seq_len: int) -> int:
    """Serial flash-loop steps of one attention layer over ``seq_len``
    tokens, as `blockwise_attention` plans them (0: no attention)."""
    if cfg.family == "ssm":
        return 0
    window = cfg.rglru.window if cfg.family == "rglru" else None
    return attention_plan(seq_len, seq_len, cfg.attn_q_chunk,
                          cfg.attn_k_chunk, causal=not cfg.encoder_only,
                          window=window).steps


def make_batch(cfg: ArchConfig, batch_size: int, seq_len: int, key=None):
    """A synthetic batch with the right structure for `cfg` (smoke tests)."""
    key = key if key is not None else jax.random.key(0)
    k1, k2, k3 = jax.random.split(key, 3)
    batch = {}
    if cfg.inputs == "embeddings":
        batch["embeds"] = (jax.random.normal(
            k1, (batch_size, seq_len, cfg.d_model), jnp.float32) * 0.02
        ).astype(jnp.dtype(cfg.param_dtype))
    else:
        batch["tokens"] = jax.random.randint(
            k1, (batch_size, seq_len), 0, cfg.vocab, jnp.int32)
    batch["labels"] = jax.random.randint(
        k2, (batch_size, seq_len), 0, cfg.vocab, jnp.int32)
    if cfg.mrope:
        pos = jnp.broadcast_to(jnp.arange(seq_len, dtype=jnp.int32),
                               (batch_size, seq_len))
        batch["positions"] = jnp.stack([pos, pos * 0, pos * 0], 0)
    return batch
