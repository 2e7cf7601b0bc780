"""How the program under test runs a Qwen2 config: its ``ArchConfig`` and
its parameter layout, filled with the reference's weights."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.models import qwen2_ref as ref

#: the program's parameter tree, keyed by the reference's names
_LAYER = {"ln1": "in_norm", "ln2": "post_norm",
          "attn": {"wq": "q_w", "bq": "q_b", "wk": "k_w", "bk": "k_b",
                   "wv": "v_w", "bv": "v_b", "wo": "o_w"},
          "ffn": {"w_gate": "gate", "w_up": "up", "w_down": "down"}}


def arch_config(conf: dict):
    """The repo's entry for this model, with every published value of
    ``conf`` in place of the entry's own."""
    from repro import configs
    dm = ref.dims(conf)
    base = configs.get_config(conf["bench"]["repo_arch"])
    return dataclasses.replace(
        base, n_layers=dm.layers, d_model=dm.d, n_heads=dm.heads,
        n_kv_heads=dm.kv_heads, head_dim=dm.head_dim, d_ff=dm.ffn,
        vocab=dm.vocab, rope_theta=dm.theta, norm_eps=dm.eps,
        tie_embeddings=dm.tied, qkv_bias=True)


def _map_layer(spec, lw):
    return {k: _map_layer(v, lw) if isinstance(v, dict) else lw[v]
            for k, v in spec.items()}


def to_program(w, vocab_padded: int):
    """Reference weights -> the program's tree.  The program keeps its
    vocabulary rows padded to ``vocab_padded``; the pad rows are zero."""
    pad = lambda t: jnp.pad(t, ((0, vocab_padded - t.shape[0]), (0, 0)))
    out = {"embed": pad(w["embed"]), "ln_f": w["norm"],
           "layers": _map_layer(_LAYER, w["layers"])}
    if "head" in w:
        out["unembed"] = pad(w["head"])
    return out


def from_program(p, vocab: int):
    """The program's tree (or one shaped like it) -> the reference's."""
    def walk(spec, node, out):
        for k, v in spec.items():
            if isinstance(v, dict):
                walk(v, node[k], out)
            else:
                out[v] = node[k]
        return out

    out = {"embed": p["embed"][:vocab], "norm": p["ln_f"],
           "layers": walk(_LAYER, p["layers"], {})}
    if "unembed" in p:
        out["head"] = p["unembed"][:vocab]
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _program_weights(dm, vocab_padded, key):
    return to_program(ref._weights(dm, key), vocab_padded)


def program_weights(conf: dict, cfg, seed: int):
    """The program's parameters from ``seed``, in one jitted call."""
    return _program_weights(ref.dims(conf), cfg.vocab_padded,
                            ref.seed_key(seed))
