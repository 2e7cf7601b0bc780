"""How the program under test runs a Mamba-2 config: its ``ArchConfig``
and its parameter layout, filled with the reference's weights."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.models import mamba2_ref as ref

#: the program's layer leaves, keyed by the reference's names
_LAYER = {"ln": "norm", "w_in": "in_proj", "conv_w": "conv_w",
          "conv_b": "conv_b", "dt_bias": "dt_bias", "a_log": "A_log",
          "d_skip": "D", "gn": "gnorm", "w_out": "out_proj"}


def arch_config(conf: dict):
    """The repo's entry for this model, with every published (or assumed)
    value of ``conf`` in place of the entry's own."""
    from repro import configs
    from repro.models.config import SSMConfig
    dm = ref.dims(conf)
    a = conf["bench"]["assumed_layer"]
    base = configs.get_config(conf["bench"]["repo_arch"])
    return dataclasses.replace(
        base, n_layers=dm.layers, d_model=dm.d, vocab=dm.vocab,
        norm_eps=dm.eps, tie_embeddings=bool(conf["tie_embeddings"]),
        ssm=SSMConfig(d_state=dm.d_state, head_dim=dm.head_dim,
                      d_conv=dm.d_conv, expand=a["expand"],
                      chunk=a["chunk_size"]))


def to_program(w, vocab_padded: int):
    pad = lambda t: jnp.pad(t, ((0, vocab_padded - t.shape[0]), (0, 0)))
    return {"embed": pad(w["embed"]), "ln_f": w["norm_f"],
            "layers": {k: w["layers"][v] for k, v in _LAYER.items()}}


def from_program(p, vocab: int):
    return {"embed": p["embed"][:vocab], "norm_f": p["ln_f"],
            "layers": {v: p["layers"][k] for k, v in _LAYER.items()}}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _program_weights(dm, vocab_padded, key):
    return to_program(ref._weights(dm, key), vocab_padded)


def program_weights(conf: dict, cfg, seed: int):
    """The program's parameters from ``seed``, in one jitted call."""
    return _program_weights(ref.dims(conf), cfg.vocab_padded,
                            ref.seed_key(seed))
