"""AdamW with global-norm clipping, fp32 moments over bf16 params (ZeRO:
the moment trees inherit the FSDP parameter sharding, so optimizer state is
fully sharded across the mesh)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def init(params):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "count": jnp.zeros((), jnp.int32),
    }


def global_norm(tree):
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(tree)))


def update(grads, state, params, lr, cfg: AdamWConfig = AdamWConfig()):
    """Returns (new_params, new_state, metrics)."""
    with jax.named_scope("adamw"):
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-12))
        count = state["count"] + 1
        c1 = 1.0 - cfg.b1 ** count.astype(jnp.float32)
        c2 = 1.0 - cfg.b2 ** count.astype(jnp.float32)

        def upd(g, m, v, p):
            g = g.astype(jnp.float32) * scale
            m = cfg.b1 * m + (1.0 - cfg.b1) * g
            v = cfg.b2 * v + (1.0 - cfg.b2) * g * g
            step = (m / c1) / (jnp.sqrt(v / c2) + cfg.eps)
            # decoupled weight decay on matrices only (ndim >= 2)
            wd = cfg.weight_decay if p.ndim >= 2 else 0.0
            new_p = p.astype(jnp.float32) - lr * (
                step + wd * p.astype(jnp.float32))
            return new_p.astype(p.dtype), m, v

        flat_g, treedef = jax.tree.flatten(grads)
        flat_m = treedef.flatten_up_to(state["m"])
        flat_v = treedef.flatten_up_to(state["v"])
        flat_p = treedef.flatten_up_to(params)
        out = [upd(g, m, v, p) for g, m, v, p in
               zip(flat_g, flat_m, flat_v, flat_p)]
        new_params = treedef.unflatten([o[0] for o in out])
        new_state = {
            "m": treedef.unflatten([o[1] for o in out]),
            "v": treedef.unflatten([o[2] for o in out]),
            "count": count,
        }
        return new_params, new_state, {"grad_norm": gnorm}
