"""Int8 cross-pod gradient averaging with error feedback.

Inter-pod links are an order of magnitude slower than in-pod ICI, so the
cross-pod all-reduce of data-parallel gradients is the one collective
worth quantising: each pod sends int8 values plus one f32 scale per leaf
(~4x fewer wire bytes than bf16, ~8x vs f32) and averages the dequantised
gathers locally.  The quantisation residual is carried in an error-feedback
state and added back into the next step's gradient, so the *accumulated*
compression error stays bounded by one quantisation step instead of
growing linearly (EF-SGD; Karimireddy et al., 2019).

    err = init_error_state(grads)
    mean, err = cross_pod_mean(grads, err, mesh)   # every step

Meshes without a ``pod`` axis (or with pod=1) skip the collective but keep
the quantise/dequantise + error-feedback arithmetic, so single-pod runs
exercise identical numerics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


POD_AXIS = "pod"


def init_error_state(grads):
    """Zeroed f32 error-feedback residuals, one per gradient leaf."""
    return jax.tree.map(
        lambda g: jnp.zeros(jnp.shape(g), jnp.float32), grads)


def _quantise(v):
    """v (f32) -> (int8 codes, f32 scale); symmetric per-leaf scaling."""
    scale = jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(v / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def _compress_leaf(g, e):
    """Returns (int8 codes, scale, new error residual)."""
    v = g.astype(jnp.float32) + e
    q, scale = _quantise(v)
    new_e = v - q.astype(jnp.float32) * scale
    return q, scale, new_e


def cross_pod_mean(grads, err, mesh, axis: str = POD_AXIS):
    """Error-feedback int8 mean of `grads` over the mesh's pod axis.

    Returns (mean tree matching grads' dtypes, new error state).  The wire
    payload per pod is the int8 code tensor + one f32 scale per leaf; the
    mean is reconstructed from the all-gathered (codes, scales) pairs.
    """
    n_pods = dict(mesh.shape).get(axis, 1)
    leaves, treedef = jax.tree.flatten(grads)
    e_leaves = treedef.flatten_up_to(err)

    if n_pods <= 1:
        out = [_compress_leaf(g, e) for g, e in zip(leaves, e_leaves)]
        means = [(q.astype(jnp.float32) * s).astype(g.dtype)
                 for (q, s, _), g in zip(out, leaves)]
        return treedef.unflatten(means), treedef.unflatten(
            [o[2] for o in out])

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False)
    def _mean_ef(g_tree, e_tree):
        gs = treedef.flatten_up_to(g_tree)
        es = treedef.flatten_up_to(e_tree)
        means, new_es = [], []
        for g, e in zip(gs, es):
            q, scale, new_e = _compress_leaf(g, e)
            # wire: int8 codes + scalar scale, gathered across pods
            qs = jax.lax.all_gather(q, axis)               # (P, ...)
            ss = jax.lax.all_gather(scale, axis)           # (P,)
            deq = qs.astype(jnp.float32) * ss.reshape((-1,) + (1,) * q.ndim)
            means.append(jnp.mean(deq, axis=0).astype(g.dtype))
            new_es.append(new_e)
        return treedef.unflatten(means), treedef.unflatten(new_es)

    return _mean_ef(grads, err)


def wire_bytes(grads) -> dict:
    """Per-step cross-pod payload: compressed vs raw (diagnostics)."""
    n = sum(leaf.size for leaf in jax.tree.leaves(grads))
    raw = sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
              for leaf in jax.tree.leaves(grads))
    n_leaves = len(jax.tree.leaves(grads))
    return {"compressed": n + 4 * n_leaves, "raw": int(raw),
            "ratio": float(raw) / max(n + 4 * n_leaves, 1)}
