"""Mamba-2 SSD (state-space duality) chunked-scan Pallas kernel.

The SSD algorithm splits the sequence into chunks: within a chunk the
recurrence is computed as a (masked, decay-weighted) quadratic attention-like
matmul (MXU-friendly); across chunks a small (N x P) state is carried.  On
TPU the state lives in VMEM scratch across sequential grid steps — the
analogue of the paper's LDS-resident accumulators on MI300.

Inputs are pre-fused by ops.py: ``dtx = x * dt`` and ``la = dt * A`` so the
kernel carries no per-head scalars.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import default_interpret


def _ssd_body(dtx_ref, la_ref, b_ref, c_ref, y_ref, state_ref, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    dtx = dtx_ref[0, 0].astype(jnp.float32)  # (L, P)
    la = la_ref[0, 0].astype(jnp.float32).reshape(chunk, 1)  # (L, 1) log-decay
    bmat = b_ref[0].astype(jnp.float32)  # (L, N)
    cmat = c_ref[0].astype(jnp.float32)  # (L, N)

    cum = jnp.cumsum(la, axis=0)  # (L, 1) inclusive
    # intra-chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (c_i . b_j) dtx_j
    seg = cum - cum.reshape(1, chunk)  # (L, L): cum_i - cum_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lmat = jnp.exp(jnp.where(jj <= ii, seg, -jnp.inf))  # mask pre-exp
    scores = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32) * lmat
    y = jnp.dot(scores, dtx, preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the state at chunk entry
    state = state_ref[...]  # (N, P)
    y = y + jnp.exp(cum) * jnp.dot(cmat, state, preferred_element_type=jnp.float32)

    # state update: S <- exp(cum_L) S + sum_j exp(cum_L - cum_j) b_j (x dt)_j
    decay_all = jnp.exp(cum[-1])  # scalar-ish (1,)
    w = jnp.exp(cum[-1] - cum)  # (L, 1)
    state_ref[...] = decay_all * state + jnp.dot(
        (bmat * w).T, dtx, preferred_element_type=jnp.float32
    )

    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd(
    dtx,
    la,
    b,
    c,
    *,
    chunk: int = 128,
    interpret: bool | None = None,
):
    """dtx: (B, H, S, P); la: (B, H, S); b, c: (B, S, N).  Returns (B, H, S, P)."""
    bsz, h, s, p = dtx.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    grid = (bsz, h, s // chunk)

    body = functools.partial(_ssd_body, chunk=chunk)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bb, hh, ic: (bb, hh, ic, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bb, hh, ic: (bb, hh, ic)),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, ic: (bb, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, ic: (bb, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p), lambda bb, hh, ic: (bb, hh, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, s, p), dtx.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=default_interpret() if interpret is None else interpret,
    )(dtx, la, b, c)
