"""Public jit'd wrappers around the Pallas kernels.

These are what the model code and the Kernel Scientist's EvaluationService
call.  Each wrapper handles padding/reshaping to kernel-legal shapes and
dispatches to the pure-jnp reference when ``use_pallas=False`` (the default
for XLA-only paths like the multi-pod dry-run, where kernels are swapped in
on real TPU hardware only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import ref as _ref
from . import scaled_gemm as _sg
from . import ssd as _ssd


def _pad_to(x, multiple, axis):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m",
        "block_n",
        "block_k",
        "grid_order",
        "scale_application",
        "use_pallas",
        "interpret",
    ),
)
def scaled_gemm(
    a,
    b,
    a_scale,
    b_scale,
    *,
    block_m=256,
    block_n=256,
    block_k=256,
    grid_order="mn",
    scale_application="scale_acc",
    use_pallas=True,
    interpret=None,
):
    if not use_pallas:
        return _ref.scaled_gemm(a, b, a_scale, b_scale)
    m, k = a.shape
    n = b.shape[1]
    block_m = min(block_m, max(128, m))
    block_n = min(block_n, max(128, n))
    block_k = min(block_k, max(128, k))
    ap = _pad_to(_pad_to(a, block_m, 0), block_k, 1)
    bp = _pad_to(_pad_to(b, block_k, 0), block_n, 1)
    asp = _pad_to(_pad_to(a_scale, block_m, 0), block_k // 128, 1)
    bsp = _pad_to(_pad_to(b_scale, block_k // 128, 0), block_n // 128, 1)
    out = _sg.scaled_gemm(
        ap,
        bp,
        asp,
        bsp,
        block_m=block_m,
        block_n=block_n,
        block_k=block_k,
        grid_order=grid_order,
        scale_application=scale_application,
        interpret=interpret,
    )
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "use_pallas", "interpret"),
)
def attention(
    q, k, v, *, causal=True, window=None, block_q=256, block_k=256,
    use_pallas=True, interpret=None,
):
    if not use_pallas:
        return _ref.attention(q, k, v, causal=causal, window=window)
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("block_k", "use_pallas", "interpret")
)
def decode_attention(q, k, v, kv_len, *, block_k=512, use_pallas=True, interpret=None):
    if not use_pallas:
        return _ref.decode_attention(q, k, v, kv_len)
    return _fa.decode_attention(q, k, v, kv_len, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas", "interpret"))
def ssd(x, dt, a, b, c, *, d_skip=None, chunk=128, use_pallas=True, interpret=None):
    """x: (B, S, H, P), dt: (B, S, H), a: (H,), b/c: (B, S, N)."""
    if not use_pallas:
        return _ref.ssd(x, dt, a, b, c, d_skip=d_skip)
    # fuse per-head scalars outside the kernel, move to (B, H, S, ...) layout
    dtx = jnp.einsum("bshp,bsh->bhsp", x.astype(jnp.float32), dt.astype(jnp.float32))
    la = jnp.transpose(dt.astype(jnp.float32) * a[None, None, :], (0, 2, 1))
    y = _ssd.ssd(dtx, la, b, c, chunk=chunk, interpret=interpret)
    y = jnp.transpose(y, (0, 2, 1, 3)).astype(x.dtype)  # back to (B, S, H, P)
    if d_skip is not None:
        y = (y.astype(jnp.float32) + x.astype(jnp.float32) * d_skip[None, None, :, None]).astype(x.dtype)
    return y
