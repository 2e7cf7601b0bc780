"""Block-scaled GEMM Pallas kernel — the paper's target kernel, TPU-native.

The AMD Developer Challenge task the paper optimizes is
``C[bf16] = dequant(A[fp8]) @ dequant(B[fp8])`` with per-(1x128) scales for A
and per-(128x128) scales for B, fp32 accumulation.  On MI300 the paper's
LLM-evolved kernel used MFMA Matrix Cores + LDS ping-pong double buffering.
The TPU-native equivalent implemented here:

  MI300 MFMA 32x32x16 fragments  ->  MXU jnp.dot, preferred_element_type=f32
  LDS tiles + ping/pong          ->  BlockSpec VMEM tiles + pipelined grid
  LDS scale-caching              ->  scale tiles as extra VMEM block operands
                                     (re-laid out by the wrapper so every
                                     block's last two dims meet Mosaic's
                                     (8, 128)-or-full-dim tiling rule)
  wave-cooperative stores        ->  grid-owned output tiles

Every axis the paper's Experiment Designer mutated (tile sizes, layouts,
vectorisation, scale application point, write-back) is a keyword parameter
here, so the Kernel Scientist's genome maps 1:1 onto ``pallas_call``
configurations.  See ``repro.core.genome``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import default_interpret

SCALE_BLOCK = 128


def kernel_scale_layouts(a_scale, b_scale, n_sub: int):
    """Challenge-layout scales -> the per-K-step layouts the kernel reads.

    a_scale (M, K/128) -> (K/block_k, M, n_sub): one K step's block is
    (block_m, n_sub), whose last dim is the array's full dim.
    b_scale (K/128, N/128) -> (K/block_k, n_sub, N), expanded per column:
    one K step's block is (n_sub, block_n), full dim then a lane multiple.
    """
    m, kb = a_scale.shape
    gk = kb // n_sub
    a_s = a_scale.astype(jnp.float32).reshape(m, gk, n_sub).transpose(1, 0, 2)
    b_s = jnp.repeat(b_scale.astype(jnp.float32), SCALE_BLOCK, axis=1)
    return a_s, b_s.reshape(gk, n_sub, -1)


def _kernel_body(
    a_ref,
    b_ref,
    as_ref,
    bs_ref,
    o_ref,
    acc_ref,
    *,
    k_steps: int,
    n_sub: int,
    scale_application: str,
    compute_dtype,
    acc_dtype,
):
    """One (block_m, block_n) output tile, one block_k slab of the K loop."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]  # (bm, bk) storage dtype
    b = b_ref[...]  # (bk, bn)
    a_s = as_ref[0]  # (bm, n_sub) f32
    b_s = bs_ref[0]  # (n_sub, bn) f32, one scale per column

    acc = acc_ref[...]
    for s in range(n_sub):  # statically unrolled over 128-wide K sub-blocks
        a_blk = a[:, s * SCALE_BLOCK : (s + 1) * SCALE_BLOCK].astype(jnp.float32)
        b_blk = b[s * SCALE_BLOCK : (s + 1) * SCALE_BLOCK, :].astype(jnp.float32)
        b_s_cols = b_s[s : s + 1, :]  # (1, bn)
        if scale_application == "dequant_inputs":
            # scale before the dot: more VPU work, inputs leave exact bf16 grid
            a_blk = (a_blk * a_s[:, s : s + 1]).astype(compute_dtype)
            b_blk = (b_blk * b_s_cols).astype(compute_dtype)
            part = jnp.dot(a_blk, b_blk, preferred_element_type=acc_dtype)
            acc = acc + part
        else:  # "scale_acc": dot raw quantized values (exact in bf16), scale after
            part = jnp.dot(
                a_blk.astype(compute_dtype),
                b_blk.astype(compute_dtype),
                preferred_element_type=acc_dtype,
            )
            acc = acc + part * a_s[:, s : s + 1] * b_s_cols
    acc_ref[...] = acc

    @pl.when(k_idx == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def scaled_gemm(
    a,
    b,
    a_scale,
    b_scale,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    grid_order: str = "mn",  # which output axis is outermost: "mn" or "nm"
    scale_application: str = "scale_acc",  # or "dequant_inputs"
    compute_dtype=jnp.bfloat16,  # MXU input dtype (bf16) or f32 (slow path)
    acc_dtype=jnp.float32,
    out_dtype=jnp.bfloat16,
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    interpret: bool | None = None,  # None: interpret only on the CPU backend
):
    """Blocked, scale-fused GEMM.  See module docstring for layout contract.

    a: (M, K) storage dtype; b: (K, N); a_scale: (M, K/128) f32;
    b_scale: (K/128, N/128) f32.  M, N, K must divide by the block sizes and
    block_k by 128 (the quantization block): the public wrapper in ``ops.py``
    pads arbitrary shapes first.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k),
        (block_m, block_n, block_k),
    )
    assert block_k % SCALE_BLOCK == 0 and block_n % SCALE_BLOCK == 0
    n_sub = block_k // SCALE_BLOCK
    gm, gn, gk = m // block_m, n // block_n, k // block_k
    a_scale, b_scale = kernel_scale_layouts(a_scale, b_scale, n_sub)

    body = functools.partial(
        _kernel_body,
        k_steps=gk,
        n_sub=n_sub,
        scale_application=scale_application,
        compute_dtype=compute_dtype,
        acc_dtype=acc_dtype,
    )

    if grid_order == "mn":
        grid = (gm, gn, gk)
        imap_a = lambda i, j, kk: (i, kk)
        imap_b = lambda i, j, kk: (kk, j)
        imap_o = lambda i, j, kk: (i, j)
        imap_as = lambda i, j, kk: (kk, i, 0)
        imap_bs = lambda i, j, kk: (kk, 0, j)
    else:  # "nm": N outermost — trades A-reload traffic for B-reload traffic
        grid = (gn, gm, gk)
        imap_a = lambda j, i, kk: (i, kk)
        imap_b = lambda j, i, kk: (kk, j)
        imap_o = lambda j, i, kk: (i, j)
        imap_as = lambda j, i, kk: (kk, i, 0)
        imap_bs = lambda j, i, kk: (kk, 0, j)

    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), imap_a),
            pl.BlockSpec((block_k, block_n), imap_b),
            pl.BlockSpec((1, block_m, n_sub), imap_as),
            pl.BlockSpec((1, n_sub, block_n), imap_bs),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), imap_o),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=dimension_semantics),
        interpret=default_interpret() if interpret is None else interpret,
    )(a, b, a_scale, b_scale)


def naive_scaled_gemm(a, b, a_scale, b_scale, *, out_dtype=jnp.bfloat16,
                      interpret=None):
    """The 'naive HIP translation' seed (paper §3): single grid step, whole
    problem resident, full dequant then one dot.  ~6x slower than the library
    path on MI300; on TPU it simply blows VMEM for real sizes — the cost model
    penalises it the same way."""
    m, k = a.shape
    _, n = b.shape
    n_sub = k // SCALE_BLOCK

    def body(a_ref, b_ref, as_ref, bs_ref, o_ref):
        a32 = a_ref[...].astype(jnp.float32).reshape(m, n_sub, SCALE_BLOCK)
        a32 = a32 * as_ref[...].astype(jnp.float32)[:, :, None]
        b32 = b_ref[...].astype(jnp.float32).reshape(n_sub, SCALE_BLOCK, n)
        bs = bs_ref[...].astype(jnp.float32)  # (n_sub, n//128)
        b32 = b32 * jnp.repeat(bs, SCALE_BLOCK, axis=1)[:, None, :]
        out = jnp.dot(
            a32.reshape(m, k), b32.reshape(k, n), preferred_element_type=jnp.float32
        )
        o_ref[...] = out.astype(o_ref.dtype)

    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=default_interpret() if interpret is None else interpret,
    )(a, b, a_scale, b_scale)
