"""Genome -> self-contained Pallas kernel source.

The Kernel Writer stage emits complete, runnable module text (as the paper's
writer emits complete HIP files).  The EvaluationService ``exec``s this text
— nothing else — so a hosted-LLM writer that emits hand-crafted source flows
through the identical path, and syntax/lowering failures surface as the
platform's compile-error feedback.

Every module defines:
  GENOME : json string (submission metadata; the analytic timing backend
           reads it — the wall-clock backend ignores it)
  run(a, b, a_scale, b_scale, interpret) -> C[bf16]

The evaluator passes ``interpret`` explicitly (true only on the CPU backend),
so a kernel is never run in interpret mode on a TPU.
"""
from __future__ import annotations

from .genome import KernelGenome

_HEADER = '''\
"""Auto-generated by the GPU Kernel Scientist writer.

{description}
"""
GENOME = {genome_json!r}

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SCALE_BLOCK = 128


def _pad_axis(x, multiple, axis):
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)
'''

_LIBRARY_BODY = '''

def run(a, b, a_scale, b_scale, interpret):
    """Library path: full f32 dequantization, then one XLA dot."""
    m, k = a.shape
    n = b.shape[1]
    kb = k // SCALE_BLOCK
    a32 = a.astype(jnp.float32).reshape(m, kb, SCALE_BLOCK)
    a32 = (a32 * a_scale.astype(jnp.float32)[:, :, None]).reshape(m, k)
    b32 = b.astype(jnp.float32).reshape(kb, SCALE_BLOCK, n // SCALE_BLOCK, SCALE_BLOCK)
    b32 = (b32 * b_scale.astype(jnp.float32)[:, None, :, None]).reshape(k, n)
    out = jnp.dot(a32, b32, preferred_element_type=jnp.float32)
    return out.astype(jnp.bfloat16)
'''

_MONOLITH_BODY = '''

def run(a, b, a_scale, b_scale, interpret):
    """Single-program kernel: whole problem resident in VMEM (no blocking)."""
    m, k = a.shape
    n = b.shape[1]
    n_sub = k // SCALE_BLOCK

    def body(a_ref, b_ref, as_ref, bs_ref, o_ref):
        a32 = a_ref[...].astype(jnp.float32).reshape(m, n_sub, SCALE_BLOCK)
        a32 = a32 * as_ref[...].astype(jnp.float32)[:, :, None]
        b32 = b_ref[...].astype(jnp.float32).reshape(n_sub, SCALE_BLOCK, n)
        bs = bs_ref[...].astype(jnp.float32)
        b32 = b32 * jnp.repeat(bs, SCALE_BLOCK, axis=1)[:, None, :]
        o_ref[...] = jnp.dot(
            a32.reshape(m, k), b32.reshape(k, n),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.bfloat16),
        interpret=interpret,
    )(a, b, a_scale, b_scale)
'''

_BLOCKED_BODY = '''
BLOCK_M = {bm}
BLOCK_N = {bn}
BLOCK_K = {bk}
K_SPLIT = {ks}
COMPUTE_DTYPE = jnp.{cdtype}
ACC_DTYPE = jnp.{adtype}
OUT_DTYPE = jnp.{odtype}
GRID_ORDER = {grid_order!r}
SCALE_APPLICATION = {scale_application!r}
DIMENSION_SEMANTICS = {dimension_semantics!r}


def _body(a_ref, b_ref, as_ref, bs_ref, o_ref, acc_ref, *, k_steps, n_sub,
          k_axis):
    k_idx = pl.program_id(k_axis)

    @pl.when(k_idx == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    a_s = as_ref[0]  # (bm, n_sub) f32
    b_s = bs_ref[0]  # (n_sub, bn) f32, one scale per column

    acc = acc_ref[...]
    for s in range(n_sub):
        a_blk = a[:, s * SCALE_BLOCK:(s + 1) * SCALE_BLOCK]
        b_blk = b[s * SCALE_BLOCK:(s + 1) * SCALE_BLOCK, :]
        b_s_cols = b_s[s:s + 1, :]
        if SCALE_APPLICATION == "dequant_inputs":
            a_d = (a_blk.astype(jnp.float32) * a_s[:, s:s + 1]).astype(COMPUTE_DTYPE)
            b_d = (b_blk.astype(jnp.float32) * b_s_cols).astype(COMPUTE_DTYPE)
            acc = acc + jnp.dot(a_d, b_d, preferred_element_type=ACC_DTYPE)
        else:  # scale_acc — raw values are exact in bf16; scale the accumulator
            part = jnp.dot(a_blk.astype(COMPUTE_DTYPE), b_blk.astype(COMPUTE_DTYPE),
                           preferred_element_type=ACC_DTYPE)
            acc = acc + part * a_s[:, s:s + 1] * b_s_cols
    acc_ref[...] = acc

    @pl.when(k_idx == k_steps - 1)
    def _store():
        # split-K output blocks carry a leading unit (slice) dimension
        o_ref[...] = acc_ref[...].astype(o_ref.dtype).reshape(o_ref.shape)


def run(a, b, a_scale, b_scale, interpret):
    m_in, k_in = a.shape
    n_in = b.shape[1]
    # clamp blocks to (128-padded) problem dims, then pad to block multiples
    bm = min(BLOCK_M, -(-m_in // 128) * 128)
    bn = min(BLOCK_N, -(-n_in // 128) * 128)
    bk = min(BLOCK_K, -(-k_in // 128) * 128)
    a = _pad_axis(_pad_axis(a, bm, 0), bk, 1)
    b = _pad_axis(_pad_axis(b, bk, 0), bn, 1)
    a_scale = _pad_axis(_pad_axis(a_scale, bm, 0), bk // SCALE_BLOCK, 1)
    b_scale = _pad_axis(_pad_axis(b_scale, bk // SCALE_BLOCK, 0),
                        bn // SCALE_BLOCK, 1)
    m, k = a.shape
    n = b.shape[1]
    n_sub = bk // SCALE_BLOCK
    gm, gn = m // bm, n // bn
    gk_total = k // bk
    ks = min(K_SPLIT, gk_total)
    # split-K slices must tile the K loop evenly; fall back when they cannot
    while gk_total % ks:
        ks -= 1
    gk = gk_total // ks
    # per-K-step scale blocks whose last two dims meet Mosaic's tiling rule
    # (full dim or (8, 128) multiple): a (K/bk, M, n_sub), b expanded per
    # column to (K/bk, n_sub, N)
    a_scale = a_scale.astype(jnp.float32).reshape(m, gk_total, n_sub)
    a_scale = a_scale.transpose(1, 0, 2)
    b_scale = jnp.repeat(b_scale.astype(jnp.float32), SCALE_BLOCK, axis=1)
    b_scale = b_scale.reshape(gk_total, n_sub, n)

    # the K grid axis index depends on the *clamped* split factor
    body = functools.partial(_body, k_steps=gk, n_sub=n_sub,
                             k_axis=3 if ks > 1 else 2)

    def _imaps(split):
        # grid index order -> (i, j, s, kk) logical coordinates
        if GRID_ORDER == "mn":
            unpack = (lambda i, j, s, kk: (i, j, s, kk)) if split else \\
                     (lambda i, j, kk: (i, j, 0, kk))
        else:
            unpack = (lambda j, i, s, kk: (i, j, s, kk)) if split else \\
                     (lambda j, i, kk: (i, j, 0, kk))
        def wrap(f):
            def g(*ids):
                return f(*unpack(*ids))
            return g
        return wrap

    wrap = _imaps(ks > 1)
    imap_a = wrap(lambda i, j, s, kk: (i, s * gk + kk))
    imap_b = wrap(lambda i, j, s, kk: (s * gk + kk, j))
    imap_as = wrap(lambda i, j, s, kk: (s * gk + kk, i, 0))
    imap_bs = wrap(lambda i, j, s, kk: (s * gk + kk, 0, j))

    if ks > 1:
        grid = (gm, gn, ks, gk) if GRID_ORDER == "mn" else (gn, gm, ks, gk)
        imap_o = wrap(lambda i, j, s, kk: (s, i, j))
        out = pl.pallas_call(
            body,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), imap_a),
                pl.BlockSpec((bk, bn), imap_b),
                pl.BlockSpec((1, bm, n_sub), imap_as),
                pl.BlockSpec((1, n_sub, bn), imap_bs),
            ],
            out_specs=pl.BlockSpec((1, bm, bn), imap_o),
            out_shape=jax.ShapeDtypeStruct((ks, m, n), ACC_DTYPE),
            scratch_shapes=[pltpu.VMEM((bm, bn), ACC_DTYPE)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=tuple(DIMENSION_SEMANTICS[:2])
                + ("arbitrary", "arbitrary")),
            interpret=interpret,
        )(a, b, a_scale, b_scale)
        out = jnp.sum(out, axis=0).astype(OUT_DTYPE)
    else:
        grid = (gm, gn, gk) if GRID_ORDER == "mn" else (gn, gm, gk)
        imap_o = wrap(lambda i, j, s, kk: (i, j))
        out = pl.pallas_call(
            body,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), imap_a),
                pl.BlockSpec((bk, bn), imap_b),
                pl.BlockSpec((1, bm, n_sub), imap_as),
                pl.BlockSpec((1, n_sub, bn), imap_bs),
            ],
            out_specs=pl.BlockSpec((bm, bn), imap_o),
            out_shape=jax.ShapeDtypeStruct((m, n), OUT_DTYPE),
            scratch_shapes=[pltpu.VMEM((bm, bn), ACC_DTYPE)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=tuple(DIMENSION_SEMANTICS)),
            interpret=interpret,
        )(a, b, a_scale, b_scale)
    return out[:m_in, :n_in]
'''


def render_source(genome: KernelGenome, description: str = "") -> str:
    header = _HEADER.format(
        description=description or genome.describe(),
        genome_json=genome.to_json(),
    )
    if genome.style == "library":
        return header + _LIBRARY_BODY
    if genome.style == "naive":
        return header + _MONOLITH_BODY
    return header + _BLOCKED_BODY.format(
        bm=genome.block_m,
        bn=genome.block_n,
        bk=genome.block_k,
        ks=genome.k_split,
        cdtype=genome.compute_dtype,
        adtype=genome.acc_dtype,
        odtype=genome.out_dtype,
        grid_order=genome.grid_order,
        scale_application=genome.scale_application,
        dimension_semantics=tuple(genome.dimension_semantics),
    )


def load_kernel(source: str):
    """'Compile' submitted source text: exec in a fresh namespace and return
    (run, genome_json).  Raises on any syntax/runtime error — the
    EvaluationService converts that into platform compile-error feedback."""
    ns: dict = {}
    exec(compile(source, "<submitted-kernel>", "exec"), ns)
    run = ns.get("run")
    if run is None:
        raise ValueError("submission defines no run(a, b, a_scale, b_scale)")
    return run, ns.get("GENOME")
