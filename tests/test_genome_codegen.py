"""Genome invariants (hypothesis) + generated-source correctness."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import codegen
from repro.core.genome import SEED_LIBRARY, SEED_MXU, SEED_NAIVE, KernelGenome
from repro.kernels import ref

blocks = st.sampled_from([128, 256, 512, 1024])
genomes = st.builds(
    KernelGenome,
    style=st.just("blocked"),
    block_m=blocks, block_n=blocks, block_k=blocks,
    grid_order=st.sampled_from(["mn", "nm"]),
    scale_application=st.sampled_from(["scale_acc", "dequant_inputs"]),
    compute_dtype=st.sampled_from(["bfloat16", "float32"]),
    k_split=st.sampled_from([1, 2, 4]),
)


@settings(max_examples=30, deadline=None)
@given(genomes)
def test_json_roundtrip(g):
    assert KernelGenome.from_json(g.to_json()) == g


@settings(max_examples=30, deadline=None)
@given(genomes)
def test_valid_genomes_have_bounded_vmem(g):
    if not g.validate():
        assert g.vmem_bytes() <= 96 * 2**20


@settings(max_examples=10, deadline=None)
@given(genomes)
def test_generated_source_is_correct(g):
    """Every legal genome's rendered source computes the right answer."""
    if g.validate():
        return
    run, gj = codegen.load_kernel(codegen.render_source(g))
    assert KernelGenome.from_json(gj) == g
    rng = np.random.default_rng(0)
    m, k, n = 256, 256, 256
    a32 = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b32 = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    aq, a_s = ref.quantize_blockwise(a32)
    bq, b_s = ref.quantize_blockwise_2d(b32)
    want = ref.scaled_gemm(aq, bq, a_s, b_s).astype(jnp.float32)
    got = np.asarray(run(aq, bq, a_s, b_s, interpret=True), dtype=np.float32)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, np.asarray(want), atol=0.03 * scale)


def test_seed_sources_run():
    for g in (SEED_LIBRARY, SEED_NAIVE, SEED_MXU):
        run, _ = codegen.load_kernel(codegen.render_source(g))
        rng = np.random.default_rng(1)
        a32 = jnp.asarray(rng.standard_normal((128, 256)), jnp.float32)
        b32 = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
        aq, a_s = ref.quantize_blockwise(a32)
        bq, b_s = ref.quantize_blockwise_2d(b32)
        want = ref.scaled_gemm(aq, bq, a_s, b_s).astype(jnp.float32)
        got = np.asarray(run(aq, bq, a_s, b_s, interpret=True), dtype=np.float32)
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got, np.asarray(want), atol=0.03 * scale)


def test_invalid_vmem_rejected():
    g = KernelGenome(style="blocked", block_m=4096, block_n=4096,
                     block_k=4096)
    assert any("VMEM" in e for e in g.validate())


def test_unaligned_block_k_rejected():
    g = KernelGenome(style="blocked", block_k=192)
    assert any("block_k" in e for e in g.validate())
