"""Ahead-of-time compiles for a described TPU v5e (no chip needed).

The TPU compiler is installed with jaxlib; it compiles for a v5e that is
described by ``jax.experimental.topologies`` but not attached.  This catches
what interpret mode cannot: Mosaic's block-tiling rule, VMEM limits, and
programs that do not fit the device's 16 GB.  Nothing here runs, so nothing
here says anything about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  Keep these compiles in this one file.
"""
import functools
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import codegen
from repro.core.genome import SEED_MXU, KernelGenome
from repro.models import api

#: v5e HBM as XLA reports it (16 GB nominal).
V5E_HBM_BYTES = 15.75 * 2**30
# (M, N, K) of the first BENCH_CONFIGS_6 entry
GEMM_MNK = (1024, 1536, 7168)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _gemm_args(one_chip, m, n, k):
    fp8 = jnp.float8_e4m3fn
    return (_sds(one_chip, (m, k), fp8), _sds(one_chip, (k, n), fp8),
            _sds(one_chip, (m, k // 128), jnp.float32),
            _sds(one_chip, (k // 128, n // 128), jnp.float32))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_device_kind_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


def test_scaled_gemm_compiles_for_v5e(one_chip):
    sg = importlib.import_module("repro.kernels.scaled_gemm")
    m, n, k = GEMM_MNK
    compiled = _compile(functools.partial(sg.scaled_gemm, interpret=False),
                        *_gemm_args(one_chip, m, n, k))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("genome", [
    SEED_MXU,
    KernelGenome(block_m=2048, block_n=256, block_k=512),  # kernel_micro's
], ids=["seed_mxu", "blocked_2048x256x512"])
def test_codegen_template_compiles_for_v5e(one_chip, genome):
    run, _ = codegen.load_kernel(codegen.render_source(genome))
    compiled = _compile(functools.partial(run, interpret=False),
                        *_gemm_args(one_chip, *GEMM_MNK))
    assert "tpu_custom_call" in compiled.as_text()


def test_attention_kernels_compile_at_qwen_head_shapes(one_chip):
    from repro.kernels import decode_attention, flash_attention
    cfg = configs.get_config("qwen2.5-3b")
    h, hkv, dh, s = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_, 2048
    bf16 = jnp.bfloat16
    q = _sds(one_chip, (1, h, s, dh), bf16)
    kv = _sds(one_chip, (1, hkv, s, dh), bf16)
    compiled = _compile(functools.partial(flash_attention, interpret=False),
                        q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()

    slots = 8
    q1 = _sds(one_chip, (slots, h, dh), bf16)
    kv = _sds(one_chip, (slots, hkv, s, dh), bf16)
    kv_len = _sds(one_chip, (slots,), jnp.int32)
    compiled = _compile(functools.partial(decode_attention, interpret=False),
                        q1, kv, kv, kv_len)
    assert "tpu_custom_call" in compiled.as_text()


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_qwen_full_width_serving_fits_one_v5e(one_chip, kind):
    """The serving phase's programs at published widths: 8 slots of 2048
    positions, prefill of one 24-token prompt, one decode step."""
    cfg = configs.get_config("qwen2.5-3b")
    slots, max_seq = 8, 2048
    on_chip = functools.partial(jax.tree.map,
                                lambda x: _sds(one_chip, x.shape, x.dtype))
    params = on_chip(api.abstract_params(cfg))
    if kind == "prefill":
        batch = {"tokens": _sds(one_chip, (1, 24), jnp.int32)}
        compiled = _compile(lambda p, b: api.prefill(p, cfg, b, max_seq),
                            params, batch)
    else:
        cache = on_chip(jax.eval_shape(
            lambda: api.init_cache(cfg, slots, max_seq)))
        tokens = _sds(one_chip, (slots,), jnp.int32)
        compiled = _compile(lambda p, c, t: api.decode_step(p, cfg, c, t),
                            params, cache, tokens)
    assert 0 < _bytes(compiled) < V5E_HBM_BYTES, compiled.memory_analysis()
