"""Pallas TPU kernels for the perf-critical hot spots, with pure-jnp oracles.

Layout per the repo convention: ``<name>.py`` holds the ``pl.pallas_call`` +
BlockSpec implementation, ``ops.py`` the jit'd public wrappers, ``ref.py``
the oracles.  ``scaled_gemm`` is the paper's target kernel (the AMD
challenge fp8 block-scaled GEMM, adapted to the TPU memory hierarchy).
"""
import jax


def default_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: only on the CPU backend.

    Every kernel's ``interpret=None`` resolves here, so a TPU never runs a
    kernel interpreted unless a caller asks for it explicitly."""
    return jax.default_backend() == "cpu"


# Submodules import default_interpret from this package, so it is defined
# before they are imported.
from . import ops, ref  # noqa: E402,F401
from .flash_attention import decode_attention, flash_attention  # noqa: E402,F401
from .scaled_gemm import naive_scaled_gemm, scaled_gemm  # noqa: E402,F401
from .ssd import ssd  # noqa: E402,F401
