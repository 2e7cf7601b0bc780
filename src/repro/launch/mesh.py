"""Mesh construction: every mesh in the repo is built here.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax use.
"""
from __future__ import annotations

import argparse
import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """A mesh over the first ``prod(shape)`` devices, with ``Auto`` axes.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which the
    in-graph ``with_sharding_constraint`` hints of ``repro.dist.partition``
    become typed shardings that ops such as dynamic-update-slice reject.
    """
    shape, axes = tuple(shape), tuple(axes)
    if devices is None:
        devices = jax.devices()[:math.prod(shape)]
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def parse_mesh_shape(text: str) -> tuple:
    """``"DxM"`` -> (D, M), for the launchers' ``--mesh`` options."""
    try:
        d, m = (int(v) for v in text.lower().split("x"))
        if d < 1 or m < 1:
            raise ValueError
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected DxM with positive ints, e.g. 2x4 (got {text!r})")
    return d, m


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None, *, multi_pod: bool = False):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = n_devices or len(jax.devices())
    if multi_pod and n >= 8:
        return make_mesh((2, 2, n // 4), ("pod", "data", "model"))
    if n == 1:
        return make_mesh((1, 1), ("data", "model"))
    return make_mesh((n // 2, 2), ("data", "model"))
