"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — dryrun.py must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import Mesh

V5E_PEAK_FLOPS = 197e12  # bf16 FLOP/s per chip
V5E_HBM_BW = 819e9  # bytes/s per chip
V5E_ICI_BW = 50e9  # bytes/s per link
V5E_HBM_BYTES = 16 * 1024**3  # 16 GiB per chip


def _make(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (tests use small ones, e.g. (2,2,2) on 8 host devices)."""
    return _make(shape, axes)
