"""Production mesh construction.

Single pod:  (16, 16)      axes ("data", "model")       = 256 chips (v5e pod)
Multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS for 512 host devices before any jax
import; smoke tests see 1 device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many devices the process has (tests)."""
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )


class HW:
    """TPU v5e-like hardware model for the roofline (per chip)."""

    PEAK_BF16_FLOPS = 197e12  # FLOP/s
    HBM_BW = 819e9  # B/s
    ICI_BW = 50e9  # B/s per link
    HBM_BYTES = 16 * 1024**3
