"""Pallas TPU kernel: per-window min/max reduction (Alg. 2's interval stats).

Computes the local value range of every length-W window of S independent
series — the input to the adaptive threshold of Eq. 4 (beta = delta_local /
delta_global).  Time is the sublane axis, series are lanes; each grid step
reduces one (W, S-tile) window in VMEM.  On TPU this is a strided VPU
reduction with no cross-lane traffic (each lane is its own series).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["interval_stats_kernel", "interval_stats_pallas"]


def interval_stats_kernel(x_ref, min_ref, max_ref):
    x = x_ref[...]  # (W, bs)
    min_ref[...] = x.min(axis=0, keepdims=True)
    max_ref[...] = x.max(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("window", "block_s", "interpret"))
def interval_stats_pallas(
    x: jax.Array,
    window: int,
    block_s: int = 128,
    *,
    interpret: bool,
):
    """x[T, S] -> (mins[T//W, S], maxs[T//W, S]).  T % window == 0."""
    t, s = x.shape
    assert t % window == 0, f"T={t} % window={window} != 0"
    nw = t // window
    bs = min(block_s, s)
    grid = (nw, pl.cdiv(s, bs))
    return pl.pallas_call(
        interval_stats_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((window, bs), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((1, bs), lambda i, j: (i, j)),
            pl.BlockSpec((1, bs), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nw, s), x.dtype),
            jax.ShapeDtypeStruct((nw, s), x.dtype),
        ],
        interpret=interpret,
    )(x)
