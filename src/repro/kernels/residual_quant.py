"""Pallas TPU kernel: fused residual computation + quantization.

The bit-level half of SHRINK as it runs *on device*: given a per-row linear
base (theta + slope * t) over blocks of a flattened tensor, compute the
residual, quantize it to a small signed integer with step ``step``, clip to
[-qmax, qmax], and emit the quantization error (error feedback for the
gradient-compression path).  Everything is one VMEM-resident fused pass —
on TPU this is a single elementwise pipeline through the VPU with no HBM
round-trip between the subtract / scale / round / clip stages.

Tiling: rows of the block matrix map to sublanes, the in-block time axis to
lanes; the block shape is (BM, N) with N the (128-multiple) SHRINK block
length, so one grid step owns BM complete blocks and the base parameters
for a grid step are a (BM, 1) column.

Ragged tails: an optional per-row valid length masks each row past its
length — padded positions emit q = 0 (no symbols for the entropy stage) and
err = 0 (no error feedback from data that does not exist).  This is the
same valid-length mask idiom as the cone-scan kernel, applied to the
residual side so a ragged batch's padded lanes stay inert end to end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "residual_quant_kernel",
    "residual_quant_pallas",
    "pyramid_quant_kernel",
    "pyramid_quant_pallas",
]


def residual_quant_kernel(
    x_ref, theta_ref, slope_ref, step_ref, len_ref, q_ref, err_ref, *, qmax: int
):
    x = x_ref[...]
    theta = theta_ref[...]  # (bm, 1)
    slope = slope_ref[...]  # (bm, 1)
    step = step_ref[...]  # (bm, 1)
    n = x.shape[-1]
    t = jax.lax.broadcasted_iota(x.dtype, (1, n), 1)
    pred = theta + slope * t
    r = x - pred
    inv = 1.0 / step
    q = jnp.clip(jnp.round(r * inv), -qmax, qmax)
    valid = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) < len_ref[...]  # (bm, 1)
    q_ref[...] = jnp.where(valid, q, 0.0).astype(jnp.int32)
    err_ref[...] = jnp.where(valid, r - q * step, 0.0)


def pyramid_quant_kernel(
    x_ref, theta_ref, slope_ref, steps_ref, len_ref, q_ref, err_ref, *, qmax: int,
    num_layers: int,
):
    """Fused multi-layer refinement quantization: one VMEM-resident pass
    computes the base prediction once and runs the whole layer ladder on
    the residual without ever spilling the intermediate error to HBM —
    layer l quantizes what layers 0..l-1 left behind (the device analogue
    of ``core.residuals.quantize_pyramid``'s ladder).  The layer loop is a
    static python loop, so the VPU sees one straight-line elementwise
    pipeline of L round/clip/subtract stages."""
    x = x_ref[...]
    theta = theta_ref[...]  # (bm, 1)
    slope = slope_ref[...]  # (bm, 1)
    n = x.shape[-1]
    t = jax.lax.broadcasted_iota(x.dtype, (1, n), 1)
    pred = theta + slope * t
    e = x - pred
    valid = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) < len_ref[...]  # (bm, 1)
    for l in range(num_layers):
        step = steps_ref[0, l]
        q = jnp.clip(jnp.round(e / step), -qmax, qmax)
        e = e - q * step
        q_ref[l, ...] = jnp.where(valid, q, 0.0).astype(jnp.int32)
    err_ref[...] = jnp.where(valid, e, 0.0)


@functools.partial(jax.jit, static_argnames=("qmax", "block_m", "interpret"))
def pyramid_quant_pallas(
    x: jax.Array,
    theta: jax.Array,
    slope: jax.Array,
    steps: jax.Array,
    lengths: jax.Array | None = None,
    qmax: int = 127,
    block_m: int = 8,
    *,
    interpret: bool,
):
    """x[M, N]; theta/slope[M, 1]; steps[L] (coarse -> fine).  Returns
    (qs int32 [L, M, N], err [M, N]): the per-layer refinement symbols and
    the error left after the finest layer.  ``lengths`` [M] masks ragged
    row tails (all layers' q and err forced to 0 past each row's
    length)."""
    m, n = x.shape
    num_layers = int(steps.shape[0])
    if lengths is None:
        lengths = jnp.full((m,), n, jnp.int32)
    len_in = jnp.asarray(lengths, jnp.int32).reshape(m, 1)
    steps_in = jnp.asarray(steps, x.dtype).reshape(1, num_layers)
    bm = min(block_m, m)
    grid = (pl.cdiv(m, bm),)
    kernel = functools.partial(pyramid_quant_kernel, qmax=qmax, num_layers=num_layers)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, num_layers), lambda i: (0, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((num_layers, bm, n), lambda i: (0, i, 0)),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_layers, m, n), jnp.int32),
            jax.ShapeDtypeStruct((m, n), x.dtype),
        ],
        interpret=interpret,
    )(x, theta, slope, steps_in, len_in)


@functools.partial(jax.jit, static_argnames=("qmax", "block_m", "interpret"))
def residual_quant_pallas(
    x: jax.Array,
    theta: jax.Array,
    slope: jax.Array,
    step: jax.Array,
    lengths: jax.Array | None = None,
    qmax: int = 127,
    block_m: int = 8,
    *,
    interpret: bool,
):
    """x[M, N]; theta/slope/step[M, 1].  Returns (q int32[M,N], err[M,N]).
    ``lengths`` [M] marks ragged row tails (q/err forced to 0 past each
    row's length); None means every row is fully valid."""
    m, n = x.shape
    if lengths is None:
        lengths = jnp.full((m,), n, jnp.int32)
    len_in = jnp.asarray(lengths, jnp.int32).reshape(m, 1)
    bm = min(block_m, m)
    grid = (pl.cdiv(m, bm),)
    kernel = functools.partial(residual_quant_kernel, qmax=qmax)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.int32),
            jax.ShapeDtypeStruct((m, n), x.dtype),
        ],
        interpret=interpret,
    )(x, theta, slope, step, len_in)
