"""Pallas TPU kernel: fused dequantize + reconstruct (inverse of
residual_quant).  pred = theta + slope * t; x_hat = pred + q * step.
One VPU pass, VMEM-tiled like residual_quant."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "dequant_kernel",
    "dequant_reconstruct_pallas",
    "pyramid_reconstruct_kernel",
    "pyramid_reconstruct_pallas",
]


def dequant_kernel(q_ref, theta_ref, slope_ref, step_ref, x_ref):
    q = q_ref[...]
    theta = theta_ref[...]
    slope = slope_ref[...]
    step = step_ref[...]
    n = q.shape[-1]
    t = jax.lax.broadcasted_iota(theta.dtype, (1, n), 1)
    x_ref[...] = theta + slope * t + q.astype(theta.dtype) * step


def pyramid_reconstruct_kernel(qs_ref, theta_ref, slope_ref, steps_ref, x_ref, *,
                               num_layers: int):
    """Fused inverse of pyramid_quant: pred + Σ_l q_l * step_l in one VPU
    pass — the layer sum never round-trips through HBM, so decoding a
    k-layer prefix costs one fused elementwise pipeline regardless of k."""
    theta = theta_ref[...]
    slope = slope_ref[...]
    n = qs_ref.shape[-1]
    t = jax.lax.broadcasted_iota(theta.dtype, (1, n), 1)
    acc = theta + slope * t
    for l in range(num_layers):
        acc = acc + qs_ref[l, ...].astype(theta.dtype) * steps_ref[0, l]
    x_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def pyramid_reconstruct_pallas(
    qs: jax.Array,
    theta: jax.Array,
    slope: jax.Array,
    steps: jax.Array,
    block_m: int = 8,
    *,
    interpret: bool,
):
    """qs int32 [L, M, N]; theta/slope [M, 1]; steps [L] -> x_hat [M, N].
    Pass a layer prefix (qs[:k+1], steps[:k+1]) to reconstruct at tier k."""
    num_layers, m, n = qs.shape
    steps_in = jnp.asarray(steps, theta.dtype).reshape(1, num_layers)
    bm = min(block_m, m)
    grid = (pl.cdiv(m, bm),)
    kernel = functools.partial(pyramid_reconstruct_kernel, num_layers=num_layers)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((num_layers, bm, n), lambda i: (0, i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, num_layers), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), theta.dtype),
        interpret=interpret,
    )(qs, theta, slope, steps_in)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def dequant_reconstruct_pallas(
    q: jax.Array,
    theta: jax.Array,
    slope: jax.Array,
    step: jax.Array,
    block_m: int = 8,
    *,
    interpret: bool,
):
    """q int32 [M, N]; theta/slope/step [M, 1] -> x_hat [M, N] (theta dtype)."""
    m, n = q.shape
    bm = min(block_m, m)
    grid = (pl.cdiv(m, bm),)
    return pl.pallas_call(
        dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), theta.dtype),
        interpret=interpret,
    )(q, theta, slope, step)
