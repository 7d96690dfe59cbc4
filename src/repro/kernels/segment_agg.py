"""Pallas TPU kernel: closed-form per-segment aggregates.

The device counterpart of ``core.segment_algebra`` (the numpy path the
analytics engine runs on the host today — this route is validated against
its jnp oracle but not yet wired into a production query path): given the
knowledge base's member segments as per-row line parameters (origin
``theta``, slope ``s``) and a query's per-segment local overlap window
``[a, b)``, emit each segment's exact contribution to the aggregate —
sum, sum of squares, min, max — using the closed forms

    sum   = m*theta + s*(S1(b) - S1(a))        S1(x) = x(x-1)/2
    sumsq = m*theta^2 + 2 theta s (S1(b)-S1(a)) + s^2 (S2(b)-S2(a))
                                                S2(x) = x(x-1)(2x-1)/6
    min/max at the window endpoints (segments are monotone).

One VPU-elementwise pass over M segment rows: no per-sample work at all,
which is the whole point — a batch of aggregate queries over S series
maps to one [M, 1]-column kernel launch regardless of how many million
samples the segments cover.  Rows with b <= a (no overlap) emit the
aggregate identity (0 sums, +inf/-inf extrema).  The jnp oracle lives in
``ref.segment_agg_ref``; the numpy host path is
``core.segment_algebra.base_aggregate``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["segment_agg_kernel", "segment_agg_pallas"]

_BIG = 3.4e38  # f32 +-inf stand-in, same sentinel as the cone-scan kernel


def segment_agg_kernel(theta_ref, slope_ref, a_ref, b_ref, sum_ref, sumsq_ref,
                       min_ref, max_ref):
    theta = theta_ref[...]  # (bm, 1)
    slope = slope_ref[...]
    a = a_ref[...]
    b = b_ref[...]
    m = jnp.maximum(b - a, 0.0)
    d1 = (b * (b - 1.0) - a * (a - 1.0)) * 0.5
    d2 = (b * (b - 1.0) * (2.0 * b - 1.0) - a * (a - 1.0) * (2.0 * a - 1.0)) / 6.0
    live = m > 0.0
    sum_ref[...] = jnp.where(live, m * theta + slope * d1, 0.0)
    sumsq_ref[...] = jnp.where(
        live, m * theta * theta + 2.0 * theta * slope * d1 + slope * slope * d2, 0.0
    )
    va = theta + slope * a
    vb = theta + slope * (b - 1.0)
    min_ref[...] = jnp.where(live, jnp.minimum(va, vb), _BIG)
    max_ref[...] = jnp.where(live, jnp.maximum(va, vb), -_BIG)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def segment_agg_pallas(
    theta: jax.Array,
    slope: jax.Array,
    a: jax.Array,
    b: jax.Array,
    block_m: int = 256,
    *,
    interpret: bool,
):
    """theta/slope/a/b [M, 1] per-segment line params + local overlap window
    ([a, b), floats).  Returns (sum, sumsq, min, max), each [M, 1]; rows
    with b <= a emit the aggregate identity (0, 0, +BIG, -BIG)."""
    m = theta.shape[0]
    bm = min(block_m, m)
    grid = (pl.cdiv(m, bm),)
    col = pl.BlockSpec((bm, 1), lambda i: (i, 0))
    return pl.pallas_call(
        segment_agg_kernel,
        grid=grid,
        in_specs=[col, col, col, col],
        out_specs=[col, col, col, col],
        out_shape=[jax.ShapeDtypeStruct((m, 1), theta.dtype)] * 4,
        interpret=interpret,
    )(theta, slope, a, b)
