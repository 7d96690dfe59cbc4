"""Pallas TPU kernel: the SHRINK shrinking-cone scan.

Hardware adaptation (DESIGN.md §3): the paper's cone scan is a sequential,
data-dependent recurrence — on a GPU one would serialize a warp; on TPU the
idiomatic equivalent exploits two facts:

1. **The TPU grid executes sequentially**, so VMEM/SMEM scratch persists
   across grid steps.  The cone state (theta, psi_lo, psi_hi, t0, eps_seg)
   lives in VMEM scratch and is carried from one time-chunk to the next —
   no HBM round-trip for the recurrence state.
2. **Lanes give free parallelism across series.**  An IoT gateway compresses
   thousands of independent streams; each lane carries one stream, so every
   per-point update is a (1, BS) vector op on the VPU.  Lanes are tiled
   512 wide (grid axis 0), so a (block_t, 512) tile of each stream fits
   VMEM however many series the batch holds; the serial dimension is
   T/BT grid steps × BT in-kernel iterations per lane block.

Ragged lanes (the gateway's real regime — series lengths span orders of
magnitude) ride the same kernel through the **valid-length mask path**: a
per-lane length vector freezes a lane's cone state at positions
``t >= lengths[s]``, so padding can never constrain, break, or seed a cone
and the final-span carry reflects the open segment at each lane's own end.
A lane with ``lengths[s] == T`` behaves exactly as the unmasked scan.

Outputs are dense per-point arrays (break flags + segment records at break
positions); the variable-length segment compaction (a cumsum gather) happens
in XLA outside the kernel, as does base merging on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["cone_scan_pallas", "padded_lanes"]

_BIG = 3.4e38
_LANES = 128
_BLOCK_S = 512  # lanes per block: a (256, 512) f32 tile is 512 KiB of VMEM


def padded_lanes(s: int) -> int:
    """The lane count ``cone_scan_pallas`` takes for ``s`` series: whole
    128-lane groups up to one block, whole blocks beyond."""
    sp = -(-s // _LANES) * _LANES
    return sp if sp <= _BLOCK_S else -(-s // _BLOCK_S) * _BLOCK_S


def _cone_scan_kernel(
    x_ref,
    eps_ref,
    len_ref,  # (1, BS) int32: valid samples per lane
    brk_ref,
    theta_ref,
    lo_out_ref,
    hi_out_ref,
    fin_lo_ref,
    fin_hi_ref,
    state_f_ref,  # VMEM (4, BS): theta, lo, hi, eps_seg
    state_i_ref,  # VMEM (1, BS) int32: t0
    *,
    block_t: int,
):
    # grid = (lane blocks, time blocks): time is the inner, sequential axis,
    # so the carry restarts at each lane block's first time block.  Rows are
    # read and written as (1, BS) slices: Mosaic tiles rank-2 arrays only.
    i = pl.program_id(1)
    bs = x_ref.shape[1]

    def row(r):
        return pl.ds(r, 1)

    @pl.when(i == 0)
    def _init():
        v0 = x_ref[row(0), :]
        e0 = eps_ref[row(0), :]
        state_f_ref[row(0), :] = jnp.floor(v0 / e0) * e0
        state_f_ref[row(1), :] = jnp.full((1, bs), -_BIG, x_ref.dtype)
        state_f_ref[row(2), :] = jnp.full((1, bs), _BIG, x_ref.dtype)
        state_f_ref[row(3), :] = e0
        state_i_ref[...] = jnp.zeros((1, bs), jnp.int32)

    lengths = len_ref[...]

    def body(r, carry):
        theta, lo, hi, eps_seg, t0 = carry
        t = i * block_t + r
        v = x_ref[row(r), :]
        eps_t = eps_ref[row(r), :]
        dt = (t - t0).astype(x_ref.dtype)
        denom = jnp.maximum(dt, 1.0)
        cand_hi = (v + eps_seg - theta) / denom
        cand_lo = (v - eps_seg - theta) / denom
        # dt == 0 is the segment's own start point (only t == 0 reaches here):
        # it defines theta, not a slope constraint — matching the host scan.
        # t >= lengths is a padded position: it freezes the lane entirely.
        grow = (dt > 0) & (t < lengths)
        new_hi = jnp.where(grow, jnp.minimum(hi, cand_hi), hi)
        new_lo = jnp.where(grow, jnp.maximum(lo, cand_lo), lo)
        brk = (new_lo > new_hi) & grow
        # records of the closing segment at the break position
        lo_out_ref[row(r), :] = lo
        hi_out_ref[row(r), :] = hi
        theta_new = jnp.floor(v / eps_t) * eps_t
        theta = jnp.where(brk, theta_new, theta)
        eps_seg = jnp.where(brk, eps_t, eps_seg)
        lo = jnp.where(brk, -_BIG, new_lo)
        hi = jnp.where(brk, _BIG, new_hi)
        t0 = jnp.where(brk, t, t0)
        brk_ref[row(r), :] = brk.astype(jnp.int32)
        theta_ref[row(r), :] = theta
        return theta, lo, hi, eps_seg, t0

    carry = (
        state_f_ref[row(0), :],
        state_f_ref[row(1), :],
        state_f_ref[row(2), :],
        state_f_ref[row(3), :],
        state_i_ref[...],
    )
    theta, lo, hi, eps_seg, t0 = jax.lax.fori_loop(0, block_t, body, carry)
    state_f_ref[row(0), :] = theta
    state_f_ref[row(1), :] = lo
    state_f_ref[row(2), :] = hi
    state_f_ref[row(3), :] = eps_seg
    state_i_ref[...] = t0
    # every time block writes; the sequential time axis means the last wins
    fin_lo_ref[...] = lo
    fin_hi_ref[...] = hi


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def cone_scan_pallas(
    x: jax.Array,
    eps_hat: jax.Array,
    lengths: jax.Array,
    block_t: int = 256,
    *,
    interpret: bool,
):
    """x[T, S], eps_hat[T, S], lengths[S] int32 -> (brk i32, theta, psi_lo,
    psi_hi, fin_lo[1,S], fin_hi[1,S]).  Semantics identical to
    ref.cone_scan_ref.  ``lengths`` holds each lane's valid samples; the
    positions past it are inert, whatever they hold.

    The shape must tile: T % block_t == 0 and S == padded_lanes(S), so
    lanes run in blocks of min(S, 512).  ``ops.cone_scan`` pads to that."""
    t, s = x.shape
    bt = min(block_t, t)
    bs = min(_BLOCK_S, s)
    assert t % bt == 0, f"T={t} % block_t={bt} != 0"
    assert s == padded_lanes(s), f"S={s} does not tile into lane blocks"
    tile = pl.BlockSpec((bt, bs), lambda j, i: (i, j))
    lane = pl.BlockSpec((1, bs), lambda j, i: (0, j))
    kernel = functools.partial(_cone_scan_kernel, block_t=bt)
    brk, theta, psi_lo, psi_hi, fin_lo, fin_hi = pl.pallas_call(
        kernel,
        grid=(s // bs, t // bt),
        in_specs=[tile, tile, lane],
        out_specs=[tile, tile, tile, tile, lane, lane],
        out_shape=[
            jax.ShapeDtypeStruct((t, s), jnp.int32),
            jax.ShapeDtypeStruct((t, s), x.dtype),
            jax.ShapeDtypeStruct((t, s), x.dtype),
            jax.ShapeDtypeStruct((t, s), x.dtype),
            jax.ShapeDtypeStruct((1, s), x.dtype),
            jax.ShapeDtypeStruct((1, s), x.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((4, bs), x.dtype),
            pltpu.VMEM((1, bs), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, eps_hat, jnp.asarray(lengths, jnp.int32).reshape(1, s))
    # match ref: brk[0] = 1, theta[0] = quantized origin (kernel already
    # wrote theta of the first segment at row 0 via the running state)
    return brk.at[0].set(1), theta, psi_lo, psi_hi, fin_lo, fin_hi
