"""Jit'd public wrappers for the SHRINK Pallas kernels.

Backend selection: on a TPU the kernels run compiled (Mosaic,
``interpret=False``), and a kernel that fails to compile raises.  On any
other backend (the CPU test container) they run in Pallas ``interpret=True``
mode — the kernel body runs as traced JAX ops with the same block/grid
decomposition, which validates BlockSpec tiling and the sequential-grid
state carry.  ``force_ref=True`` routes to the pure-jnp oracle (used for
differentiable paths and in tests).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .calls import note_call
from .cone_scan import cone_scan_pallas, padded_lanes
from .flash_attention import flash_attention_pallas
from .dequant import dequant_reconstruct_pallas, pyramid_reconstruct_pallas
from .interval_stats import interval_stats_pallas
from .rans import decode_rows as rans_decode_rows
from .rans import encode_rows as rans_encode_rows
from .residual_quant import pyramid_quant_pallas, residual_quant_pallas
from .segment_agg import segment_agg_pallas

__all__ = [
    "flash_attention",
    "interval_stats",
    "residual_quant",
    "dequant_reconstruct",
    "pyramid_quant",
    "pyramid_reconstruct",
    "cone_scan",
    "cone_scan_segments",
    "rans_decode_rows",
    "rans_encode_rows",
    "segment_agg",
    "use_interpret",
]


def use_interpret() -> bool:
    """Interpret mode everywhere but on a TPU, where kernels compile."""
    return jax.default_backend() != "tpu"


def interval_stats(x: jax.Array, window: int, force_ref: bool = False):
    if force_ref:
        return ref.interval_stats_ref(x, window)
    return interval_stats_pallas(x, window, interpret=use_interpret())


def residual_quant(
    x: jax.Array,
    theta: jax.Array,
    slope: jax.Array,
    step: jax.Array,
    qmax: int = 127,
    force_ref: bool = False,
    lengths: jax.Array | None = None,
):
    """``lengths`` [M] marks ragged row tails: positions >= lengths[m] emit
    q = 0 / err = 0 so padded blocks contribute no symbols or feedback."""
    if force_ref:
        return ref.residual_quant_ref(x, theta, slope, step, qmax=qmax, lengths=lengths)
    return residual_quant_pallas(
        x, theta, slope, step, lengths=lengths, qmax=qmax, interpret=use_interpret()
    )


def dequant_reconstruct(
    q: jax.Array,
    theta: jax.Array,
    slope: jax.Array,
    step: jax.Array,
    force_ref: bool = False,
):
    if force_ref:
        return ref.dequant_reconstruct_ref(q, theta, slope, step)
    return dequant_reconstruct_pallas(q, theta, slope, step, interpret=use_interpret())


def pyramid_quant(
    x: jax.Array,
    theta: jax.Array,
    slope: jax.Array,
    steps: jax.Array,
    qmax: int = 127,
    force_ref: bool = False,
    lengths: jax.Array | None = None,
):
    """Fused multi-layer refinement quantization: layer l quantizes the
    error layers 0..l-1 left behind (steps[L] coarse -> fine).  Returns
    (qs int32 [L, M, N], err [M, N]).  ``lengths`` [M] marks ragged row
    tails: positions >= lengths[m] emit q = 0 on every layer and err = 0."""
    if force_ref:
        return ref.pyramid_quant_ref(x, theta, slope, steps, qmax=qmax, lengths=lengths)
    return pyramid_quant_pallas(
        x, theta, slope, steps, lengths=lengths, qmax=qmax, interpret=use_interpret()
    )


def pyramid_reconstruct(
    qs: jax.Array,
    theta: jax.Array,
    slope: jax.Array,
    steps: jax.Array,
    force_ref: bool = False,
):
    """Fused inverse of pyramid_quant: pred + Σ_l qs[l] * steps[l].  Feed a
    layer prefix (qs[:k+1], steps[:k+1]) to reconstruct at tier k."""
    if force_ref:
        return ref.pyramid_reconstruct_ref(qs, theta, slope, steps)
    return pyramid_reconstruct_pallas(
        qs, theta, slope, steps, interpret=use_interpret()
    )


def segment_agg(
    theta: jax.Array,
    slope: jax.Array,
    a: jax.Array,
    b: jax.Array,
    force_ref: bool = False,
):
    """Closed-form per-segment aggregates for compressed-domain analytics:
    theta/slope/a/b [M, 1] -> (sum, sumsq, min, max) [M, 1] of each
    segment's predictions over its local window [a, b) — O(segments), no
    per-sample work (rows with b <= a emit the aggregate identity)."""
    if force_ref:
        return ref.segment_agg_ref(theta, slope, a, b)
    return segment_agg_pallas(theta, slope, a, b, interpret=use_interpret())


def _bucket_rows(t: int, block_t: int) -> tuple[int, int]:
    """(padded T, time block) for a scan of T rows: T rounds up to a power
    of two, a whole number of time blocks."""
    tp = 1 << (t - 1).bit_length()
    bt = min(block_t, tp)
    return -(-tp // bt) * bt, bt


def _scan_bucketed(x, eps_hat, lengths, block_t: int):
    """The kernel at a bucketed shape, so few shapes compile: T by
    ``_bucket_rows``, S to ``padded_lanes(S)``.  Edge padding repeats the
    last row and lane; the repeated rows lie past every lane's length, so
    the mask keeps them inert, and the repeated lanes are the caller's to
    drop.  Numpy input is padded on the host, device arrays on the
    device."""
    t, s = x.shape
    if lengths is None:
        lengths = np.full((s,), t, np.int32)
    tp, bt = _bucket_rows(t, block_t)
    rows_lanes = ((0, tp - t), (0, padded_lanes(s) - s))

    def pad(a, widths):
        return (np if isinstance(a, np.ndarray) else jnp).pad(a, widths, mode="edge")

    out = cone_scan_pallas(
        pad(x, rows_lanes), pad(eps_hat, rows_lanes), pad(lengths, rows_lanes[1:]),
        block_t=bt, interpret=use_interpret(),
    )
    note_call("cone_scan", out[0])
    return out


def cone_scan(
    x: jax.Array,
    eps_hat: jax.Array,
    block_t: int = 256,
    force_ref: bool = False,
    lengths: jax.Array | None = None,
):
    """``lengths`` [S] activates the valid-length mask path for ragged lanes
    (positions past a lane's length are inert); None = all lanes full."""
    if force_ref:
        return ref.cone_scan_ref(x, eps_hat, lengths=lengths)
    t, s = x.shape
    brk, theta, lo, hi, fin_lo, fin_hi = _scan_bucketed(x, eps_hat, lengths, block_t)
    return (brk[:t, :s], theta[:t, :s], lo[:t, :s], hi[:t, :s],
            fin_lo[:, :s], fin_hi[:, :s])


@jax.jit
def _compact_segments(brk, theta, psi_lo, psi_hi, fin_lo, fin_hi):
    """Dense per-point scan outputs -> per-series segment records, in XLA.

    brk/theta/psi_*[T, S].  Returns (counts[S], t0s[T, S], thetas[T, S],
    lo[T, S], hi[T, S]) where row k of each [T, S] array describes segment k
    of that series (rows >= counts[s] are padding).  The scatter is a cumsum
    over break flags — O(T) with no host round-trip.
    """
    t_len, s_len = brk.shape
    seg_of_t = jnp.cumsum(brk, axis=0) - 1  # segment index at each point
    cols = jnp.broadcast_to(jnp.arange(s_len)[None, :], (t_len, s_len))
    is_brk = brk.astype(bool)
    # scatter rows: break positions land at their segment's slot; everything
    # else goes to a dump row at index t_len
    rows = jnp.where(is_brk, seg_of_t, t_len)
    tpos = jnp.broadcast_to(jnp.arange(t_len)[:, None], (t_len, s_len))
    t0s = jnp.zeros((t_len + 1, s_len), jnp.int32).at[rows, cols].set(tpos)
    thetas = jnp.zeros((t_len + 1, s_len), theta.dtype).at[rows, cols].set(theta)
    # the span recorded at break t closes segment seg_of_t[t] - 1
    close_rows = jnp.where(is_brk & (seg_of_t > 0), seg_of_t - 1, t_len)
    lo = jnp.zeros((t_len + 1, s_len), psi_lo.dtype).at[close_rows, cols].set(psi_lo)
    hi = jnp.zeros((t_len + 1, s_len), psi_hi.dtype).at[close_rows, cols].set(psi_hi)
    counts = brk.sum(axis=0)
    # the still-open segment's span comes from the final carry
    lo = lo.at[counts - 1, jnp.arange(s_len)].set(fin_lo[0])
    hi = hi.at[counts - 1, jnp.arange(s_len)].set(fin_hi[0])
    return counts, t0s[:t_len], thetas[:t_len], lo[:t_len], hi[:t_len]


def cone_scan_segments(
    x: jax.Array,
    eps_hat: jax.Array,
    block_t: int = 256,
    lengths: jax.Array | None = None,
):
    """Lane-parallel cone scan + on-device segment compaction.

    x[T, S], eps_hat[T, S] -> (counts[S'], t0s[T', S'], thetas[T', S'],
    psi_lo[T', S'], psi_hi[T', S']), at the bucketed shape T' >= T,
    S' >= S the scan ran at (lanes >= S are padding); row k of the
    [T', S'] outputs is segment k of that series.  Spans use +-3.4e38 as
    the unbounded sentinel (map to inf on the host).  Segment lengths
    follow from consecutive t0s (and the lane end for the last segment),
    since each lane's segments partition [0, lengths[s]).

    ``lengths`` [S] (default: T for every lane) is the valid-length mask for
    ragged lanes: positions past a lane's length are inert, so arbitrary
    padding up to T never creates segments or pollutes the open segment's
    fin_lo/fin_hi carry.
    """
    return _compact_segments(*_scan_bucketed(x, eps_hat, lengths, block_t))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
                    force_ref: bool = False):
    """Multi-head flash attention: q/k/v [B, H, S, D] (vmapped over B, H)."""
    if force_ref:
        fn = lambda qq, kk, vv: ref.flash_attention_ref(qq, kk, vv, causal)
    else:
        fn = lambda qq, kk, vv: flash_attention_pallas(
            qq, kk, vv, causal=causal, interpret=use_interpret()
        )
    return jax.vmap(jax.vmap(fn))(q, k, v)
