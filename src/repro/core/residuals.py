"""Residuals encoding (Alg. 6 + Eq. 6 of the paper).

Residuals are the element-wise difference between the original values and the
base (candidate-line) reconstruction.  Two quantization modes:

* ``midpoint`` (lossy): step = 2*eps_r, q = floor((r - r_lo)/step), dequant
  at the bin midpoint -> max abs error eps_r.  (The paper's Eq. 6 uses step
  eps_r with left-edge reconstruction, max error < eps_r; the midpoint
  variant meets the same |err| <= eps_r guarantee with half the symbol count,
  i.e. strictly better CR at equal guarantee.  Both satisfy Def. 1.)
* ``exact`` (lossless): for series with a fixed number of decimal places d,
  work in the integer domain at scale 10^d so reconstruction is bit-exact
  after rounding to d decimals.
"""
from __future__ import annotations

import numpy as np

from . import entropy
from .types import Base, ResidualStream
from .base import base_predictions

__all__ = [
    "compute_residuals",
    "quantize_residuals",
    "quantize_residuals_batch",
    "dequantize_residuals",
    "quantize_exact",
    "quantize_exact_batch",
    "dequantize_exact",
    "normalize_tiers",
    "quantize_pyramid",
    "quantize_pyramid_batch",
]

# row-block size (in elements) for batched quantization: keeps the per-tier
# [rows, T] float64 temporaries cache-resident (measured sweet spot on the
# bench box); rows are independent so blocking never changes bytes
_BATCH_BLOCK_ELEMS = 32 * 1024


def compute_residuals(values: np.ndarray, base: Base) -> np.ndarray:
    return np.asarray(values, dtype=np.float64) - base_predictions(base)


def _quantize_midpoint_rows(r: np.ndarray, eps_r: float) -> tuple[np.ndarray, np.ndarray]:
    """The midpoint quantizer on [S, T] rows: (q int64 [S, T], r_lo [S]).
    Row s is bit-identical to quantizing r[s] alone — every op is
    elementwise or a per-row reduction."""
    step = 2.0 * eps_r
    # + 0.0 folds -0.0 into 0.0: a min over both zeros returns either one,
    # depending on the reduction's order, and r_lo goes on the wire
    r_lo = (r.min(axis=1) if r.size else np.zeros(r.shape[0])) + 0.0
    q = np.floor((r - r_lo[:, None]) / step).astype(np.int64)
    # Floor at bin boundaries can land one bin off in floating point (e.g.
    # 0.5/0.0002 -> 2499.999...); correct so |r - dequant| <= step/2 holds
    # exactly (up to one ulp of the final subtraction).
    deq = r_lo[:, None] + (q.astype(np.float64) + 0.5) * step
    q += (r - deq) > step / 2
    q -= (deq - r) > step / 2
    return q, r_lo


def quantize_residuals(r: np.ndarray, eps_r: float) -> ResidualStream:
    """Lossy path: |dequant - r| <= eps_r."""
    if eps_r <= 0:
        raise ValueError("eps_r must be positive for the lossy path")
    r = np.asarray(r, dtype=np.float64)
    q, r_lo = _quantize_midpoint_rows(r[None, :], eps_r)
    return ResidualStream(
        eps_r=eps_r, step=2.0 * eps_r, r_lo=float(r_lo[0]), mode="midpoint", q=q[0]
    )


def quantize_residuals_batch(
    r: np.ndarray, eps_r: float, lengths: np.ndarray | None = None
) -> list[ResidualStream]:
    """Batched lossy path over rows r[S, T]; stream i is byte-identical to
    ``quantize_residuals(r[i], eps_r)`` — or, with ``lengths`` (ragged rows
    padded to T), to ``quantize_residuals(r[i, :lengths[i]], eps_r)``:
    the per-row minimum is taken over the valid prefix only and each q
    stream is cut at its row's length, so padding never reaches the
    entropy coder."""
    if eps_r <= 0:
        raise ValueError("eps_r must be positive for the lossy path")
    r = np.asarray(r, dtype=np.float64)
    if lengths is None:
        q, r_lo = _quantize_midpoint_rows(r, eps_r)
        return [
            ResidualStream(
                eps_r=eps_r, step=2.0 * eps_r, r_lo=float(r_lo[i]), mode="midpoint", q=q[i]
            )
            for i in range(r.shape[0])
        ]
    ns = np.asarray(lengths, dtype=np.int64)
    pad = np.arange(r.shape[1])[None, :] >= ns[:, None]
    # pad with 0.0 so every elementwise op below stays finite; the per-row
    # min ignores padding via +inf substitution (exact same float result as
    # min over the unpadded slice)
    r = np.where(pad, 0.0, r)
    step = 2.0 * eps_r
    r_lo = np.where(
        ns > 0, np.where(pad, np.inf, r).min(axis=1, initial=np.inf), 0.0
    ) + 0.0  # -0.0 -> 0.0, as in _quantize_midpoint_rows
    q = np.floor((r - r_lo[:, None]) / step).astype(np.int64)
    deq = r_lo[:, None] + (q.astype(np.float64) + 0.5) * step
    q += (r - deq) > step / 2
    q -= (deq - r) > step / 2
    return [
        ResidualStream(
            eps_r=eps_r,
            step=step,
            r_lo=float(r_lo[i]),
            mode="midpoint",
            q=q[i, : ns[i]].copy(),
        )
        for i in range(r.shape[0])
    ]


def dequantize_residuals(stream: ResidualStream) -> np.ndarray:
    if stream.mode == "midpoint":
        return stream.r_lo + (stream.q.astype(np.float64) + 0.5) * stream.step
    raise ValueError(f"not a lossy stream: {stream.mode}")


def quantize_exact(
    values: np.ndarray, base: Base, decimals: int, pred: np.ndarray | None = None
) -> ResidualStream:
    """Lossless path for fixed-decimal data.

    v_int = round(v * 10^d); pred_int = round(pred * 10^d);
    q = v_int - pred_int  (exact int64).  Reconstruction returns
    (pred_int + q) / 10^d == round(v, d) exactly.  ``pred`` lets callers
    that already materialized the base reconstruction skip recomputing it.
    """
    if pred is None:
        pred = base_predictions(base)
    values = np.asarray(values, dtype=np.float64)
    return quantize_exact_batch(values[None, :], pred[None, :], decimals)[0]


def quantize_exact_batch(
    values: np.ndarray, preds: np.ndarray, decimals: int,
    lengths: np.ndarray | None = None,
) -> list[ResidualStream]:
    """Batched lossless path over rows values/preds[S, T]; stream i is
    byte-identical to ``quantize_exact(values[i], ..., pred=preds[i])``.
    With ``lengths`` (ragged rows padded to T) each q stream is cut at its
    row's length; the quantization itself is elementwise, so padding never
    influences the valid symbols."""
    scale = 10.0**decimals
    v_int = np.round(np.asarray(values, dtype=np.float64) * scale).astype(np.int64)
    p_int = np.round(preds * scale).astype(np.int64)
    q = v_int - p_int
    if lengths is None:
        return [
            ResidualStream(eps_r=0.0, step=1.0 / scale, r_lo=0.0, mode="exact", q=q[i])
            for i in range(v_int.shape[0])
        ]
    ns = np.asarray(lengths, dtype=np.int64)
    return [
        ResidualStream(
            eps_r=0.0, step=1.0 / scale, r_lo=0.0, mode="exact", q=q[i, : ns[i]].copy()
        )
        for i in range(v_int.shape[0])
    ]


def dequantize_exact(stream: ResidualStream, base: Base, decimals: int) -> np.ndarray:
    scale = 10.0**decimals
    pred = base_predictions(base)
    p_int = np.round(pred * scale).astype(np.int64)
    return (p_int + stream.q) / scale


# --------------------------------------------------------------------- #
# Refinement pyramid: tier k quantizes the reconstruction error of the
# prefix through tier k-1, so an archive with many tiers stores each bit of
# residual information once (docs/architecture.md, "progressive decode").
# --------------------------------------------------------------------- #
def normalize_tiers(eps_targets: list[float], decimals: int | None) -> list[float]:
    """Canonical tier ladder: unique eps targets sorted coarse -> fine
    (strictly decreasing), the lossless tier (0.0) last.  The pyramid is
    *defined* over this order — callers may pass targets in any order."""
    tiers = sorted({float(e) for e in eps_targets}, reverse=True)
    if tiers and tiers[-1] < 0.0:
        raise ValueError(f"eps targets must be >= 0, got {tiers[-1]}")
    if tiers and tiers[-1] == 0.0 and decimals is None:
        raise ValueError("lossless stream requires `decimals`")
    return tiers


def _midpoint_rows_masked(
    e: np.ndarray, eps_r: float, ns: np.ndarray | None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Midpoint quantizer on rows e[S, T] (optionally ragged, padded past
    ``ns``): returns (q int64 [S, T], r_lo [S], deq [S, T]) where ``deq`` is
    recomputed from the *corrected* q — bitwise the array a decoder
    produces from (q, r_lo, step), which is what lets the encoder carry the
    decoder's reconstruction forward to the next layer."""
    step = 2.0 * eps_r
    if ns is None:
        r_lo = e.min(axis=1) if e.size else np.zeros(e.shape[0])
    else:
        pad = np.arange(e.shape[1])[None, :] >= ns[:, None]
        r_lo = np.where(
            ns > 0, np.where(pad, np.inf, e).min(axis=1, initial=np.inf), 0.0
        )
    r_lo = r_lo + 0.0  # -0.0 -> 0.0, as in _quantize_midpoint_rows
    q = np.floor((e - r_lo[:, None]) / step).astype(np.int64)
    # floor at bin boundaries can land one bin off in floating point; correct
    # so |e - dequant| <= step/2 holds exactly (same fix as the flat path)
    deq = r_lo[:, None] + (q.astype(np.float64) + 0.5) * step
    q += (e - deq) > step / 2
    q -= (deq - e) > step / 2
    deq = r_lo[:, None] + (q.astype(np.float64) + 0.5) * step
    return q, r_lo, deq


def quantize_pyramid_batch(
    values: np.ndarray,
    preds: np.ndarray,
    tiers: list[float],
    decimals: int | None = None,
    lengths: np.ndarray | None = None,
) -> list[list[ResidualStream | None]]:
    """Refinement-ladder quantization over rows values/preds[S, T].

    ``tiers`` must be the :func:`normalize_tiers` ladder (strictly
    decreasing, optional 0.0 last).  Returns ``layers[s][k]``: the
    ``ResidualStream`` of series s at tier k, or ``None`` (an *identity*
    layer) when the prefix through tier k-1 already meets tier k's
    guarantee — e.g. every tier above the practical base error.

    Guarantees, each property-tested in tests/test_pyramid_property.py:

    * per-tier: |values - reconstruction through tier k| <= tiers[k];
    * row s is bit-identical to the S == 1 call on (values[s], preds[s])
      (every op is elementwise or a per-row masked reduction), which is
      what keeps one-shot / streaming / batched / ragged paths
      byte-identical per tier;
    * the carried reconstruction is recomputed from the corrected integer
      symbols exactly as a decoder recomputes it, so the lossless tier's
      integer deltas match the decoder's integer view bit-for-bit.

    With ``lengths`` (ragged rows padded to T) the per-row reductions run
    over each row's valid prefix only and every emitted q stream is cut at
    its row's length, so padding never reaches the entropy coder.
    """
    values = np.asarray(values, dtype=np.float64)
    preds = np.asarray(preds, dtype=np.float64)
    s, t = values.shape
    ns = None if lengths is None else np.asarray(lengths, dtype=np.int64)
    # Cache blocking: each tier streams several [S, T] float64 temporaries;
    # for large batches those thrash cache and run ~1.7x slower than row
    # blocks that fit.  Every op is elementwise or a per-row reduction, so
    # block outputs concatenate unchanged (bit-identical rows).
    rows_blk = max(1, _BATCH_BLOCK_ELEMS // max(1, t))
    if s > rows_blk:
        blocks: list[list[ResidualStream | None]] = []
        for lo in range(0, s, rows_blk):
            blocks.extend(
                quantize_pyramid_batch(
                    values[lo : lo + rows_blk],
                    preds[lo : lo + rows_blk],
                    tiers,
                    decimals,
                    lengths=None if ns is None else ns[lo : lo + rows_blk],
                )
            )
        return blocks
    if ns is None:
        valid = None
    else:
        valid = np.arange(t)[None, :] < ns[:, None]
        values = np.where(valid, values, 0.0)
        preds = np.where(valid, preds, 0.0)
    out: list[list[ResidualStream | None]] = [[None] * len(tiers) for _ in range(s)]
    recon = preds.copy()
    for k, eps in enumerate(tiers):
        if eps == 0.0:
            if decimals is None:
                raise ValueError("lossless stream requires `decimals`")
            scale = 10.0**decimals
            v_int = np.round(values * scale).astype(np.int64)
            rec_int = np.round(recon * scale).astype(np.int64)
            q = v_int - rec_int
            for i in range(s):
                qi = q[i] if ns is None else q[i, : ns[i]].copy()
                out[i][k] = ResidualStream(
                    eps_r=0.0, step=1.0 / scale, r_lo=0.0, mode="exact", q=qi
                )
            continue
        e = values - recon
        if valid is not None:
            e = np.where(valid, e, 0.0)
        m = np.abs(e).max(axis=1) if t else np.zeros(s)
        need = np.flatnonzero(m > eps)
        if need.size == 0:
            continue  # identity layer for every row
        full = need.size == s
        q, r_lo, deq = _midpoint_rows_masked(
            e if full else e[need], eps, None if ns is None else ns[need]
        )
        # the elementwise add is identical either way; skipping the fancy
        # indexing when every row needs the layer (the common case) avoids
        # two full-matrix gather/scatter copies per tier
        if full:
            recon = recon + deq
        else:
            recon[need] = recon[need] + deq
        step = 2.0 * eps
        for j, i in enumerate(need):
            qi = q[j] if ns is None else q[j, : ns[i]].copy()
            out[int(i)][k] = ResidualStream(
                eps_r=eps, step=step, r_lo=float(r_lo[j]), mode="midpoint", q=qi
            )
    return out


def quantize_pyramid(
    values: np.ndarray,
    pred: np.ndarray,
    tiers: list[float],
    decimals: int | None = None,
) -> list[ResidualStream | None]:
    """Single-series refinement ladder — the S == 1 row of
    :func:`quantize_pyramid_batch` (same code path, hence bit-identical)."""
    values = np.asarray(values, dtype=np.float64)
    return quantize_pyramid_batch(values[None, :], pred[None, :], tiers, decimals)[0]


def encode_residuals_batch(
    streams: list[ResidualStream], backend: str = "best"
) -> list[bytes]:
    """Entropy-encode a batch of residual streams in one fused pass — the
    single funnel every pyramid producer (one-shot, rect-batch, ragged,
    streaming drain) routes through.  ``backend='best'`` partitions the
    batch per stream via the cost model and keeps the rans-bound group on
    the fused state machines; see :func:`repro.core.entropy.encode_ints_batch`."""
    return entropy.encode_ints_batch([st.q for st in streams], backend=backend)
