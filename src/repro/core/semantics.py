"""Semantics extraction via shrinking cones (Alg. 3 of the paper).

A cone starts at index ``t0`` with a quantized origin ``theta`` (Alg. 2 /
phases.py) and an adaptive threshold ``eps_hat`` fixed for its lifetime.
Every subsequent point (dt = i - t0 > 0) constrains the feasible slope set to

    [ (v_i - eps_hat - theta)/dt ,  (v_i + eps_hat - theta)/dt ]

and the cone keeps the running intersection (psi_lo, psi_hi).  When the
intersection empties, the cone closes and a new one starts at the violating
point — Fig. 2(b) of the paper.

Three implementations with identical semantics:

* ``extract_semantics_py``     — literal per-point loop; the test oracle.
* ``extract_semantics``        — chunked-vectorized numpy scan (production
  host path).  Within a candidate chunk the running intersection is a prefix
  min/max (``np.minimum.accumulate``), and the first emptiness is located
  with ``argmax`` — O(n) total work, numpy-speed.
* ``extract_semantics_batch``  — the same chunked scan run in lockstep over
  S independent series at once ([S, T] input).  Candidate slopes, running
  intersections, and first-violation searches are [S, chunk] array ops;
  only series that break inside a chunk re-scan the remainder of that
  chunk.  Because min/max and first-violation do not depend on how the time
  axis is chunked, the per-series output is bit-identical to
  ``extract_semantics`` on each row.

The Pallas kernel ``kernels/cone_scan.py`` implements the same recurrence on
TPU using the sequential-grid idiom; ``kernels/ref.py`` mirrors this module.
"""
from __future__ import annotations

import math

import numpy as np

from .phases import default_interval_length, divide, fluctuation_table
from .types import Segment, ShrinkConfig

__all__ = [
    "extract_semantics",
    "extract_semantics_py",
    "extract_semantics_batch",
    "extract_semantics_batch_pallas",
    "global_range",
]

_INF = math.inf
# row-block size (in elements) for the batched cone scan: big enough to
# amortize per-block python overhead, small enough that the [rows, T]
# temporaries stay cache-resident (measured sweet spot on the bench box)
_BATCH_BLOCK_ELEMS = 64 * 1024


def global_range(values: np.ndarray) -> tuple[float, float]:
    if values.size == 0:  # empty series compress to an empty base
        return 0.0, 0.0
    return float(values.min()), float(values.max())


def extract_semantics_py(
    values: np.ndarray,
    config: ShrinkConfig,
    value_range: tuple[float, float] | None = None,
    n_hint: int | None = None,
) -> list[Segment]:
    """Reference loop implementation (kept simple; used as the oracle).

    ``value_range``/``n_hint`` pin the two global quantities the scan
    otherwise derives from the full series (the fluctuation denominator
    ``delta_global`` and the interval length ``L``).  Streaming ingest
    pins them so a chunk-at-a-time scan matches this one-shot scan
    bit-for-bit; ``None`` keeps the derive-from-data behavior.
    """
    n = int(values.shape[0])
    if n == 0:
        return []
    vmin, vmax = global_range(values) if value_range is None else value_range
    delta_global = vmax - vmin
    L = default_interval_length(n if n_hint is None else int(n_hint), config)

    segments: list[Segment] = []
    i = 0
    while i < n:
        theta, level, eps_hat = divide(values, i, L, delta_global, config)
        psi_lo, psi_hi = -_INF, _INF
        j = i + 1
        while j < n:
            dt = float(j - i)
            hi = (float(values[j]) + eps_hat - theta) / dt
            lo = (float(values[j]) - eps_hat - theta) / dt
            new_hi = min(psi_hi, hi)
            new_lo = max(psi_lo, lo)
            if new_lo > new_hi:
                break  # cone empty -> close at j-1, next cone starts at j
            psi_lo, psi_hi = new_lo, new_hi
            j += 1
        segments.append(
            Segment(theta=theta, level=level, psi_lo=psi_lo, psi_hi=psi_hi, t0=i, length=j - i)
        )
        i = j
    return segments


def extract_semantics(
    values: np.ndarray,
    config: ShrinkConfig,
    value_range: tuple[float, float] | None = None,
    n_hint: int | None = None,
) -> list[Segment]:
    """Chunked-vectorized scan; semantics identical to extract_semantics_py.

    ``value_range``/``n_hint`` optionally pin ``delta_global`` and the
    interval length ``L`` (see ``extract_semantics_py``); defaults derive
    them from ``values`` exactly as before.
    """
    values = np.asarray(values, dtype=np.float64)
    n = int(values.shape[0])
    if n == 0:
        return []
    vmin, vmax = global_range(values) if value_range is None else value_range
    delta_global = vmax - vmin
    L = default_interval_length(n if n_hint is None else int(n_hint), config)

    segments: list[Segment] = []
    i = 0
    while i < n:
        theta, level, eps_hat = divide(values, i, L, delta_global, config)
        psi_lo, psi_hi = -_INF, _INF
        j = i + 1
        chunk = 256
        closed = False
        while j < n:
            end = min(n, j + chunk)
            dt = np.arange(j - i, end - i, dtype=np.float64)
            seg_vals = values[j:end]
            hi = (seg_vals + (eps_hat - theta)) / dt
            lo = (seg_vals - (eps_hat + theta)) / dt
            run_hi = np.minimum(np.minimum.accumulate(hi), psi_hi)
            run_lo = np.maximum(np.maximum.accumulate(lo), psi_lo)
            viol = run_lo > run_hi
            if viol.any():
                idx = int(np.argmax(viol))
                if idx > 0:
                    psi_hi = float(run_hi[idx - 1])
                    psi_lo = float(run_lo[idx - 1])
                k = j + idx
                segments.append(
                    Segment(theta=theta, level=level, psi_lo=psi_lo, psi_hi=psi_hi, t0=i, length=k - i)
                )
                i = k
                closed = True
                break
            psi_hi = float(run_hi[-1])
            psi_lo = float(run_lo[-1])
            j = end
            chunk = min(chunk * 2, 65536)
        if not closed:
            segments.append(
                Segment(theta=theta, level=level, psi_lo=psi_lo, psi_hi=psi_hi, t0=i, length=n - i)
            )
            i = n
    return segments


def extract_semantics_batch(
    values: np.ndarray,
    config: ShrinkConfig,
    chunk: int = 256,
    lengths: np.ndarray | None = None,
) -> list[list[Segment]]:
    """Multi-series cone scan: values[S, T] -> one segment list per series.

    All series advance through shared time chunks; per-series cone state
    (theta, eps_hat, t0, psi) lives in [S] vectors.  A chunk is re-scanned
    only for the series that broke inside it, with positions at or before
    the new segment start masked to non-constraining candidates.  The chunk
    length adapts to the observed break density (long segments -> bigger
    chunks); the output is invariant to chunking.

    ``lengths`` makes the lanes ragged: row s holds a series of
    ``lengths[s]`` real samples padded to T.  Positions past a row's length
    are masked to non-constraining candidates (the padding can never break
    or extend a cone) and the final segment closes at the row's own end, so
    each row's output is bit-identical to ``extract_semantics`` on its
    unpadded slice — padding never leaks into cones.
    """
    values = np.asarray(values, dtype=np.float64)
    if values.ndim != 2:
        raise ValueError(f"expected [S, T], got shape {values.shape}")
    s, n = values.shape
    # Cache blocking: the scan's whole-matrix passes (fluctuation table,
    # re-scan gathers) stream [S, T]-sized temporaries, which for large
    # batches fall out of cache and run ~1.5x slower than row blocks that
    # fit.  Rows are independent (each is bit-identical to the scalar
    # scan), so block outputs concatenate unchanged.
    rows_blk = max(1, _BATCH_BLOCK_ELEMS // max(1, n))
    if s > rows_blk:
        blocks: list[list[Segment]] = []
        for lo in range(0, s, rows_blk):
            blocks.extend(
                extract_semantics_batch(
                    values[lo : lo + rows_blk],
                    config,
                    chunk=chunk,
                    lengths=None
                    if lengths is None
                    else np.asarray(lengths, dtype=np.int64)[lo : lo + rows_blk],
                )
            )
        return blocks
    out: list[list[Segment]] = [[] for _ in range(s)]
    if n == 0 or s == 0:
        return out
    if lengths is None:
        ns = np.full(s, n, dtype=np.int64)
        delta_global = values.max(axis=1) - values.min(axis=1)
        levels_tab, eps_tab = fluctuation_table(values, delta_global, config)
    else:
        ns = np.asarray(lengths, dtype=np.int64)
        if ns.shape != (s,):
            raise ValueError(f"lengths must be [S]={s}, got shape {ns.shape}")
        if (ns < 0).any() or (ns > n).any():
            raise ValueError(f"lengths must lie in [0, T={n}]")
        pad_mask = np.arange(n)[None, :] >= ns[:, None]
        vmax_in = np.where(pad_mask, -_INF, values)
        vmin_in = np.where(pad_mask, _INF, values)
        delta_global = np.where(ns > 0, vmax_in.max(axis=1) - vmin_in.min(axis=1), 0.0)
        levels_tab, eps_tab = fluctuation_table(values, delta_global, config, lengths=ns)
    live = ns > 0  # rows with no samples emit no segments

    seg_level = levels_tab[:, 0].copy()
    eps = np.where(live, eps_tab[:, 0], 1.0)  # dead rows: any finite eps
    theta = np.floor(values[:, 0] / eps) * eps
    t0 = np.zeros(s, dtype=np.int64)
    psi_lo = np.full(s, -_INF)
    psi_hi = np.full(s, _INF)

    c0 = 1
    n_scan = int(ns.max()) if s else 0
    while c0 < n_scan:
        c1 = min(n_scan, c0 + chunk)
        active = np.flatnonzero(ns > c0)  # rows with real samples in this chunk
        lo0 = c0  # re-scans only need positions past the earliest new segment
        breaks = 0
        while active.size:
            ts = np.arange(lo0, c1, dtype=np.float64)
            v = values[active, lo0:c1]
            ep = eps[active][:, None]
            th = theta[active][:, None]
            dt = ts[None, :] - t0[active][:, None]
            with np.errstate(divide="ignore", invalid="ignore"):
                hi = (v + (ep - th)) / dt
                lo = (v - (ep + th)) / dt
            pre = dt <= 0  # positions at/before the segment start: no constraint
            if lengths is not None:
                # ragged lanes: padding is likewise non-constraining
                pre = pre | (ts[None, :] >= ns[active][:, None])
            if pre.any():
                hi[pre] = _INF
                lo[pre] = -_INF
            run_hi = np.minimum(np.minimum.accumulate(hi, axis=1), psi_hi[active][:, None])
            run_lo = np.maximum(np.maximum.accumulate(lo, axis=1), psi_lo[active][:, None])
            viol = run_lo > run_hi
            has = viol.any(axis=1)
            done = active[~has]
            if done.size:  # cone survived the chunk: carry the intersection
                psi_hi[done] = run_hi[~has, -1]
                psi_lo[done] = run_lo[~has, -1]
            if not has.any():
                break
            rows = np.flatnonzero(has)
            broke = active[has]
            breaks += broke.size
            first = viol[rows].argmax(axis=1)
            closed_hi = np.where(first > 0, run_hi[rows, first - 1], psi_hi[broke])
            closed_lo = np.where(first > 0, run_lo[rows, first - 1], psi_lo[broke])
            brk_t = lo0 + first
            for a, k, plo, phi in zip(broke, brk_t, closed_lo, closed_hi):
                out[a].append(
                    Segment(
                        theta=float(theta[a]),
                        level=int(seg_level[a]),
                        psi_lo=float(plo),
                        psi_hi=float(phi),
                        t0=int(t0[a]),
                        length=int(k - t0[a]),
                    )
                )
            # open a new cone at the violating point (Alg. 2 DIVISION)
            seg_level[broke] = levels_tab[broke, brk_t]
            eps[broke] = eps_tab[broke, brk_t]
            theta[broke] = np.floor(values[broke, brk_t] / eps[broke]) * eps[broke]
            t0[broke] = brk_t
            psi_lo[broke] = -_INF
            psi_hi[broke] = _INF
            active = broke  # re-scan the chunk tail for just these series
            lo0 = int(brk_t.min()) + 1
            if lo0 >= c1:
                break
        if breaks == 0:
            chunk = min(chunk * 2, 65536)
        else:  # aim for ~2x the observed mean segment length
            mean_len = (c1 - c0) * max(int(np.count_nonzero(ns > c0)), 1) / breaks
            chunk = int(min(max(2 * mean_len, 128), 65536))
        c0 = c1
    for a in np.flatnonzero(live):
        out[a].append(
            Segment(
                theta=float(theta[a]),
                level=int(seg_level[a]),
                psi_lo=float(psi_lo[a]),
                psi_hi=float(psi_hi[a]),
                t0=int(t0[a]),
                length=int(ns[a] - t0[a]),
            )
        )
    return out


_SPAN_SENTINEL = 1e38  # kernel spans at/beyond this magnitude mean "unbounded"


def extract_semantics_batch_pallas(
    values: np.ndarray,
    config: ShrinkConfig,
    block_t: int = 256,
    lengths: np.ndarray | None = None,
) -> list[list[Segment]]:
    """Multi-series cone scan routed through the lane-parallel Pallas kernel
    (``kernels.cone_scan``) with segment compaction done in XLA; only the
    final Segment materialization happens on the host.

    ``lengths`` activates the kernel's valid-length mask path for ragged
    lanes: row s carries ``lengths[s]`` real samples padded to T, the
    in-kernel mask freezes a lane's cone state past its length (padding
    can never break, constrain, or seed a cone), and each row's segments
    partition [0, lengths[s]).

    The device scan runs in float32 (TPU-native), so — unlike
    ``extract_semantics_batch`` — segment spans can differ from the float64
    host scan in the last ulp.  Use this path for throughput on TPU; the
    numpy path is the bit-exact reference.
    """
    from ..kernels import ops as _kops  # lazy: keep numpy-only users jax-free

    values = np.asarray(values, dtype=np.float64)
    if values.ndim != 2:
        raise ValueError(f"expected [S, T], got shape {values.shape}")
    s, n = values.shape
    if n == 0 or s == 0:
        return [[] for _ in range(s)]
    if lengths is None:
        ns = np.full(s, n, dtype=np.int64)
        delta_global = values.max(axis=1) - values.min(axis=1)
        levels_tab, eps_tab = fluctuation_table(values, delta_global, config)
    else:
        ns = np.asarray(lengths, dtype=np.int64)
        if ns.shape != (s,):
            raise ValueError(f"lengths must be [S]={s}, got shape {ns.shape}")
        if (ns < 1).any() or (ns > n).any():
            raise ValueError(
                "pallas route needs lengths in [1, T]; route empty series "
                "around the kernel (compress_batch does)"
            )
        pad_mask = np.arange(n)[None, :] >= ns[:, None]
        # benign padding for the device scan: repeat each row's last real
        # value (the kernel masks these positions; repeats just keep every
        # float op finite in float32)
        values = np.where(pad_mask, values[np.arange(s), ns - 1][:, None], values)
        vmax_in = np.where(pad_mask, -_INF, values)
        vmin_in = np.where(pad_mask, _INF, values)
        delta_global = vmax_in.max(axis=1) - vmin_in.min(axis=1)
        levels_tab, eps_tab = fluctuation_table(values, delta_global, config, lengths=ns)
        eps_tab = np.where(pad_mask, eps_tab[np.arange(s), ns - 1][:, None], eps_tab)
    # the kernel wrapper buckets the shape, so counts/t0s/... come padded
    counts, t0s, thetas, lo, hi = (
        np.asarray(a)
        for a in _kops.cone_scan_segments(
            values.T.astype(np.float32), eps_tab.T.astype(np.float32),
            block_t=block_t, lengths=ns.astype(np.int32),
        )
    )
    out: list[list[Segment]] = []
    for a in range(s):
        n_a = int(ns[a])
        c = int(counts[a])
        starts = t0s[:c, a].astype(np.int64)
        keep = starts < n_a  # defensive: masked lanes cannot break past n_a
        starts = starts[keep]
        c = starts.size
        ends = np.minimum(np.append(starts[1:], n_a), n_a)
        plo = lo[:c, a].astype(np.float64)
        phi = hi[:c, a].astype(np.float64)
        plo[plo <= -_SPAN_SENTINEL] = -_INF
        phi[phi >= _SPAN_SENTINEL] = _INF
        out.append(
            [
                Segment(
                    theta=float(thetas[k, a]),
                    level=int(levels_tab[a, starts[k]]),
                    psi_lo=float(plo[k]),
                    psi_hi=float(phi[k]),
                    t0=int(starts[k]),
                    length=int(ends[k] - starts[k]),
                )
                for k in range(c)
            ]
        )
    return out
