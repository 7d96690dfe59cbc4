"""Knowledge-base construction (Alg. 4 of the paper).

Cones are grouped by their quantized origin (same adaptive-grid index and
the same fluctuation level -> identical float theta), ordered inside each
group by ascending psi_lo, and greedily merged while the spans intersect.
Sorting by the lower slope makes the greedy scan optimal (interval-graph
perfect elimination — the same argument as Sim-Piece [13], [19], [20]).

The merged sub-base keeps the *intersection* of the member spans, so any
line inside it approximates every member segment's points within that
segment's eps_hat.
"""
from __future__ import annotations

import math
from collections import defaultdict

import numpy as np

from .phases import eps_hat_for_level
from .slope import optimized_slope
from .types import Base, Segment, ShrinkConfig, SubBase

__all__ = [
    "construct_base",
    "base_predictions",
    "base_predictions_batch",
    "base_predictions_ragged",
    "origin_index",
    "practical_eps_b",
]


def origin_index(theta: float, level: int, config: ShrinkConfig) -> int:
    """Grid index of a quantized origin: theta == idx * eps_hat(level).

    This is the canonical identity of a cone origin — the serializer
    delta-codes it, Alg. 4 groups by it, and the streaming knowledge base
    dedups (level, idx, slope) line entries across frames and series.
    """
    return int(round(theta / eps_hat_for_level(level, config)))


def _origin_key(seg: Segment, config: ShrinkConfig) -> tuple[int, int]:
    return (seg.level, origin_index(seg.theta, seg.level, config))


def construct_base(
    segments: list[Segment],
    n: int,
    vmin: float,
    vmax: float,
    config: ShrinkConfig,
) -> Base:
    """Alg. 4: group by origin, sort by psi_lo, greedy merge intersections."""
    groups: dict[tuple[int, int], list[Segment]] = defaultdict(list)
    for seg in segments:
        groups[_origin_key(seg, config)].append(seg)

    subbases: list[SubBase] = []
    for key in sorted(groups.keys()):
        group = sorted(groups[key], key=lambda s: (s.psi_lo, s.psi_hi))
        cur_lo, cur_hi = -math.inf, math.inf
        cur_members: list[Segment] = []
        level, idx = key
        # the origin on its grid, exactly as the decoder rebuilds it: a scan
        # in float32 (the device cone scan) lands within an ulp of it
        theta = idx * eps_hat_for_level(level, config)

        def _flush() -> None:
            if not cur_members:
                return
            slope, digits = optimized_slope(cur_lo, cur_hi)
            t0s = np.array([s.t0 for s in cur_members], dtype=np.int64)
            order = np.argsort(t0s)
            lengths = np.array([s.length for s in cur_members], dtype=np.int64)[order]
            subbases.append(
                SubBase(
                    theta=theta,
                    level=level,
                    psi_lo=cur_lo,
                    psi_hi=cur_hi,
                    slope=slope,
                    slope_digits=digits,
                    t0s=t0s[order],
                    lengths=lengths,
                )
            )

        for seg in group:
            lo, hi = seg.psi_lo, seg.psi_hi
            new_lo = max(cur_lo, lo)
            new_hi = min(cur_hi, hi)
            if not cur_members or new_lo <= new_hi:
                cur_lo, cur_hi = new_lo, new_hi
                cur_members.append(seg)
            else:
                _flush()
                cur_lo, cur_hi, cur_members = lo, hi, [seg]
        _flush()

    # deterministic order: by first timestamp (helps delta-coding timestamps)
    subbases.sort(key=lambda sb: int(sb.t0s[0]))
    # + 0.0 folds -0.0 into 0.0: a min or max over both zeros returns
    # either one, depending on the reduction's order, and both go on the wire
    return Base(n=n, config=config, vmin=vmin + 0.0, vmax=vmax + 0.0, subbases=subbases)


def _flat_segments(
    base: Base,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """All member segments as parallel arrays sorted by t0 (the partition
    order): (t0s i64, lengths i64, thetas f64, slopes f64)."""
    sbs = base.subbases
    if not sbs:
        z = np.zeros(0, dtype=np.int64)
        return z, z, z.astype(np.float64), z.astype(np.float64)
    t0s = np.concatenate([sb.t0s for sb in sbs])
    lens = np.concatenate([sb.lengths for sb in sbs])
    thetas = np.concatenate([np.full(len(sb.t0s), sb.theta) for sb in sbs])
    slopes = np.concatenate([np.full(len(sb.t0s), sb.slope) for sb in sbs])
    order = np.argsort(t0s, kind="stable")  # t0s are unique: a partition
    return t0s[order], lens[order], thetas[order], slopes[order]


def base_predictions(base: Base) -> np.ndarray:
    """Vectorized reconstruction of the base-only approximation (n floats)."""
    n = base.n
    if n == 0:
        return np.zeros(0, dtype=np.float64)
    t0s, lens, thetas, slopes = _flat_segments(base)
    theta = np.repeat(thetas, lens)
    slope = np.repeat(slopes, lens)
    start = np.repeat(t0s.astype(np.float64), lens)
    t = np.arange(n, dtype=np.float64)
    return theta + slope * (t - start)


def base_predictions_batch(bases: list[Base]) -> np.ndarray:
    """``np.stack([base_predictions(b) for b in bases])`` in one repeat pass;
    all bases must share the same n."""
    s = len(bases)
    if s == 0:
        return np.zeros((0, 0), dtype=np.float64)
    n = bases[0].n
    if n == 0:
        return np.zeros((s, 0), dtype=np.float64)
    flats = [_flat_segments(b) for b in bases]
    lens = np.concatenate([f[1] for f in flats])
    theta = np.repeat(np.concatenate([f[2] for f in flats]), lens)
    slope = np.repeat(np.concatenate([f[3] for f in flats]), lens)
    start = np.repeat(np.concatenate([f[0] for f in flats]).astype(np.float64), lens)
    t = np.tile(np.arange(n, dtype=np.float64), s)
    return (theta + slope * (t - start)).reshape(s, n)


def base_predictions_ragged(bases: list[Base], pad_to: int) -> np.ndarray:
    """Ragged counterpart of ``base_predictions_batch``: bases may have any
    mix of lengths; returns [S, pad_to] with row i holding
    ``base_predictions(bases[i])`` in its first ``bases[i].n`` slots and
    0.0 beyond (one concatenated repeat pass, no per-series python loop)."""
    s = len(bases)
    out = np.zeros((s, pad_to), dtype=np.float64)
    if s == 0:
        return out
    ns = np.array([b.n for b in bases], dtype=np.int64)
    if ns.max(initial=0) > pad_to:
        raise ValueError(f"pad_to={pad_to} smaller than longest base n={ns.max()}")
    total = int(ns.sum())
    if total == 0:
        return out
    flats = [_flat_segments(b) for b in bases]
    lens = np.concatenate([f[1] for f in flats])
    theta = np.repeat(np.concatenate([f[2] for f in flats]), lens)
    slope = np.repeat(np.concatenate([f[3] for f in flats]), lens)
    start = np.repeat(np.concatenate([f[0] for f in flats]).astype(np.float64), lens)
    series_of = np.repeat(np.arange(s), ns)
    t_local = np.arange(total) - np.repeat(np.cumsum(ns) - ns, ns)
    out[series_of, t_local] = theta + slope * (t_local.astype(np.float64) - start)
    return out


def practical_eps_b(
    values: np.ndarray, base: Base, pred: np.ndarray | None = None
) -> float:
    """The paper's \\hat{eps}_b: realized max |v - base prediction|.
    ``pred`` lets callers that already materialized the reconstruction skip
    recomputing it."""
    if pred is None:
        pred = base_predictions(base)
    return float(np.max(np.abs(values - pred))) if base.n else 0.0
