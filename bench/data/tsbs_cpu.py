"""TSBS ``devops`` ``cpu-only``: every host reports the ten ``cpu`` fields
every ``interval_s``; each field is TSBS's clamped random walk (N(0,
step_sigma) steps clamped to [lo, hi], started U(lo, hi)).  Each (host,
field) pair is one series, id ``host * n_fields + field``, and a tick
delivers ``deliver_every_s`` of readings of every series."""
from __future__ import annotations

import numpy as np

from bench.generate import Pool, rng


def clamped_walk(data: dict, seed: int, n: int) -> np.ndarray:
    """[series, n] TSBS clamped random walks, one row per (host, field)."""
    s = int(data["hosts"]) * len(data["fields"])
    lo, hi = float(data["lo"]), float(data["hi"])
    g = rng(seed, "walk")
    x = g.uniform(lo, hi, size=s)
    steps = g.standard_normal((n, s)) * data["step_sigma"]
    out = np.empty((n, s))
    for t in range(n):  # the clamp makes each step depend on the last
        x = np.minimum(np.maximum(x + steps[t], lo), hi)
        out[t] = x
    return np.round(out.T, data["decimals"])


def per_tick(data: dict) -> int:
    return int(data["deliver_every_s"]) // int(data["interval_s"])


def mean_samples_per_tick(data: dict) -> float:
    return int(data["hosts"]) * len(data["fields"]) * per_tick(data)


def pool_ticks(data: dict, seed: int, ticks: int) -> Pool:
    k = per_tick(data)
    vals = clamped_walk(data, seed, ticks * k)
    s = vals.shape[0]
    offsets = np.repeat(np.arange(ticks + 1, dtype=np.int64)[:, None] * k, s, axis=1)
    return Pool(values=list(vals), offsets=offsets)
