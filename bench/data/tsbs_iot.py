"""TSBS ``iot``: a fleet of trucks, each reporting its ``readings`` and
``diagnostics`` fields every ``interval_s``.  Each (truck, field) pair is one
series, id ``truck * n_fields + field``; each field is a clamped random walk
in its own range (N(0, step) steps clamped to [lo, hi], started U(lo, hi),
rounded to the field's decimals).

Delivery follows the TSBS README's "batch ingestion (for trucks that are
offline for a period of time)": a truck online at tick ``k`` delivers every
reading it has taken up to the tick's end, so one that comes back from an
outage delivers its whole backlog, in order, in one tick, and nothing is
lost.  The outages are the configuration's: their lengths are the
quantiles of a log-uniform on [outage_lo_h, outage_hi_h] hours, dealt to
each truck in a seeded order, and the online gaps between them are
exponential, with the mean that puts ``offline_share`` of truck-hours
offline.  Both come from ``schedule_seed``, so every run meets the same
flush shapes and ``--seed`` draws only the values.
"""
from __future__ import annotations

import numpy as np

from bench.generate import Pool, rng

N_QUANTILES = 16  # outage lengths per cycle of a truck's schedule


def fields(data: dict) -> list[dict]:
    return [f for table in ("readings", "diagnostics") for f in data[table]]


def per_tick(data: dict) -> int:
    return int(data["deliver_every_s"]) // int(data["interval_s"])


def mean_samples_per_tick(data: dict) -> float:
    return int(data["hosts"]) * len(fields(data)) * per_tick(data)


def outage_lengths(data: dict) -> np.ndarray:
    """The outage lengths, in ticks: quantiles of the log-uniform."""
    hour = int(data["deliver_every_s"]) / 3600.0
    lo, hi = np.log(data["outage_lo_h"]), np.log(data["outage_hi_h"])
    q = (np.arange(N_QUANTILES) + 0.5) / N_QUANTILES
    return np.maximum(1, np.round(np.exp(lo + (hi - lo) * q) / hour)).astype(np.int64)


def online(data: dict, ticks: int) -> np.ndarray:
    """[ticks, trucks] bool: whether each truck delivers at each tick.  Each
    truck draws from its own generator, so a longer pool extends the
    schedule and a larger fleet adds trucks without moving the others'."""
    trucks = int(data["hosts"])
    lengths = outage_lengths(data)
    share = float(data["offline_share"])
    gap_mean = lengths.mean() * (1.0 - share) / share
    out = np.ones((ticks, trucks), bool)
    for h in range(trucks):
        g = np.random.default_rng([int(data["schedule_seed"]), h])
        # start at a uniform point of the first cycle, so that the fleet is
        # as far into its outages at the first tick as at any other
        t = -int(g.integers(0, lengths.sum() + N_QUANTILES * gap_mean))
        while t < ticks:
            for length in lengths[g.permutation(N_QUANTILES)].tolist():
                t += 1 + int(g.exponential(gap_mean))
                out[max(t, 0) : max(t + length, 0), h] = False
                t += length
    return out


def clamped_walk(data: dict, seed: int, n: int) -> np.ndarray:
    """[series, n] clamped random walks, one row per (truck, field)."""
    fs = fields(data)
    trucks = int(data["hosts"])
    lo = np.tile([float(f["lo"]) for f in fs], trucks)
    hi = np.tile([float(f["hi"]) for f in fs], trucks)
    step = np.tile([float(f["step"]) for f in fs], trucks)
    g = rng(seed, "walk")
    x = g.uniform(lo, hi)
    vals = np.empty((x.size, n))
    block = 4096  # steps drawn a block at a time: bounded scratch memory
    for t0 in range(0, n, block):
        steps = g.standard_normal((min(block, n - t0), x.size)) * step
        for t in range(steps.shape[0]):  # the clamp makes each step depend on the last
            x = np.minimum(np.maximum(x + steps[t], lo), hi)
            steps[t] = x
        vals[:, t0 : t0 + steps.shape[0]] = steps.T
    by_field = vals.reshape(trucks, len(fs), n)
    for i, f in enumerate(fs):
        np.round(by_field[:, i], int(f["decimals"]), out=by_field[:, i])
    return vals


def pool_ticks(data: dict, seed: int, ticks: int) -> Pool:
    k = per_tick(data)
    vals = clamped_walk(data, seed, ticks * k)
    on = online(data, ticks)
    # a truck online at tick t has delivered everything up to the tick's end
    last = np.where(on, np.arange(1, ticks + 1)[:, None], 0)
    delivered = np.maximum.accumulate(last, axis=0) * k
    offsets = np.zeros((ticks + 1, vals.shape[0]), np.int64)
    offsets[1:] = np.repeat(delivered, len(fields(data)), axis=1)
    return Pool(values=list(vals), offsets=offsets)
