"""The IoT-gateway sensor model of ``repro.data.synthetic
.ragged_sensor_traffic``, vectorised: per-sensor rates log-uniform on
[rate_lo, rate_hi], Poisson(rate) samples per sensor per tick, a random
walk with N(0, step_sigma) steps plus N(0, noise_sigma) measurement noise,
rounded to ``decimals``."""
from __future__ import annotations

import numpy as np

from bench.generate import Pool, rng


def sensor_rates(data: dict, seed: int) -> np.ndarray:
    """Log-uniform rates on [rate_lo, rate_hi]: the same set of rates for
    every seed (the quantiles of the distribution), dealt to the sensors in
    a seeded order, so that seeds change which sensor is fast and not how
    much work a tick holds."""
    s = int(data["sensors"])
    lo, hi = np.log(data["rate_lo"]), np.log(data["rate_hi"])
    rates = np.exp(lo + (hi - lo) * (np.arange(s) + 0.5) / s)
    return rates[rng(seed, "rates").permutation(s)]


def mean_samples_per_tick(data: dict) -> float:
    lo, hi = float(data["rate_lo"]), float(data["rate_hi"])
    return data["sensors"] * (hi - lo) / np.log(hi / lo)


def pool_ticks(data: dict, seed: int, ticks: int) -> Pool:
    s = int(data["sensors"])
    rates = sensor_rates(data, seed)
    counts = rng(seed, "arrivals").poisson(rates[None, :], size=(ticks, s)).astype(np.int64)
    offsets = np.zeros((ticks + 1, s), np.int64)
    np.cumsum(counts, axis=0, out=offsets[1:])
    totals = offsets[-1]
    n = int(totals.sum())
    g = rng(seed, "values")
    # one stream per sensor, sensor-major: a global cumulative sum of the
    # steps minus its value at each sensor's start is each sensor's walk
    walk = np.cumsum(g.standard_normal(n) * data["step_sigma"])
    starts = np.zeros(s, np.int64)
    np.cumsum(totals[:-1], out=starts[1:])
    before = np.where(starts > 0, walk[np.maximum(starts - 1, 0)], 0.0)
    walk -= np.repeat(before, totals)
    walk += g.standard_normal(n) * data["noise_sigma"]
    flat = np.round(walk, data["decimals"])
    return Pool(values=np.split(flat, starts[1:]), offsets=offsets)
