"""The generators reproduce from their seed and match their stated rates and
ranges."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import generate
from bench.data import ragged_sensors

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
SEED = 2**33 + 7  # wider than 32 bits, as the benchmark's seeds are
# the gateway model of repro.data.synthetic.ragged_sensor_traffic
GATEWAY = {"kind": "ragged_sensors", "sensors": 1024, "rate_lo": 2.0, "rate_hi": 512.0,
           "step_sigma": 0.03, "noise_sigma": 0.01, "decimals": 4}


def _data(name, **changes):
    if name == "gateway":
        data = dict(GATEWAY)
    else:
        with open(CONFIGS / f"{name}.json") as f:
            data = json.load(f)["data"]
    data.update(changes)
    return data


def _same(a, b):
    return np.array_equal(a.offsets, b.offsets) and all(
        np.array_equal(x, y) for x, y in zip(a.values, b.values)
    )


@pytest.mark.parametrize("name,changes", [
    ("gateway", {"sensors": 128}),
    ("tsbs_cpu", {"hosts": 6}),
])
def test_pool_reproduces_from_its_seed(name, changes):
    data = _data(name, **changes)
    a = generate.pool_ticks(data, SEED, 40)
    assert _same(a, generate.pool_ticks(data, SEED, 40))
    assert not _same(a, generate.pool_ticks(data, SEED + 1, 40))


def test_gateway_rates_and_values():
    data = _data("gateway", sensors=256)
    ticks = 400
    pool = generate.pool_ticks(data, SEED, ticks)
    rates = ragged_sensors.sensor_rates(data, SEED)
    # every seed deals the same rates, log-uniform over [rate_lo, rate_hi]
    assert np.allclose(np.sort(rates), np.sort(ragged_sensors.sensor_rates(data, SEED + 1)))
    assert rates.min() >= data["rate_lo"] and rates.max() <= data["rate_hi"]
    logs = np.log(np.sort(rates))
    assert np.allclose(np.diff(logs), np.diff(logs)[0])
    # Poisson arrivals at each sensor's rate
    per_tick = pool.offsets[-1] / ticks
    assert np.all(np.abs(per_tick - rates) < 5 * np.sqrt(rates / ticks) + 1e-9)
    # the walk: steps N(0, step_sigma) plus N(0, noise_sigma) on each sample
    v = pool.values[int(np.argmax(rates))]
    d = np.diff(v)
    want = np.sqrt(data["step_sigma"] ** 2 + 2 * data["noise_sigma"] ** 2)
    assert abs(d.std() / want - 1) < 0.05 and abs(d.mean()) < 0.01
    assert np.array_equal(v, np.round(v, data["decimals"]))
    # the tick view hands out each series' stream in order, once
    got = {}
    for k in range(ticks):
        for sid, chunk in pool.tick(k):
            got.setdefault(sid, []).append(chunk)
    for sid, chunks in got.items():
        assert np.array_equal(np.concatenate(chunks), pool.values[sid])


def test_tsbs_clamped_walk():
    data = _data("tsbs_cpu", hosts=20)
    pool = generate.pool_ticks(data, SEED, 24)
    per_tick = data["deliver_every_s"] // data["interval_s"]
    assert pool.n_series == 20 * len(data["fields"]) == 200
    assert np.all(pool.offsets[-1] == 24 * per_tick)
    v = np.stack(pool.values)
    assert v.min() >= data["lo"] and v.max() <= data["hi"]
    assert np.array_equal(v, np.round(v, data["decimals"]))
    # away from the clamps a step is N(0, step_sigma)
    d = np.diff(v, axis=1)
    inside = (v[:, :-1] > 5) & (v[:, :-1] < 95) & (v[:, 1:] > 0) & (v[:, 1:] < 100)
    assert abs(d[inside].std() / data["step_sigma"] - 1) < 0.05
    assert abs(d[inside].mean()) < 0.05


def test_tsbs_queries_reproduce_and_cover_the_mix():
    data = _data("tsbs_cpu")
    with open(CONFIGS.parent / "traffic" / "tsbs_query.json") as f:
        mix = json.load(f)
    span = 8640

    def take(seed, n):
        it = generate.queries(data, mix, seed, span)
        return [next(it) for _ in range(n)]

    a = take(SEED, 70)
    assert [(q.kind, q.calls) for q in a] == [(q.kind, q.calls) for q in take(SEED, 70)]
    assert [q.calls for q in a] != [q.calls for q in take(SEED + 1, 70)]
    kinds = [q.kind for q in a]
    names = [t["name"] for t in mix["types"]]
    for r in range(10):  # each round holds every type once
        assert sorted(kinds[7 * r : 7 * r + 7]) == sorted(names)
    n_fields = len(data["fields"])
    for q in a:
        qt = next(t for t in mix["types"] if t["name"] == q.kind)
        rows = qt["range_s"] // data["interval_s"]
        assert len({sid // n_fields for sid, *_ in q.calls}) == qt["hosts"]
        assert {sid % n_fields for sid, *_ in q.calls} == set(range(qt["fields"]))
        for sid, op, t0, t1, arg in q.calls:
            assert 0 <= t0 < t1 <= span and op == qt["op"]
        t_lo = min(c[2] for c in q.calls)
        assert max(c[3] for c in q.calls) - t_lo == rows
