"""The TSBS iot fleet's traffic (``bench/data/tsbs_iot.py``) and its cell:
the outage schedule is the configuration's and the values the seed's, every
backlog arrives whole and in order, and the check of ``tsbs_iot.ingest``
refuses its control and a planted fault."""
import json
from pathlib import Path

import numpy as np

from bench import generate
from bench.data import tsbs_iot
from bench.tests.test_checks import _altered_batch
from bench.tests.tiny import run_tiny

SEED = 2**33 + 16  # wider than 32 bits, as the benchmark's seeds are
CELL = "tsbs_iot.ingest"


def _data(**changes) -> dict:
    with open(Path(__file__).resolve().parents[1] / "configs" / "tsbs_iot.json") as f:
        data = json.load(f)["data"]
    data.update(changes)
    return data


def _same_values(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in zip(a.values, b.values))


def test_pool_reproduces_from_its_seed():
    data = _data(hosts=6)
    a = generate.pool_ticks(data, SEED, 60)
    b = generate.pool_ticks(data, SEED, 60)
    assert np.array_equal(a.offsets, b.offsets) and _same_values(a, b)


def test_schedule_is_the_configurations_and_values_the_seeds():
    data = _data(hosts=6)
    a = generate.pool_ticks(data, SEED, 60)
    b = generate.pool_ticks(data, SEED + 1, 60)
    assert np.array_equal(a.offsets, b.offsets)
    assert not any(np.array_equal(x, y) for x, y in zip(a.values, b.values))
    c = generate.pool_ticks(_data(hosts=6, schedule_seed=17), SEED, 60)
    assert not np.array_equal(a.offsets, c.offsets)
    # a longer pool and a larger fleet extend the schedule, moving nothing
    longer = tsbs_iot.online(data, 90)
    assert np.array_equal(longer[:60], tsbs_iot.online(data, 60))
    assert np.array_equal(tsbs_iot.online(_data(hosts=9), 60)[:, :6], longer[:60])


def test_fields_walk_in_their_ranges():
    data = _data(hosts=4)
    pool = generate.pool_ticks(data, SEED, 24)
    fields = tsbs_iot.fields(data)
    for sid, v in enumerate(pool.values):
        f = fields[sid % len(fields)]
        assert v.size == 24 * tsbs_iot.per_tick(data)
        assert f["lo"] <= v.min() and v.max() <= f["hi"]
        assert np.array_equal(v, np.round(v, f["decimals"]))
        steps = np.diff(v)
        assert np.abs(steps).max() <= 6 * f["step"] + 10.0 ** -f["decimals"]


def test_backlogs_arrive_whole_and_in_order():
    data = _data(hosts=40)
    ticks, k, n_fields = 400, tsbs_iot.per_tick(data), len(tsbs_iot.fields(data))
    pool = generate.pool_ticks(data, SEED, ticks)
    on = tsbs_iot.online(data, ticks)
    assert 0.07 < 1.0 - on.mean() < 0.13  # 10% of truck-hours offline
    lengths = tsbs_iot.outage_lengths(data)
    assert lengths.min() == 1 and lengths.max() <= 24
    got: dict[int, list] = {}
    for t in range(ticks):
        for sid, chunk in pool.tick(t):
            assert on[t, sid // n_fields]  # only a truck online delivers
            got.setdefault(sid, []).append(chunk)
            # everything it has read up to the tick's end, after what it sent
            assert pool.offsets[t + 1, sid] == (t + 1) * k
    backlogs = 0
    for sid, v in enumerate(pool.values):
        n = int(pool.offsets[-1, sid])
        assert np.array_equal(np.concatenate(got[sid]), v[:n])
        backlogs += sum(c.size > k for c in got[sid])
    assert backlogs > 0


def test_control_and_a_planted_fault_are_not_correct(monkeypatch):
    _, line = run_tiny(CELL, control=True)
    assert line["correct"] is False
    failed = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert failed == {"lossless_mismatches", "bytes_per_sample_tier0"}
    _altered_batch(monkeypatch)
    _, line = run_tiny(CELL)
    assert line["correct"] is False
    assert line["checks"]["lossless_mismatches"]["value"] > 0
