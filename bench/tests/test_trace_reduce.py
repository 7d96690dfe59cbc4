"""The reduction from a profiler trace to the benchmark's device numbers."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce

RECORDED = sorted((Path(__file__).resolve().parent / "data").glob("*.xplane.pb"))


def ev(name, start, dur):
    return NS(name=name, start_ns=start, end_ns=start + dur, duration_ns=dur)


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def fake_trace():
    host = plane("/host:CPU", {"python": [ev("bench.window", 1000, 9000)]})
    dev = plane("/device:TPU:0", {
        "XLA Ops": [ev("fusion.1", 500, 1000),  # starts before the window
                    ev("custom-call.2", 2000, 1000), ev("custom-call.2", 2500, 1000),
                    ev("while.3", 6000, 2000)],
        "XLA Modules": [ev("jit_cone_scan_pallas(1)", 2000, 1500),
                        ev("jit_rans_encode_ref(2)", 6000, 2000)],
    })
    return NS(planes=[host, dev])


def test_reduce_busy_kernels_and_gaps():
    pd = fake_trace()
    win = trace_reduce.window_of(pd)
    assert win == (1000, 10000)
    spans = [("bench.admit", 1000, 4000), ("bench.flush", 4000, 9000)]
    r = trace_reduce.reduce(pd, win, spans)
    # busy: [1000, 1500) + [2000, 3500) + [6000, 8000) of a 9000 ns window
    assert r["window_s"] == pytest.approx(9e-6)
    assert r["busy_s"] == pytest.approx(4e-6)
    assert r["kernel_s"] == pytest.approx({"cone_scan": 1.5e-6, "rans_encode": 2e-6})
    assert r["device_ops"][0] == ["custom-call.2", pytest.approx(2e-6)]
    gaps = dict(r["idle_gaps"])
    # idle [1500, 2000) and [3500, 4000) under admit, [4000, 6000) and
    # [8000, 9000) under flush, [9000, 10000) under no span
    assert gaps == pytest.approx({"bench.admit": 1e-6, "bench.flush": 3e-6,
                                  "host.other": 1e-6})


def test_reduce_keeps_only_the_cells_chips():
    """A chip the cell does not use sits idle in the trace; it counts only
    where the reduction is not told which chips the cell uses."""
    pd = fake_trace()
    pd.planes.append(plane("/device:TPU:1", {"XLA Ops": [], "XLA Modules": []}))
    win = trace_reduce.window_of(pd)
    r = trace_reduce.reduce(pd, win, devices=[0])
    assert [d["plane"] for d in r["devices"]] == ["/device:TPU:0"]
    assert r["busy_s"] == pytest.approx(4e-6)
    both = trace_reduce.reduce(pd, win)
    assert len(both["devices"]) == 2 and both["busy_s"] == pytest.approx(2e-6)


def test_dropped_buffers_end_the_window():
    """A device that dropped trace buffers ends the window at its last
    recorded operation: the lost tail is not idle time."""
    pd = fake_trace()
    pd.planes[1].stats = [("device_type_string", "TPU v5 Lite"), ("dropped_traces", 3)]
    win = trace_reduce.window_of(pd)
    r = trace_reduce.reduce(pd, win, [("bench.flush", 1000, 10000)])
    # the last op ends at 8000: busy 4000 ns of a 7000 ns window
    assert r["dropped_traces"] == 3
    assert r["window_s"] == pytest.approx(7e-6)
    assert r["busy_s"] == pytest.approx(4e-6)
    assert dict(r["idle_gaps"]) == pytest.approx({"bench.flush": 3e-6})


def test_union_and_gaps():
    assert trace_reduce._union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert trace_reduce._gaps([(1, 4), (5, 8)], 0, 10) == [(0, 1), (4, 5), (8, 10)]


@pytest.mark.skipif(not RECORDED, reason="no recorded trace under bench/tests/data")
def test_recorded_chip_trace():
    """A short window recorded on one v5e: the reduction finds the device,
    the kernels the window ran and a busy time inside the window."""
    r = trace_reduce.reduce_file(RECORDED[0])
    assert len(r["devices"]) == 1 and r["devices"][0]["plane"].startswith("/device:TPU:")
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["kernel_s"].get("cone_scan", 0) > 0 and r["kernel_s"].get("rans_encode", 0) > 0
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(r["window_s"] - r["busy_s"])
