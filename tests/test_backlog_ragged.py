"""Backlogged fleet traffic through the ragged flush: trucks that come back
from an outage deliver hours of readings at once, so one flush holds
day-long series, series that stopped part-way and backlogs several times
the median, of fields whose scales lie orders of magnitude apart.

The ragged flush must seal exactly what a per-series ``compress`` loop
seals, and the device rANS engine must run such jobs in the fixed blocks
of their step class (``kernels.rans.ragged_blocks``), with the wire bytes
of the numpy machine and cell counts that follow from the rule."""
import struct

import numpy as np
import pytest

from repro.core import ShrinkCodec, ShrinkConfig, cs_to_bytes, entropy
from repro.core.serialize import frame_payload, parse_framed_container
from repro.core.streaming import decode_series
from repro.kernels import ops, rans
from repro.kernels.calls import cell_counts
from repro.serving.ragged import RaggedBatcher

_RNG = np.random.default_rng(1616)
TIERS = [0.5, 0.0]
# (lo, hi, step, decimals) of the TSBS iot fields, as bench/configs/tsbs_iot.json
FIELDS = [
    (-90.0, 90.0, 0.001, 4), (-180.0, 180.0, 0.001, 4), (0.0, 5000.0, 1.0, 4),
    (0.0, 100.0, 1.0, 4), (0.0, 360.0, 1.0, 4), (0.0, 100.0, 1.0, 4),
    (0.0, 50.0, 1.0, 4), (0.0, 5000.0, 1.0, 4), (0.0, 1.0, 0.01, 4), (0.0, 5.0, 1.0, 0),
]
TICK = 360  # an hour of 10 s readings


def _walk(n: int, lo: float, hi: float, step: float, decimals: int) -> np.ndarray:
    x, out = _RNG.uniform(lo, hi), np.empty(n)
    for t, d in enumerate(_RNG.standard_normal(n) * step):
        x = min(max(x + d, lo), hi)
        out[t] = x
    return np.round(out, decimals)


def _fleet() -> tuple[list[list[np.ndarray]], list[int]]:
    """Per truck, its ten fields' pending readings and the ticks they came
    in: a day-long truck, one back with a backlog four times the median,
    one that stopped part-way, and one that sent under 64 readings."""
    ticks = [[TICK] * 4, [4 * 4 * TICK], [TICK, TICK, 180], [40]]
    trucks = [[_walk(sum(tk), *f) for f in FIELDS] for tk in ticks]
    return trucks, ticks


def _cells(name: str) -> tuple[int, int]:
    return cell_counts().get(name, (0, 0))


@pytest.mark.parametrize("device", ["0", "1"])
def test_backlog_flush_matches_per_series_compress(device, rans_engine):
    trucks, ticks = _fleet()
    cfg = ShrinkConfig(eps_b=5.0, lam=1e-5)
    codec = ShrinkCodec(config=cfg, backend="rans")
    series = [v for fields in trucks for v in fields]
    with rans_engine("numpy"):
        want = [cs_to_bytes(codec.compress(v, eps_targets=TIERS, decimals=4)) for v in series]
        empty = cs_to_bytes(codec.compress(np.zeros(0), eps_targets=TIERS, decimals=4))

    b = RaggedBatcher(cfg, eps_targets=TIERS, decimals=4, flush_samples=None,
                      semantics="numpy")
    with rans_engine({"0": "numpy", "1": "device"}[device]):
        for truck, (fields, tk) in enumerate(zip(trucks, ticks)):
            at = np.cumsum([0] + tk)
            for lo, hi in zip(at[:-1], at[1:]):  # a backlog is one chunk
                for f, v in enumerate(fields):
                    b.submit(truck * len(FIELDS) + f, v[lo:hi])
        assert b.submit(len(series), np.zeros(0)) == []  # an empty series seals nothing
        real0, run0 = _cells("cone_scan")
        blob = b.finalize()
        real1, run1 = _cells("cone_scan")
        batch = codec.compress_batch(series + [np.zeros(0)], eps_targets=TIERS,
                                     decimals=4, semantics="numpy")
    # the host scan pads nothing: every cell it notes is a sample
    n_total = sum(v.size for v in series)
    assert (real1 - real0, run1 - run0) == (n_total, n_total)
    assert [cs_to_bytes(cs) for cs in batch] == want + [empty]

    metas, _ = parse_framed_container(blob)
    assert sorted(m.series_id for m in metas) == list(range(len(series)))
    for m in metas:
        assert (m.t_lo, m.t_hi) == (0, series[m.series_id].size)
        assert frame_payload(blob, m) == want[m.series_id], m.series_id
    for sid, v in enumerate(series):
        np.testing.assert_array_equal(decode_series(blob, sid, 0.0), v)
        err = np.abs(decode_series(blob, sid, 0.5) - v)
        assert err.max(initial=0.0) <= 0.5 * (1 + 1e-9), sid


def _mix(rng: np.random.Generator) -> list[np.ndarray]:
    """Streams of log-uniform lengths (empty and shorter than the 64
    interleaved states among them) and of 1 to 3 byte planes."""
    while True:
        n = np.exp(rng.uniform(0, np.log(20_000), size=int(rng.integers(3, 30))))
        lengths = np.where(rng.random(n.size) < 0.1, 0, n.astype(np.int64))
        if np.unique(lengths).size > 1:
            break
    scales = rng.choice([20.0, 2_000.0, 200_000.0], size=lengths.size)
    return [np.round(rng.standard_normal(k) * s).astype(np.int64)
            for k, s in zip(lengths.tolist(), scales)]


def _expected_cells(qs, blobs) -> tuple[int, int]:
    """Real and dispatched cells of the class rule: each plane of a stream
    of at least K symbols is a row of its step class; a class's rows run
    in whole blocks."""
    k = rans._K
    real, rows_of = 0, {}
    for q, blob in zip(qs, blobs):
        if q.size < k:
            continue  # the scalar coder: fewer states, no engine
        planes = struct.unpack_from("<qQBB", blob, 1)[2]
        real += q.size * planes
        steps, rows = rans.class_shape(-(-q.size // k))
        rows_of[(steps, rows)] = rows_of.get((steps, rows), 0) + planes
    run = sum(-(-n // rows) * rows * steps * k for (steps, rows), n in rows_of.items())
    return real, run


@pytest.mark.parametrize("draw", range(20))
def test_ragged_engine_runs_fixed_class_blocks(draw, monkeypatch, rans_engine):
    qs = _mix(np.random.default_rng([1616, draw]))
    with rans_engine("numpy"):
        want = entropy.encode_ints_batch(qs, backend="rans")
    shapes = []
    scan = rans._enc_ref_jit

    def recording(sym_cube, *a, **kw):
        shapes.append(sym_cube.shape)
        return scan(sym_cube, *a, **kw)

    monkeypatch.setattr(rans, "_enc_ref_jit", recording)
    with rans_engine("device"):
        real0, run0 = _cells("rans_encode")
        got = entropy.encode_ints_batch(qs, backend="rans")
        real1, run1 = _cells("rans_encode")
    assert got == want
    longest = max(-(-q.size // rans._K) for q in qs)
    classes = {rans.class_shape(1 << e) for e in range(5, longest.bit_length() + 1)}
    assert len(classes) <= max(1, longest.bit_length() - 4)
    for steps, rows, lanes in shapes:
        assert (steps, rows) in classes and lanes == rans._K
    assert (real1 - real0, run1 - run0) == _expected_cells(qs, want)


def test_class_blocks_partition_the_rows():
    steps = np.array([1, 12, 32, 33, 64, 65, 259, 512, 513, 1025] * 60)
    blocks = rans.ragged_blocks(steps)
    assert np.array_equal(np.sort(np.concatenate(blocks)), np.arange(steps.size))
    for ids in blocks:
        shapes = {rans.class_shape(s) for s in steps[ids].tolist()}
        assert len(shapes) == 1
        (c, rows), = shapes
        assert ids.size <= rows and c >= steps[ids].max()
    assert rans.class_shape(12) == rans.class_shape(32) == (32, 256)
    assert rans.class_shape(259) == (512, 256) and rans.class_shape(513) == (1024, 128)


def test_bucketed_scan_notes_real_and_dispatched_cells():
    """The device cone scan pads T to a power of two and S to whole lane
    groups; it notes the lanes' lengths as real cells and the padded shape
    as dispatched."""
    t, lengths = 100, np.array([100, 37, 1, 64, 99], np.int32)
    x = np.cumsum(_RNG.standard_normal((t, lengths.size)), axis=0).astype(np.float32)
    eps = np.full_like(x, 0.5)
    real0, run0 = _cells("cone_scan")
    ops.cone_scan(x, eps, lengths=lengths)
    real1, run1 = _cells("cone_scan")
    assert (real1 - real0, run1 - run0) == (int(lengths.sum()), 128 * 128)
