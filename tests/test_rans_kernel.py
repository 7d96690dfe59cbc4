"""Device rANS engine parity: kernels.rans vs the numpy wire machine.

Two layers of byte-identity, per the kernel-testing contract:

* machine parity — ``encode_rows`` must return the states and words of
  ``core.entropy._rans_machine``, and ``decode_rows`` the symbols of
  ``core.entropy._rans_decode_plane``, row by row.
* wire parity — forcing ``core.entropy``'s device engine on/off (the
  ``rans_engine`` fixture) must produce byte-identical blobs for scalar,
  rect-batch, and ragged-batch encodes across the edge shapes (empty, one
  symbol, n < K lanes, single-plane, 8-plane int64 extremes), and the
  device engine must actually have run (the kernel call counters move),
  so no parity assertion compares numpy to numpy.
"""
import struct

import numpy as np
import pytest

from repro.core import entropy

jax = pytest.importorskip("jax", reason="kernel parity suite needs jax")

from repro.kernels import rans  # noqa: E402
from repro.kernels.calls import call_counts, cell_counts  # noqa: E402

_RNG = np.random.default_rng(20260808)


def _rows(shape_spec):
    """Build (sym_mat, freqs) for a list of per-row symbol streams."""
    streams = []
    for n, hi in shape_spec:
        streams.append(_RNG.integers(0, hi, n).astype(np.int64))
    cols = max((s.size for s in streams), default=1)
    sym = np.full((len(streams), max(1, cols)), rans._ID, dtype=np.uint16)
    freqs = np.empty((len(streams), 256), dtype=np.int64)
    for i, s in enumerate(streams):
        sym[i, : s.size] = s
        counts = np.bincount(s.astype(np.int64), minlength=256)
        freqs[i] = entropy._rans_normalize_freqs(counts)
    lens = [s.size for s in streams]
    return sym, freqs, lens


_ROW_SPECS = {
    "one_row_one_step": [(64, 16)],
    "three_rows_ragged_pad": [(200, 8), (64, 250), (130, 2)],
    "four_rows_two_steps": [(128, 256)] * 4,
    "single_symbol_rows": [(96, 1), (96, 1)],
    "sub_lane_row": [(1, 4)],  # cols < K: every lane but 0 is identity pad
    # 17 rows and 17 steps: both axes pad to 18, not to 32
    "off_grid_rows_and_steps": [(17 * 64, 200)] * 16 + [(16 * 64 + 5, 30)],
}


@pytest.mark.parametrize("name", sorted(_ROW_SPECS))
def test_route_parity_encode_decode(name):
    sym, freqs, lens = _rows(_ROW_SPECS[name])
    # the numpy machine takes its rows longest first
    order = np.argsort(-np.array(lens), kind="stable")
    sym, freqs, lens = sym[order], freqs[order], np.array(lens)[order]
    st_d, w_d = rans.encode_rows(sym, freqs)
    st_n, w_n = entropy._rans_machine(sym, freqs, lens, rans._K)
    np.testing.assert_array_equal(st_d, st_n)
    assert len(w_d) == len(w_n)
    for a, b in zip(w_d, w_n):
        np.testing.assert_array_equal(a, b)
    out_d = rans.decode_rows(st_d, freqs, w_d, sym.shape[1])
    # each row's real prefix round-trips; positions past a row's length are
    # identity padding (byte-exact no-ops on the wire, undefined on decode)
    for i, ln in enumerate(lens.tolist()):
        body = (st_d[i].astype("<u4").tobytes() + struct.pack("<I", w_d[i].size)
                + w_d[i].astype("<u2").tobytes())
        cums = np.concatenate(([0], np.cumsum(freqs[i])[:-1]))
        out_n, end = entropy._rans_decode_plane(body, 0, ln, freqs[i], cums, rans._K)
        assert end == len(body)
        np.testing.assert_array_equal(out_d[i, :ln], out_n)
        np.testing.assert_array_equal(out_d[i, :ln], sym[i, :ln].astype(np.uint8))


def test_identity_pad_lanes_emit_no_words():
    """A row that is pure identity padding must keep its states at L and
    emit zero renorm words — the invariant the shape bucketing relies on
    for byte-exactness."""
    sym = np.full((1, 256), rans._ID, dtype=np.uint16)
    freqs = np.zeros((1, 256), dtype=np.int64)
    freqs[0, 0] = rans._M  # normalized table for an all-zeros row (unused)
    states, words = rans.encode_rows(sym, freqs)
    np.testing.assert_array_equal(states, np.full((1, rans._K), rans._L, np.uint32))
    assert words[0].size == 0


# --------------------------------------------------------------------- #
# Shape bucket of the step and row axes
# --------------------------------------------------------------------- #
_BUCKET_LIMIT = 100_000


@pytest.mark.parametrize("octave", range((_BUCKET_LIMIT - 1).bit_length() + 1))
def test_bucket_properties(octave):
    """Over the sizes ``(2^(octave-1), 2^octave]`` (capped at 100,000), the
    bucket covers the size, is exact up to 16, wastes under 1/8 above it,
    takes at most 8 values, and is monotone and idempotent."""
    lo, hi = (1 << octave) // 2 + 1, min(1 << octave, _BUCKET_LIMIT)
    vs = range(lo, hi + 1)
    bs = [rans._bucket(v) for v in vs]
    for v, b in zip(vs, bs):
        assert b >= v
        if v <= 16:
            assert b == v
        else:
            assert (b - v) * 8 < v
        assert rans._bucket(b) == b
    assert len(set(bs)) <= 8
    assert all(a <= b for a, b in zip(bs, bs[1:]))
    assert rans._bucket(hi + 1) >= bs[-1]


# --------------------------------------------------------------------- #
# Wire parity: core.entropy with the device engine forced on vs off
# --------------------------------------------------------------------- #
def _engine_calls(kind: str) -> int:
    """Device rANS calls of ``kind`` ("encode"/"decode") so far."""
    return sum(v for (name, _p, _d), v in call_counts().items()
               if name == f"rans_{kind}")


def _wire_streams() -> dict[str, np.ndarray]:
    return {
        "empty": np.zeros(0, dtype=np.int64),
        "one_symbol": np.array([-42], dtype=np.int64),
        "sub_k": _RNG.integers(-100, 100, 63).astype(np.int64),  # n < K: scalar k
        "exactly_k": _RNG.integers(-100, 100, 64).astype(np.int64),
        "single_plane": _RNG.integers(-64, 64, 1_000).astype(np.int64),
        "eight_plane_extremes": np.concatenate(
            [
                np.array([0, 1, -1, 2**62, -(2**62), 2**63 - 1, -(2**63) + 1]),
                _RNG.integers(-(2**45), 2**45, 500),
            ]
        ).astype(np.int64),
        "gaussian_5k": np.round(
            _RNG.standard_normal(5_000) * 200
        ).astype(np.int64),
    }


_WIRE = _wire_streams()


@pytest.mark.parametrize("name", sorted(_WIRE))
def test_scalar_wire_bytes_identical(name, rans_engine):
    q = _WIRE[name]
    with rans_engine("numpy"):
        blob_np = entropy.encode_ints(q, backend="rans")
    with rans_engine("device"):
        before = _engine_calls("encode")
        blob_dev = entropy.encode_ints(q, backend="rans")
        if q.size >= rans._K:  # shorter streams use fewer states: numpy only
            assert _engine_calls("encode") > before
        np.testing.assert_array_equal(entropy.decode_ints(blob_dev), q)
    assert blob_np == blob_dev
    np.testing.assert_array_equal(entropy.decode_ints(blob_np), q)


def test_device_engine_engages_on_big_stream(rans_engine):
    """With the override on and a >= K stream, both directions must run on
    the engine — otherwise every parity assertion above would vacuously
    compare numpy to numpy."""
    q = _WIRE["gaussian_5k"]
    with rans_engine("device"):
        enc, dec = _engine_calls("encode"), _engine_calls("decode")
        entropy.decode_ints(entropy.encode_ints(q, backend="rans"))
        assert _engine_calls("encode") == enc + 1
        assert _engine_calls("decode") == dec + 1


def test_rect_batch_wire_bytes_identical(rans_engine):
    qs = [
        np.round(_RNG.standard_normal(2_048) * 150).astype(np.int64)
        for _ in range(6)
    ]
    with rans_engine("numpy"):
        blobs_np = entropy.encode_ints_batch(qs, backend="rans")
    with rans_engine("device"):
        before = _engine_calls("encode")
        blobs_dev = entropy.encode_ints_batch(qs, backend="rans")
        assert _engine_calls("encode") > before
    assert blobs_np == blobs_dev
    for blob, q in zip(blobs_dev, qs):
        np.testing.assert_array_equal(entropy.decode_ints(blob), q)


def test_ragged_batch_wire_bytes_identical(rans_engine):
    """Ragged lane groups: lengths spanning sub-K, multi-step, and empty
    rows exercise the identity-pad grouping in the batch encoder."""
    lens = [1_700, 300, 900, 64, 10, 800, 1_700, 0, 63]
    qs = [
        np.round(_RNG.standard_normal(n) * 120).astype(np.int64) for n in lens
    ]
    with rans_engine("numpy"):
        blobs_np = entropy.encode_ints_batch(qs, backend="rans")
    with rans_engine("device"):
        before = _engine_calls("encode")
        blobs_dev = entropy.encode_ints_batch(qs, backend="rans")
        assert _engine_calls("encode") > before
    assert blobs_np == blobs_dev
    for blob, q in zip(blobs_dev, qs):
        np.testing.assert_array_equal(entropy.decode_ints(blob), q)


def test_device_decode_matches_numpy_decode(rans_engine):
    """A numpy-encoded blob must decode identically through the device
    path (and vice versa) — decoder symmetry, not just encoder parity."""
    q = _WIRE["gaussian_5k"]
    with rans_engine("numpy"):
        blob = entropy.encode_ints(q, backend="rans")
    with rans_engine("device"):
        np.testing.assert_array_equal(entropy.decode_ints(blob), q)


def _cells(kind: str) -> tuple[int, int]:
    return cell_counts().get(f"rans_{kind}", (0, 0))


def test_off_grid_rect_batch_wire_bytes_and_cells(rans_engine):
    """37 streams of 8,640 samples, 2 of them two-plane: 39 rows of 135
    steps, which the bucket pads to 40 rows of 144 steps (a power of two
    would pad to 64 x 256).  Wire bytes match the numpy machine, every
    blob decodes through ``decode_rows``, and the cell counters show the
    padding the bucket implies."""
    n, streams = 8_640, 37
    qs = np.round(_RNG.standard_normal((streams, n)) * 20).astype(np.int64)
    qs[:2] *= 100  # past 255 after zigzag: a second byte plane
    with rans_engine("numpy"):
        blobs_np = entropy.encode_ints_batch(qs, backend="rans")
    with rans_engine("device"):
        real0, run0 = _cells("encode")
        blobs_dev = entropy.encode_ints_batch(qs, backend="rans")
        real1, run1 = _cells("encode")
        rows = streams + 2
        assert (real1 - real0, run1 - run0) == (rows * n, 144 * 40 * rans._K)
        assert blobs_np == blobs_dev
        for i, (blob, q) in enumerate(zip(blobs_dev, qs)):
            planes = 2 if i < 2 else 1
            real0, run0 = _cells("decode")
            np.testing.assert_array_equal(entropy.decode_ints(blob), q)
            real1, run1 = _cells("decode")
            assert (real1 - real0, run1 - run0) == (planes * n, 144 * planes * rans._K)
