"""Device rANS engine parity: kernels.rans vs the numpy wire machine.

Two layers of byte-identity, per the kernel-testing contract:

* route parity — ``encode_rows``/``decode_rows`` must return identical
  states/words/symbols on the jit'd-scan route (``xla``, the CPU
  production path) and the Pallas ``interpret`` route (the kernel body
  with the real block/grid decomposition).  Shapes are kept small: the
  interpret grid runs one Python-dispatched step per grid index.
* wire parity — forcing ``core.entropy``'s device engine on/off via the
  ``SHRINK_RANS_DEVICE`` override must produce byte-identical blobs for
  scalar, rect-batch, and ragged-batch encodes across the edge shapes
  (empty, one symbol, n < K lanes, single-plane, 8-plane int64
  extremes), and the device engine must actually have run (the kernel
  call counters move), so no parity assertion compares numpy to numpy.
"""
import contextlib
import os

import numpy as np
import pytest

from repro.core import entropy

jax = pytest.importorskip("jax", reason="kernel parity suite needs jax")

from repro.kernels import rans  # noqa: E402
from repro.kernels.calls import call_counts  # noqa: E402

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
}


@pytest.mark.parametrize("name", sorted(_ROW_SPECS))
def test_route_parity_encode_decode(name):
    sym, freqs, lens = _rows(_ROW_SPECS[name])
    st_x, w_x = rans.encode_rows(sym, freqs, route="xla")
    st_i, w_i = rans.encode_rows(sym, freqs, route="interpret")
    np.testing.assert_array_equal(st_x, st_i)
    assert len(w_x) == len(w_i)
    for a, b in zip(w_x, w_i):
        np.testing.assert_array_equal(a, b)
    n = sym.shape[1]
    out_x = rans.decode_rows(st_x, freqs, w_x, n, route="xla")
    out_i = rans.decode_rows(st_x, freqs, w_x, n, route="interpret")
    np.testing.assert_array_equal(out_x, out_i)
    # each row's real prefix round-trips; positions past a row's length are
    # identity padding (byte-exact no-ops on the wire, undefined on decode)
    for i, ln in enumerate(lens):
        np.testing.assert_array_equal(out_x[i, :ln], sym[i, :ln].astype(np.uint8))


def test_identity_pad_lanes_emit_no_words():
    """A row that is pure identity padding must keep its states at L and
    emit zero renorm words — the invariant the pow2 shape bucketing
    relies on for byte-exactness."""
    sym = np.full((1, 256), rans._ID, dtype=np.uint16)
    freqs = np.zeros((1, 256), dtype=np.int64)
    freqs[0, 0] = rans._M  # normalized table for an all-zeros row (unused)
    states, words = rans.encode_rows(sym, freqs, route="xla")
    np.testing.assert_array_equal(states, np.full((1, rans._K), rans._L, np.uint32))
    assert words[0].size == 0


# --------------------------------------------------------------------- #
# Wire parity: core.entropy with the device engine forced on vs off
# --------------------------------------------------------------------- #
@contextlib.contextmanager
def _device_mode(mode: str):
    saved = os.environ.get("SHRINK_RANS_DEVICE")
    os.environ["SHRINK_RANS_DEVICE"] = mode
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop("SHRINK_RANS_DEVICE", None)
        else:
            os.environ["SHRINK_RANS_DEVICE"] = saved


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
def test_scalar_wire_bytes_identical(name):
    q = _WIRE[name]
    with _device_mode("0"):
        blob_np = entropy.encode_ints(q, backend="rans")
    with _device_mode("1"):
        before = _engine_calls("encode")
        blob_dev = entropy.encode_ints(q, backend="rans")
        if q.size >= rans._K:  # shorter streams use fewer states: numpy only
            assert _engine_calls("encode") > before
        np.testing.assert_array_equal(entropy.decode_ints(blob_dev), q)
    assert blob_np == blob_dev
    np.testing.assert_array_equal(entropy.decode_ints(blob_np), q)


def test_device_engine_engages_on_big_stream():
    """With the override on and a >= K stream, both directions must run on
    the engine — otherwise every parity assertion above would vacuously
    compare numpy to numpy."""
    q = _WIRE["gaussian_5k"]
    with _device_mode("1"):
        enc, dec = _engine_calls("encode"), _engine_calls("decode")
        entropy.decode_ints(entropy.encode_ints(q, backend="rans"))
        assert _engine_calls("encode") == enc + 1
        assert _engine_calls("decode") == dec + 1


def test_rect_batch_wire_bytes_identical():
    qs = [
        np.round(_RNG.standard_normal(2_048) * 150).astype(np.int64)
        for _ in range(6)
    ]
    with _device_mode("0"):
        blobs_np = entropy.encode_ints_batch(qs, backend="rans")
    with _device_mode("1"):
        before = _engine_calls("encode")
        blobs_dev = entropy.encode_ints_batch(qs, backend="rans")
        assert _engine_calls("encode") > before
    assert blobs_np == blobs_dev
    for blob, q in zip(blobs_dev, qs):
        np.testing.assert_array_equal(entropy.decode_ints(blob), q)


def test_ragged_batch_wire_bytes_identical():
    """Ragged lane groups: lengths spanning sub-K, multi-step, and empty
    rows exercise the identity-pad grouping in the batch encoder."""
    lens = [1_700, 300, 900, 64, 10, 800, 1_700, 0, 63]
    qs = [
        np.round(_RNG.standard_normal(n) * 120).astype(np.int64) for n in lens
    ]
    with _device_mode("0"):
        blobs_np = entropy.encode_ints_batch(qs, backend="rans")
    with _device_mode("1"):
        before = _engine_calls("encode")
        blobs_dev = entropy.encode_ints_batch(qs, backend="rans")
        assert _engine_calls("encode") > before
    assert blobs_np == blobs_dev
    for blob, q in zip(blobs_dev, qs):
        np.testing.assert_array_equal(entropy.decode_ints(blob), q)


def test_device_decode_matches_numpy_decode():
    """A numpy-encoded blob must decode identically through the device
    path (and vice versa) — decoder symmetry, not just encoder parity."""
    q = _WIRE["gaussian_5k"]
    with _device_mode("0"):
        blob = entropy.encode_ints(q, backend="rans")
    with _device_mode("1"):
        np.testing.assert_array_equal(entropy.decode_ints(blob), q)
