"""Device preparation of rectangular rANS jobs: byte parity with the numpy
machine.

``kernels.rans_prep`` takes a rectangular job's median, zigzag, byte
planes and histograms onto the device when every value lies in
[-2^30, 2^30); ``kernels.rans.encode_cube`` packs the scan's words on the
device for every caller.  Each blob must equal the numpy machine's
(``SHRINK_RANS_DEVICE=0``) byte for byte, and the ``rans_prep`` cell counts
must show which rows were prepared on the device: every row of a job in
range, none of a job out of it.
"""
import numpy as np
import pytest

from repro.core import entropy

jax = pytest.importorskip("jax", reason="device prep suite needs jax")

from repro.kernels import rans, rans_prep  # noqa: E402
from repro.kernels.calls import cell_counts  # noqa: E402

from test_rans_kernel import _device_mode, _rows  # noqa: E402

_RNG = np.random.default_rng(20261019)
_LIM = rans_prep.LIMIT


def _prep_cells() -> tuple[int, int]:
    return cell_counts().get("rans_prep", (0, 0))


def _rows_of(qs: np.ndarray) -> int:
    """Plane rows of a job, from the numpy machine's own front end."""
    return entropy._rans_prep_rect(qs)[2].size


def _planes_job(n: int) -> np.ndarray:
    """Eight streams of n samples with 1, 2, 3 and 4 byte planes."""
    scales = [20, 20, 3_000, 3_000, 600_000, 600_000, 100_000_000, _LIM // 2]
    return np.stack(
        [np.round(_RNG.standard_normal(n) * s).clip(-_LIM, _LIM - 1) for s in scales]
    ).astype(np.int64)


def _edge_jobs() -> dict[str, np.ndarray]:
    n = 8_640
    tsbs = np.round(_RNG.standard_normal((37, n)) * 20).astype(np.int64)
    tsbs[:3] *= 100  # two planes
    tsbs[3] *= 200_000  # three
    halves = np.zeros((4, 1_000), dtype=np.int64)
    halves[:, :500] = [[-3], [-1], [5], [-(2**30)]]
    halves[:, 500:] = [[0], [0], [-4], [2**30 - 1]]
    extremes = _RNG.integers(-(_LIM - 1), _LIM, (5, 700))
    extremes[:, :2] = [-(_LIM - 1), _LIM - 1]
    constant = np.full((3, 4_096), 17, dtype=np.int64)
    constant[1] = -5
    constant[2, :] = _RNG.integers(0, 4, 4_096)
    return {
        "tsbs_cut": tsbs,
        "n64": _planes_job(64),
        "n65_odd": _planes_job(65),
        "n1000_even": _planes_job(1_000),
        "n1001_off_bucket": _planes_job(1_001),
        "n8639": _planes_job(8_639),
        # even n whose middle pair sums odd and negative: truncation toward
        # zero, not floor, and the widest pair the range holds
        "middle_pair_halves": halves,
        "near_limit": extremes,
        "constant_rows": constant,
    }


_JOBS = _edge_jobs()


def _encode_both(qs):
    with _device_mode("0"):
        blobs_np = entropy.encode_ints_batch(qs, backend="rans")
    with _device_mode("1"):
        before = _prep_cells()
        blobs_dev = entropy.encode_ints_batch(qs, backend="rans")
        after = _prep_cells()
    return blobs_np, blobs_dev, (after[0] - before[0], after[1] - before[1])


@pytest.mark.parametrize("name", sorted(_JOBS))
def test_device_prep_wire_bytes_identical(name):
    qs = _JOBS[name]
    assert rans_prep.fits(qs)
    blobs_np, blobs_dev, cells = _encode_both(qs)
    rows = _rows_of(qs)
    assert cells == (rows, rows)
    assert blobs_dev == blobs_np
    for blob, q in zip(blobs_dev, qs):
        np.testing.assert_array_equal(entropy.decode_ints(blob), q)


def test_planes_job_mixes_one_to_four_planes():
    med, nplanes, *_ = entropy._rans_prep_rect(_JOBS["n1000_even"])
    assert sorted(set(nplanes.tolist())) == [1, 2, 3, 4]


def test_constant_row_takes_the_whole_table():
    """A stream of one value codes one symbol at freq = M and emits no
    word."""
    blobs_np, blobs_dev, _ = _encode_both(_JOBS["constant_rows"])
    assert blobs_dev == blobs_np
    med, nplanes, src, cube, counts = rans_prep.prepare(_JOBS["constant_rows"])
    freqs = entropy._rans_normalize_freqs_rows(counts)
    assert freqs[0].max() == entropy._RANS_M and freqs[1].max() == entropy._RANS_M
    _states, words = rans.encode_cube(cube, freqs, src.size * 4_096)
    assert words[0].size == 0 and words[1].size == 0 and words[2].size > 0


@pytest.mark.parametrize(
    "value", [_LIM, -_LIM - 1, 2**63 - 1, -(2**63)],
    ids=["2^30", "-2^30-1", "i64max", "i64min"],
)
def test_out_of_range_job_prepares_on_the_host(value):
    qs = np.round(_RNG.standard_normal((6, 2_048)) * 150).astype(np.int64)
    qs[4, 1_000] = value
    assert not rans_prep.fits(qs)
    blobs_np, blobs_dev, cells = _encode_both(qs)
    assert cells == (0, _rows_of(qs))
    assert blobs_dev == blobs_np


def test_auto_mode_prepares_big_jobs_on_the_device():
    """Unforced, a job past the engine's threshold engages the device prep
    by its range alone; a numpy-only run counts nothing."""
    qs = _JOBS["tsbs_cut"]
    before = _prep_cells()
    with _device_mode("auto"):
        entropy.encode_ints_batch(qs, backend="rans")
    after = _prep_cells()
    rows = _rows_of(qs)
    assert (after[0] - before[0], after[1] - before[1]) == (rows, rows)
    with _device_mode("0"):
        entropy.encode_ints_batch(qs, backend="rans")
    assert _prep_cells() == after


def test_device_median_matches_numpy():
    """The bisection median equals ``_median_i64`` row by row, repeats and
    negative odd middle sums included."""
    qs = np.concatenate([
        _RNG.integers(-7, 7, (40, 130)),
        _RNG.integers(-_LIM, _LIM, (40, 130)),
    ])
    med = rans_prep.prepare(qs)[0]
    np.testing.assert_array_equal(med, entropy._median_i64(qs, axis=1))


def test_compaction_matches_boolean_extraction_on_a_ragged_block():
    """The device word packing of a ragged class block equals the boolean
    extraction over the scan's dense need/vals, row by row, with zeros
    after each row's words."""
    sym, freqs, lens = _rows([(200, 8), (64, 250), (1_300, 40), (900, 2), (2_048, 256)])
    steps = -(-sym.shape[1] // rans._K)
    steps_p, rp = rans.class_shape(steps)
    cube = np.full((rp, steps_p * rans._K), rans._ID, dtype=np.int32)
    cube[: sym.shape[0], : sym.shape[1]] = sym
    cube = cube.reshape(rp, steps_p, rans._K).transpose(1, 0, 2)
    f_ext = np.full((rp, 257), rans._M, dtype=np.uint32)
    c_ext = np.zeros((rp, 257), dtype=np.uint32)
    f_ext[: len(lens), :256] = freqs
    c_ext[: len(lens), 1:256] = np.cumsum(freqs[:, :-1], axis=1)
    _x, need, vals = rans._enc_ref_jit(cube, f_ext, c_ext, unroll=rans._ENC_UNROLL)
    need_t = np.asarray(need).transpose(1, 0, 2).reshape(rp, -1)
    vals_t = np.asarray(vals).transpose(1, 0, 2).reshape(rp, -1)
    counts, packed = (np.asarray(a) for a in rans._compact_words(need, vals))
    np.testing.assert_array_equal(counts, need_t.sum(axis=1))
    for row in range(rp):
        np.testing.assert_array_equal(packed[row, : counts[row]], vals_t[row][need_t[row]])
        assert not packed[row, counts[row]:].any()
    _states, words = rans.encode_rows(sym, freqs, lengths=np.array(lens))
    for row, w in enumerate(words):
        np.testing.assert_array_equal(w, vals_t[row][need_t[row]])


@pytest.mark.parametrize("fill", [0.0, 0.3, 0.9, 1.0])
def test_compaction_packs_any_mask(fill):
    """The shift network packs every mask, empty and full rows included,
    at an off-bucket width."""
    rng = np.random.default_rng(7)
    need = rng.random((5, 7, rans._K)) < fill
    vals = rng.integers(0, 1 << 16, need.shape).astype(np.uint16)
    counts, packed = (np.asarray(a) for a in rans._compact_words(need, vals))
    need_t = need.transpose(1, 0, 2).reshape(7, -1)
    vals_t = vals.transpose(1, 0, 2).reshape(7, -1)
    for row in range(7):
        assert counts[row] == need_t[row].sum()
        np.testing.assert_array_equal(packed[row, : counts[row]], vals_t[row][need_t[row]])
        assert not packed[row, counts[row]:].any()
