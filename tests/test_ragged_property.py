"""Property-based tests (hypothesis) for the ragged batched pipeline.

The acceptance property of the ragged-ingest PR: for ANY mix of series
lengths (empty and length-1 included), ANY bucket count, and eps targets
spanning base-only / quantized / lossless regimes, ``compress_batch`` over
the ragged list is **byte-identical** to a python loop of ``compress`` —
bucketed padded lanes, masked cone scans, and the shared ragged rANS pass
must be invisible in the output bytes.  Skipped without the ``hypothesis``
dev extra; CI runs it with a fixed seed via the ``ci`` profile
(tests/conftest.py).
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need the hypothesis dev extra")
from hypothesis import example, given, settings, strategies as st

from repro.core import ShrinkCodec, ShrinkConfig, cs_to_bytes

# Bounded finite values on a 4-decimal grid (the lossless eps=0.0 path
# guarantees exactness only for fixed-decimal data, as in Table II).
_value = st.floats(
    min_value=-1e4, max_value=1e4, allow_nan=False, allow_infinity=False, width=32
)


@st.composite
def _ragged_batch(draw):
    """A list of 1-16 series with independently drawn lengths 0..60 —
    random length mixes, empties and singletons included."""
    s = draw(st.integers(min_value=1, max_value=16))
    series = []
    for _ in range(s):
        n = draw(st.integers(min_value=0, max_value=60))
        vals = draw(
            st.lists(_value, min_size=n, max_size=n)
        )
        series.append(np.round(np.array(vals, dtype=np.float64), 4))
    return series


@given(
    _ragged_batch(),
    st.floats(min_value=1e-4, max_value=1.0),
    st.integers(min_value=1, max_value=6),
)
@settings(max_examples=200, deadline=None)
# a min over 0.0 and -0.0 may return either: r_lo and vmin must not care
@example([np.array([0.0, 0.0, 0.0, -0.0]), np.zeros(9)], 1.0, 1)
def test_ragged_compress_batch_bit_identical_to_loop(series, eps_rel, max_buckets):
    """The acceptance property: ragged compress_batch == compress loop,
    byte-for-byte, for any length mix and bucketing."""
    nonempty = [v for v in series if v.size]
    if nonempty:
        allv = np.concatenate(nonempty)
        rng = float(allv.max() - allv.min())
    else:
        rng = 0.0
    if rng <= 0:
        rng = 1.0  # constant/empty batches still must round-trip
    cfg = ShrinkConfig(eps_b=0.05 * rng, lam=1e-3)
    codec = ShrinkCodec(config=cfg, backend="rans")
    eps_targets = [eps_rel * rng, 0.0]
    batch = codec.compress_batch(
        series, eps_targets=eps_targets, decimals=4, max_buckets=max_buckets
    )
    assert len(batch) == len(series)
    for i, v in enumerate(series):
        single = codec.compress(v, eps_targets=eps_targets, decimals=4)
        assert cs_to_bytes(batch[i]) == cs_to_bytes(single), (i, v.size)
        # and the lossless stream reconstructs the 4-decimal grid exactly
        np.testing.assert_array_equal(np.round(codec.decompress_at(batch[i], 0.0), 4), v)


@given(_ragged_batch())
@settings(max_examples=40, deadline=None)
def test_ragged_batcher_container_decodes_everywhere(series):
    """RaggedBatcher end to end under hypothesis: whatever the length mix,
    the finalized SHRKS container reconstructs every submitted series."""
    from repro.core.streaming import decode_series
    from repro.serving.ragged import RaggedBatcher

    nonempty = [v for v in series if v.size]
    if not nonempty:
        return
    allv = np.concatenate(nonempty)
    rng = max(float(allv.max() - allv.min()), 1e-9)
    cfg = ShrinkConfig(eps_b=0.05 * rng, lam=1e-3)
    b = RaggedBatcher(cfg, eps_targets=[0.0], decimals=4, flush_samples=64)
    for sid, v in enumerate(series):
        b.submit(sid, v[: v.size // 2])
        b.submit(sid, v[v.size // 2 :])
    blob = b.finalize()
    for sid, v in enumerate(series):
        if v.size == 0:
            continue
        np.testing.assert_array_equal(np.round(decode_series(blob, sid, 0.0), 4), v)
