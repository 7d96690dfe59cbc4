"""Property-based tests (hypothesis) for the residual refinement pyramid.

The contract, for ANY fixed-decimal series, ANY tier ladder, ANY chunking,
and ANY ragged mix:

* per-tier guarantee: |v - decode_at(eps_k)| <= eps_k for every tier, and
  the lossless tier reconstructs the decimal grid bit-exactly;
* layer-prefix byte sizes are monotone non-decreasing coarse -> fine;
* one-shot, streaming, rectangular-batch, and ragged-batch compression
  produce byte-identical archives at every tier (the batched machines are
  an implementation detail, never a format variant).

Skipped without the ``hypothesis`` dev extra; CI runs it with a fixed seed
via the ``ci`` profile (tests/conftest.py).
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need the hypothesis dev extra")
from hypothesis import example, given, settings, strategies as st

from repro.core import (
    ShrinkCodec,
    ShrinkConfig,
    ShrinkStreamCodec,
    cs_to_bytes,
    decompress_at,
)
from repro.core.semantics import global_range

_DECIMALS = 4

_series_strategy = st.lists(
    st.floats(min_value=-1e4, max_value=1e4, allow_nan=False, allow_infinity=False,
              width=32),
    min_size=2,
    max_size=300,
).map(lambda xs: np.round(np.array(xs, dtype=np.float64), _DECIMALS))

# ladders of 1-4 relative tiers (fractions of the value range), optionally
# ending with the lossless tier
_ladder_strategy = st.tuples(
    st.lists(
        st.floats(min_value=1e-4, max_value=0.5), min_size=1, max_size=4, unique=True
    ),
    st.booleans(),
)


def _codec_for(v):
    rng = float(v.max() - v.min())
    if rng <= 0:
        return None, []
    return (
        ShrinkCodec(
            config=ShrinkConfig(eps_b=0.05 * rng, lam=1e-3), backend="rans"
        ),
        rng,
    )


def _tiers(rel, lossless, rng):
    tiers = sorted({r * rng for r in rel}, reverse=True)
    if lossless:
        tiers.append(0.0)
    return tiers


@given(_series_strategy, _ladder_strategy)
@settings(max_examples=200, deadline=None)
def test_per_tier_guarantee_and_monotone_prefix_bytes(v, ladder):
    codec, rng = _codec_for(v)
    if codec is None:
        return
    tiers = _tiers(*ladder, rng)
    cs = codec.compress(v, eps_targets=tiers, decimals=_DECIMALS)
    assert cs.tiers() == tiers
    ulp_slack = 4 * np.finfo(np.float64).eps * max(1.0, float(np.abs(v).max()))
    for eps in tiers:
        vhat = decompress_at(cs, eps)
        if eps == 0.0:
            assert np.array_equal(np.round(vhat, _DECIMALS), v)
        else:
            assert np.max(np.abs(vhat - v)) <= eps * (1 + 1e-9) + ulp_slack
    sizes = [cs.size_at(e) for e in tiers]
    assert sizes == sorted(sizes)


@st.composite
def _series_chunking_ladder(draw):
    v = draw(_series_strategy)
    n = len(v)
    k = draw(st.integers(min_value=0, max_value=min(n - 1, 8)))
    cuts = sorted(draw(
        st.lists(st.integers(min_value=1, max_value=n - 1), min_size=k, max_size=k,
                 unique=True)
    )) if n > 1 else []
    ladder = draw(_ladder_strategy)
    return v, [0] + cuts + [n], ladder


@given(_series_chunking_ladder())
@settings(max_examples=100, deadline=None)
# a min over 0.0 and -0.0 may return either: r_lo must not care
@example((np.array([0.0, 0.0, -0.0, 1.0, 1.0, 1.0, 2.0, 0.0]), [0, 8], ([0.03125], False)))
def test_one_shot_streaming_batch_ragged_byte_identical(args):
    v, bounds, ladder = args
    codec, rng = _codec_for(v)
    if codec is None:
        return
    tiers = _tiers(*ladder, rng)
    one_shot = cs_to_bytes(codec.compress(
        v, eps_targets=tiers, decimals=_DECIMALS,
        value_range=global_range(v), n_hint=len(v),
    ))

    # streaming, arbitrary chunking, single flush-frame
    sc = ShrinkStreamCodec(
        codec.config, eps_targets=tiers, decimals=_DECIMALS, backend="rans",
        value_range=global_range(v), n_hint=len(v),
    )
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        sc.ingest(v[lo:hi])
    sc.flush()
    assert sc._sealed[0][4] == one_shot

    # rectangular batch (pads v with itself)
    plain = cs_to_bytes(codec.compress(v, eps_targets=tiers, decimals=_DECIMALS))
    batch = codec.compress_batch(
        np.stack([v, v]), eps_targets=tiers, decimals=_DECIMALS
    )
    assert cs_to_bytes(batch[0]) == plain
    assert cs_to_bytes(batch[1]) == plain

    # ragged batch: the series plus shorter companions (prefix + empty)
    ragged = [v, v[: max(1, len(v) // 3)], np.zeros(0)]
    rbatch = codec.compress_batch(
        ragged, eps_targets=tiers, decimals=_DECIMALS, max_buckets=2
    )
    assert cs_to_bytes(rbatch[0]) == plain
    for arr, cs in zip(ragged[1:], rbatch[1:]):
        assert cs_to_bytes(cs) == cs_to_bytes(
            codec.compress(arr, eps_targets=tiers, decimals=_DECIMALS)
        )
