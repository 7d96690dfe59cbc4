"""Ragged pipeline contract: compress_batch over mixed-length series is
byte-identical to a python loop of compress — across input forms (list vs
padded+lengths), bucket counts (1, default, one-bucket-per-series), eps
regimes, and the edge cases the gateway actually sees (empty series,
length-1 series, orders-of-magnitude spread) — and the RaggedBatcher
admission scheduler seals frames that standard SHRKS consumers decode."""
import dataclasses

import numpy as np
import pytest

from repro.core import (
    ShrinkCodec,
    ShrinkConfig,
    cs_from_bytes,
    cs_to_bytes,
    extract_semantics,
    extract_semantics_batch,
    fluctuation_table,
)
from repro.core.phases import default_interval_length, divide
from repro.core.streaming import decode_range, decode_series, read_knowledge_base
from repro.serving.ragged import RaggedBatcher

_RNG = np.random.default_rng(99)


def _ragged_series(lengths) -> list[np.ndarray]:
    out = []
    for n in lengths:
        v = np.cumsum(_RNG.standard_normal(n) * 0.05) + _RNG.standard_normal(n) * 0.02
        out.append(np.round(v, 4))
    return out


def _codec_for(series, backend="rans") -> tuple[ShrinkCodec, float]:
    allv = np.concatenate([v for v in series if v.size]) if any(
        v.size for v in series
    ) else np.zeros(1)
    rng = max(float(allv.max() - allv.min()), 1e-9)
    return ShrinkCodec(config=ShrinkConfig(eps_b=0.05 * rng, lam=1e-3), backend=backend), rng


# --------------------------------------------------------- ragged cone scan
def test_ragged_scan_matches_single():
    lengths = [1000, 1, 2, 17, 513, 257, 64, 999, 3, 128]
    series = _ragged_series(lengths)
    codec, _ = _codec_for(series)
    t = max(lengths)
    padded = np.zeros((len(series), t))
    for i, v in enumerate(series):
        padded[i, : v.size] = v
    batch = extract_semantics_batch(
        padded, codec.config, chunk=64, lengths=np.array(lengths)
    )
    for i, v in enumerate(series):
        single = extract_semantics(v, codec.config)
        assert [dataclasses.astuple(x) for x in single] == [
            dataclasses.astuple(x) for x in batch[i]
        ], i


def test_ragged_fluctuation_table_matches_divide():
    lengths = [300, 7, 150, 2, 299]
    series = _ragged_series(lengths)
    cfg = ShrinkConfig(eps_b=0.3, lam=1e-3)
    t = max(lengths)
    padded = np.zeros((len(series), t))
    for i, v in enumerate(series):
        padded[i, : v.size] = v
    ns = np.array(lengths)
    dg = np.array([float(v.max() - v.min()) for v in series])
    levels, eps = fluctuation_table(padded, dg, cfg, lengths=ns)
    for i, v in enumerate(series):
        el = default_interval_length(v.size, cfg)
        for tt in range(0, v.size, 5):
            _, lv, eh = divide(v, tt, el, float(dg[i]), cfg)
            assert lv == levels[i, tt], (i, tt)
            assert eh == eps[i, tt], (i, tt)


# --------------------------------------------------------- full pipeline
@pytest.mark.parametrize("backend", ["rans", "best"])
def test_ragged_compress_batch_byte_identical(backend):
    lengths = [0, 1, 2, 17, 513, 257, 64, 1500, 3, 129, 5]
    series = _ragged_series(lengths)
    codec, rng = _codec_for(series, backend=backend)
    # spans base-only, quantized, and lossless regimes
    eps_ts = [0.5 * rng, 1e-2 * rng, 1e-3 * rng, 0.0]
    batch = codec.compress_batch(series, eps_targets=eps_ts, decimals=4)
    for i, v in enumerate(series):
        single = codec.compress(v, eps_targets=eps_ts, decimals=4)
        assert cs_to_bytes(batch[i]) == cs_to_bytes(single), (i, lengths[i])


def test_ragged_padded_lengths_input_equivalent():
    lengths = [40, 3, 120, 1, 77]
    series = _ragged_series(lengths)
    codec, rng = _codec_for(series)
    t = max(lengths)
    padded = np.zeros((len(series), t))
    for i, v in enumerate(series):
        padded[i, : v.size] = v
    a = codec.compress_batch(series, eps_targets=[1e-2 * rng, 0.0], decimals=4)
    b = codec.compress_batch(
        padded, eps_targets=[1e-2 * rng, 0.0], decimals=4, lengths=np.array(lengths)
    )
    assert [cs_to_bytes(x) for x in a] == [cs_to_bytes(x) for x in b]


def test_ragged_bucketing_invariance():
    """Output must not depend on the bucket count — including the
    pathological one-bucket-per-series spread and a single shared bucket."""
    lengths = [2048, 4, 512, 33, 1, 900, 65, 7]
    series = _ragged_series(lengths)
    codec, rng = _codec_for(series)
    eps_ts = [1e-2 * rng, 0.0]
    want = [
        cs_to_bytes(codec.compress(v, eps_targets=eps_ts, decimals=4)) for v in series
    ]
    for buckets in (1, 3, len(series), 2 * len(series)):
        got = codec.compress_batch(
            series, eps_targets=eps_ts, decimals=4, max_buckets=buckets
        )
        assert [cs_to_bytes(x) for x in got] == want, buckets


def test_ragged_equal_length_list_hits_rect_path():
    series = _ragged_series([256, 256, 256])
    codec, rng = _codec_for(series)
    a = codec.compress_batch(series, eps_targets=[1e-2 * rng])
    b = codec.compress_batch(np.stack(series), eps_targets=[1e-2 * rng])
    assert [cs_to_bytes(x) for x in a] == [cs_to_bytes(x) for x in b]


def test_ragged_roundtrip_guarantees():
    lengths = [700, 1, 90, 2, 350]
    series = _ragged_series(lengths)
    codec, rng = _codec_for(series)
    eps = 1e-3 * rng
    batch = codec.compress_batch(series, eps_targets=[eps, 0.0], decimals=4)
    for i, v in enumerate(series):
        cs = cs_from_bytes(cs_to_bytes(batch[i]))  # survive the container
        vhat = codec.decompress_at(cs, eps)
        bound = batch[i].eps_b_practical if batch[i].pyramid.layers[0].mode == "identity" else eps
        if v.size:
            assert np.max(np.abs(vhat - v)) <= bound * (1 + 1e-9) + 1e-12
        np.testing.assert_array_equal(np.round(codec.decompress_at(cs, 0.0), 4), v)


def test_ragged_compress_batch_pallas_route_runs():
    """The kernel route (interpret mode on CPU) on ragged lanes: float32 on
    device so bytes may differ from numpy, but every codec guarantee must
    hold at every length."""
    lengths = [513, 1, 64, 300, 2]
    series = _ragged_series(lengths)
    codec, rng = _codec_for(series)
    eps = 1e-2 * rng
    batch = codec.compress_batch(series, eps_targets=[eps], semantics="pallas")
    for i, v in enumerate(series):
        vhat = codec.decompress_at(batch[i], eps)
        bound = batch[i].eps_b_practical if batch[i].pyramid.layers[0].mode == "identity" else eps
        assert np.max(np.abs(vhat - v)) <= bound * (1 + 1e-6) + 1e-9, i


def test_ragged_pallas_route_keeps_tiers_exact():
    """The float32 device scan's origins land on the decoder's grid, so the
    residual tiers hold exactly: within eps at the lossy tier and bit-exact
    at 0.0, the gateway's two promises."""
    series = _ragged_series([513, 1, 64, 300, 2, 2_000])
    codec, rng = _codec_for(series)
    eps = 1e-3 * rng
    batch = codec.compress_batch(series, eps_targets=[eps, 0.0], decimals=4,
                                 semantics="pallas")
    for i, v in enumerate(series):
        cs = cs_from_bytes(cs_to_bytes(batch[i]))
        assert np.max(np.abs(codec.decompress_at(cs, eps) - v)) <= eps * (1 + 1e-9), i
        np.testing.assert_array_equal(codec.decompress_at(cs, 0.0), v)


def test_ragged_compress_batch_validates_input():
    codec = ShrinkCodec(config=ShrinkConfig(eps_b=1.0))
    with pytest.raises(ValueError):  # lengths alongside a ragged list
        codec.compress_batch([np.zeros(4)], eps_targets=[0.1], lengths=np.array([4]))
    with pytest.raises(ValueError):  # lengths shape mismatch
        codec.compress_batch(np.zeros((2, 8)), eps_targets=[0.1], lengths=np.array([8]))
    with pytest.raises(ValueError):  # length out of range
        codec.compress_batch(
            np.zeros((2, 8)), eps_targets=[0.1], lengths=np.array([4, 9])
        )
    with pytest.raises(ValueError):  # lossless needs decimals (ragged path)
        codec.compress_batch(
            [np.zeros(4), np.zeros(7)], eps_targets=[0.0]
        )
    with pytest.raises(ValueError):  # bucket count
        codec.compress_batch(
            [np.zeros(4), np.zeros(7)], eps_targets=[0.1], max_buckets=0
        )


def test_empty_batch_and_all_empty_series():
    codec = ShrinkCodec(config=ShrinkConfig(eps_b=1.0), backend="rans")
    assert codec.compress_batch([], eps_targets=[0.1]) == []
    batch = codec.compress_batch(
        [np.zeros(0), np.zeros(0)], eps_targets=[0.1, 0.0], decimals=4
    )
    for cs in batch:
        assert cs.base.n == 0
        assert cs_to_bytes(cs) == cs_to_bytes(
            codec.compress(np.zeros(0), eps_targets=[0.1, 0.0], decimals=4)
        )
        assert codec.decompress_at(cs, 0.0).size == 0


# --------------------------------------------------------- RaggedBatcher
class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _cfg_for_batcher(series) -> ShrinkConfig:
    allv = np.concatenate([v for v in series if v.size])
    return ShrinkConfig(eps_b=0.05 * float(allv.max() - allv.min()), lam=1e-3)


def test_batcher_size_trigger_and_decode():
    lengths = [400, 37, 1200, 5, 800, 64]
    series = _ragged_series(lengths)
    cfg = _cfg_for_batcher(series)
    b = RaggedBatcher(cfg, eps_targets=[0.0], decimals=4, flush_samples=1000)
    sealed = []
    for c0 in range(0, max(lengths), 100):  # interleaved chunk arrivals
        for sid, v in enumerate(series):
            sealed += b.submit(sid, v[c0 : c0 + 100])
    blob = b.finalize()
    assert b.stats()["flushes"] >= 2  # the size trigger actually fired
    for sid, v in enumerate(series):
        np.testing.assert_array_equal(np.round(decode_series(blob, sid, 0.0), 4), v)
        mid = max(1, v.size // 2)
        np.testing.assert_array_equal(
            np.round(decode_range(blob, sid, 0, mid, 0.0), 4), v[:mid]
        )
    # frames are contiguous per series
    spans: dict[int, int] = {}
    for sid, lo, hi in b.sealed_frames:
        assert lo == spans.get(sid, 0)
        spans[sid] = hi
    assert spans == {sid: v.size for sid, v in enumerate(series)}
    kb = read_knowledge_base(blob)
    assert kb is not None and kb.stats()["entries"] > 0


def test_batcher_deadline_trigger():
    clock = _FakeClock()
    cfg = ShrinkConfig(eps_b=0.5, lam=1e-3)
    b = RaggedBatcher(
        cfg, eps_targets=[1e-2], flush_samples=None, flush_deadline_s=5.0, clock=clock
    )
    v = np.round(np.cumsum(_RNG.standard_normal(50) * 0.1), 4)
    assert b.submit(0, v) == []
    clock.t = 4.9
    assert b.poll() == []  # deadline not reached
    clock.t = 5.1
    sealed = b.poll()
    assert sealed == [(0, 0, 50)]
    assert b.poll() == []  # nothing pending anymore
    # deadline restarts from the next submit, not the old epoch
    clock.t = 100.0
    assert b.submit(0, v[:10]) == []
    clock.t = 104.9
    assert b.poll() == []
    clock.t = 105.0
    assert b.poll() == [(0, 50, 60)]


def test_batcher_frames_match_stream_codec_deferred_seal():
    """A RaggedBatcher frame must be byte-identical to what the deferred-scan
    ShrinkStreamCodec seals for the same buffer (both reduce to one-shot
    compress of the window) — the two ingest paths share one wire format."""
    from repro.core import ShrinkStreamCodec
    from repro.core.serialize import frame_payload, parse_framed_container

    v = np.round(np.cumsum(_RNG.standard_normal(333) * 0.05), 4)
    cfg = ShrinkConfig(eps_b=0.05 * float(v.max() - v.min()), lam=1e-3)

    b = RaggedBatcher(cfg, eps_targets=[0.0], decimals=4, flush_samples=None)
    b.submit(7, v)
    blob_b = b.finalize()
    sc = ShrinkStreamCodec(cfg, eps_targets=[0.0], decimals=4, backend="rans")
    sc.ingest(v, series_id=7)
    blob_s = sc.finalize()
    pb, _ = parse_framed_container(blob_b)
    ps, _ = parse_framed_container(blob_s)
    assert frame_payload(blob_b, pb[0]) == frame_payload(blob_s, ps[0])


def test_batcher_shares_knowledge_base():
    series = _ragged_series([300, 200])
    cfg = _cfg_for_batcher(series)
    from repro.core.streaming import KnowledgeBase

    kb = KnowledgeBase(cfg)
    b1 = RaggedBatcher(cfg, eps_targets=[1e-2], kb=kb, flush_samples=None)
    b2 = RaggedBatcher(cfg, eps_targets=[1e-2], kb=kb, flush_samples=None)
    b1.submit(0, series[0])
    b1.flush()
    entries_after_first = kb.stats()["entries"]
    b2.submit(0, series[0])  # identical data -> identical lines -> dedup
    b2.flush()
    assert kb.stats()["entries"] == entries_after_first
    assert kb.stats()["total_refs"] >= 2 * entries_after_first


def test_batcher_rejects_use_after_finalize():
    cfg = ShrinkConfig(eps_b=0.5)
    b = RaggedBatcher(cfg, eps_targets=[1e-2])
    b.submit(0, np.ones(4))
    b.finalize()
    with pytest.raises(ValueError):
        b.submit(0, np.ones(4))
    with pytest.raises(ValueError):
        RaggedBatcher(cfg, eps_targets=[0.0])  # lossless without decimals


def test_flush_after_finalize_is_noop():
    """Regression: a ``flush_deadline_s`` timer that fires after
    ``finalize`` (the race window of any real deployment, where the timer
    loop and the shutdown path interleave) must be a no-op — it used to
    reach the sealed writer and double-seal the pending pool."""
    clock = _FakeClock()
    cfg = ShrinkConfig(eps_b=0.5, lam=1e-3)
    b = RaggedBatcher(
        cfg, eps_targets=[1e-2], flush_samples=None, flush_deadline_s=5.0, clock=clock
    )
    v = np.round(np.cumsum(_RNG.standard_normal(64) * 0.1), 4)
    b.submit(0, v)
    blob = b.finalize()
    frames = list(b.sealed_frames)
    clock.t = 100.0  # the deadline is long past due when the timer fires
    assert b.due() is False
    assert b.due_series() == []
    assert b.poll() == []
    assert b.flush() == []
    assert b.sealed_frames == frames  # nothing double-sealed
    assert b.finalize() == blob  # container unchanged


def test_reentrant_flush_during_compression_cannot_double_seal():
    """Regression for the deadline/finalize double-seal: a flush trigger
    firing *while a flush is compressing* (timer thread, or anything the
    compression path calls back into) must find an empty pending pool —
    the buffers are detached before compression starts."""
    clock = _FakeClock()
    cfg = ShrinkConfig(eps_b=0.5, lam=1e-3)
    b = RaggedBatcher(
        cfg, eps_targets=[1e-2], flush_samples=None, flush_deadline_s=5.0, clock=clock
    )
    v = np.round(np.cumsum(_RNG.standard_normal(80) * 0.1), 4)
    b.submit(0, v)
    clock.t = 10.0  # deadline fired; the poll below starts the flush

    inner: dict = {"polls": [], "finalized_inside": False}
    real = b.codec.compress_batch

    def reentrant(arrs, **kw):
        # a concurrent timer tick AND a concurrent shutdown, mid-flush
        inner["polls"].append(b.poll())
        inner["flush"] = b.flush()
        return real(arrs, **kw)

    b.codec.compress_batch = reentrant
    sealed = b.poll()
    b.codec.compress_batch = real

    assert sealed == [(0, 0, 80)]
    assert inner["polls"] == [[]] and inner["flush"] == []  # reentrants no-op
    assert b.sealed_frames == [(0, 0, 80)]  # exactly once
    blob = b.finalize()
    got = decode_range(blob, 0, 0, 80, 1e-2)
    assert float(np.abs(got - v).max()) <= 1e-2 * (1 + 1e-9)


def test_scope_series_flush_isolation():
    """Under ``scope="series"`` a series' flush trigger is a pure function
    of its OWN ingest history — co-batched series neither trigger it nor
    get dragged into its frames early (the property that makes fleet
    sharding byte-invariant; see tests/test_fleet.py)."""
    series = _ragged_series([100, 100])
    cfg = _cfg_for_batcher(series)
    # batch scope: the aggregate pool (32+32 >= 64) seals BOTH series,
    # even though neither alone reached the threshold
    b = RaggedBatcher(cfg, eps_targets=[1e-2], flush_samples=64, scope="batch")
    assert b.submit(0, series[0][:32]) == []
    assert {s for s, _, _ in b.submit(1, series[1][:32])} == {0, 1}
    # series scope: each series seals alone, exactly when ITS 64 arrive
    s = RaggedBatcher(cfg, eps_targets=[1e-2], flush_samples=64, scope="series")
    assert s.submit(0, series[0][:32]) == []
    assert s.submit(1, series[1][:64]) == [(1, 0, 64)]  # 0 untouched
    assert s.submit(0, series[0][32:64]) == [(0, 0, 64)]
    with pytest.raises(ValueError):
        RaggedBatcher(cfg, eps_targets=[1e-2], scope="frame")  # unknown scope
