"""Serving launcher: continuous batching over a reduced or production
model, batched range-query decode over a streamed SHRINK container, or
ragged multi-sensor gateway ingest through the admission scheduler.

    # LLM decode loop (continuous batching)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --requests 16 --slots 8 --max-new 8

    # time-series range queries against a freshly streamed SHRKS container
    PYTHONPATH=src python -m repro.launch.serve --mode range \
        --series 8 --points 65536 --frame-len 8192 --queries 256

    # ragged gateway ingest: heterogeneous-rate sensors -> RaggedBatcher
    # (size/deadline admission, bucketed ragged compress_batch) -> SHRKS
    PYTHONPATH=src python -m repro.launch.serve --mode ingest \
        --series 64 --ticks 200 --flush-samples 131072

    # compressed-domain analytics: aggregates / threshold counts / top-k
    # straight off the container, differentially checked against decode
    PYTHONPATH=src python -m repro.launch.serve --mode analytics \
        --series 8 --points 65536 --frame-len 8192 --queries 256

    # chaos campaign: seeded fault injection (byte flips, truncation, CRC
    # smash, frame drops, transient decode failures) against the
    # fault-tolerant gateway; every answer differentially checked, exits
    # non-zero on ANY silent corruption
    PYTHONPATH=src python -m repro.launch.serve --mode chaos \
        --series 4 --points 16384 --frame-len 2048 --fault-rate 0.01

    # sharded multi-tenant fleet: Poisson mixed workload (ingest + range +
    # analytics) over N shards with per-tenant admission quotas; p50/p99
    # latencies, critical-path aggregate MB/s, cross-shard differential
    # check vs the 1-shard oracle, and a shard-kill chaos tail — exits
    # non-zero on any silent corruption or cross-shard byte mismatch
    PYTHONPATH=src python -m repro.launch.serve --mode fleet --shards 4
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def _serve_model(args) -> int:
    import jax

    from ..configs import get_config, reduced_config
    from ..models import build_model
    from ..serving import ContinuousBatcher, Request

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    decode = jax.jit(model.decode_step)
    rng = np.random.default_rng(0)

    batcher = ContinuousBatcher(
        decode_fn=lambda t, c, i: decode(params, t, c, i),
        make_caches=lambda: model.make_decode_caches(args.slots, args.max_seq),
        n_slots=args.slots,
        eos_token=-1,
    )
    for rid in range(args.requests):
        batcher.submit(Request(
            rid=rid,
            prompt=rng.integers(1, cfg.vocab_size, size=int(rng.integers(4, 16))).astype(np.int32),
            max_new_tokens=args.max_new,
        ))
    t0 = time.perf_counter()
    done = batcher.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.prompt) + len(r.generated) for r in done)
    print(f"served {len(done)} requests, {toks} tokens, {dt:.1f}s ({toks/dt:.1f} tok/s)")
    return 0


def _serve_range(args) -> int:
    """Stream synthetic gateway sensors into a SHRKS container, then serve
    random range queries through the frame-cached batcher."""
    from ..core import BYTES_PER_ROW, ShrinkConfig, ShrinkStreamCodec
    from ..serving import RangeQuery, RangeQueryBatcher

    rng = np.random.default_rng(0)
    s, n = args.series, args.points
    v = np.cumsum(rng.standard_normal((s, n)) * 0.05, axis=1)
    v += rng.standard_normal((s, n)) * 0.02
    v = np.round(v, 4)
    vmin, vmax = float(v.min()), float(v.max())
    cfg = ShrinkConfig(eps_b=0.05 * max(vmax - vmin, 1e-12), lam=1e-4)
    eps = args.eps * (vmax - vmin)

    codec = ShrinkStreamCodec(
        cfg, eps_targets=[eps], backend="rans",
        value_range=(vmin, vmax), frame_len=args.frame_len,
    )
    t0 = time.perf_counter()
    for c0 in range(0, n, args.chunk):  # interleaved chunk-at-a-time ingest
        for sid in range(s):
            codec.ingest(v[sid, c0 : c0 + args.chunk], series_id=sid)
    blob = codec.finalize()
    dt_ingest = time.perf_counter() - t0
    mb = s * n * BYTES_PER_ROW / 1e6
    st = codec.stats()
    print(
        f"ingested {s} series x {n} samples in {dt_ingest:.2f}s "
        f"({mb/dt_ingest:.1f} MB/s), {st['frames']} frames, "
        f"CR={s*n*BYTES_PER_ROW/len(blob):.1f}, kb={st['kb']}"
    )

    batcher = RangeQueryBatcher(blob, cache_frames=args.cache_frames)
    qrng = np.random.default_rng(1)
    for qid in range(args.queries):
        sid = int(qrng.integers(0, s))
        t_lo = int(qrng.integers(0, n - 16))
        t_hi = int(min(n, t_lo + qrng.integers(16, args.frame_len)))
        batcher.submit(RangeQuery(qid=qid, series_id=sid, t0=t_lo, t1=t_hi, eps=eps))
    t0 = time.perf_counter()
    done = batcher.run()
    dt_q = time.perf_counter() - t0
    worst = 0.0
    for q in done:
        assert q.error is None, q.error
        worst = max(worst, float(np.abs(q.result - v[q.series_id, q.t0 : q.t1]).max()))
    bs = batcher.stats
    print(
        f"served {len(done)} range queries in {dt_q:.3f}s "
        f"({len(done)/dt_q:.0f} q/s), frames decoded={bs['frames_decoded']} "
        f"cache hits={bs['frame_hits']}, max |err|={worst:.2e} (eps={eps:.2e})"
    )
    return 0 if worst <= eps * (1 + 1e-9) else 1


def _serve_analytics(args) -> int:
    """Compressed-domain analytics over a freshly streamed container: a
    mixed workload of aggregates (random ranges and resolutions),
    threshold counts at the exact tier, and top-k segment queries —
    every answer differentially verified against the decode-then-numpy
    oracle before it counts."""
    from ..analytics import AnalyticsEngine
    from ..core import BYTES_PER_ROW, ShrinkConfig, ShrinkStreamCodec
    from ..serving import RangeQueryBatcher

    rng = np.random.default_rng(0)
    s, n = args.series, args.points
    v = np.cumsum(rng.standard_normal((s, n)) * 0.05, axis=1)
    v += rng.standard_normal((s, n)) * 0.02
    v = np.round(v, 4)
    vmin, vmax = float(v.min()), float(v.max())
    vrng = max(vmax - vmin, 1e-12)
    cfg = ShrinkConfig(eps_b=0.02 * vrng, lam=1e-4)
    tiers = [1e-2 * vrng, 1e-3 * vrng, 0.0]

    codec = ShrinkStreamCodec(
        cfg, eps_targets=tiers, decimals=4, backend="rans",
        value_range=(vmin, vmax), frame_len=args.frame_len,
    )
    for sid in range(s):
        codec.ingest(v[sid], series_id=sid)
    blob = codec.finalize()
    print(
        f"streamed {s} series x {n} samples into {codec.stats()['frames']} frames, "
        f"CR={s * n * BYTES_PER_ROW / len(blob):.1f}"
    )

    eng = AnalyticsEngine(RangeQueryBatcher(blob, cache_frames=args.cache_frames))
    qrng = np.random.default_rng(1)
    ops = ["min", "max", "sum", "mean", "stddev"]
    checked = 0
    t0 = time.perf_counter()
    for qid in range(args.queries):
        sid = int(qrng.integers(0, s))
        lo = int(qrng.integers(0, n - 16))
        hi = int(min(n, lo + qrng.integers(16, 4 * args.frame_len)))
        sl = v[sid, lo:hi]
        kind = qid % 3
        if kind == 0:  # zero-decode sketch aggregate off the segments
            op = ops[qid % len(ops)]
            ans = eng.aggregate(sid, op, lo, hi, eps=None)
        elif kind == 1:  # tiered aggregate (refine loop through the LRU)
            op = ops[qid % len(ops)]
            ans = eng.aggregate(sid, op, lo, hi, eps=tiers[qid % len(tiers)])
        else:  # exact threshold count: refine only straddling frames
            c = float(qrng.uniform(sl.min(), sl.max() + 1e-9))
            ans = eng.count_where(sid, "gt", c, lo, hi, eps=0.0)
        truth = {
            "min": sl.min, "max": sl.max, "sum": sl.sum, "mean": sl.mean,
            "stddev": sl.std,
        }[op]() if kind != 2 else float((sl > c).sum())
        assert ans.lo - 1e-9 <= truth <= ans.hi + 1e-9, (qid, ans, truth)
        if kind == 2:
            assert ans.exact
        checked += 1
    dt = time.perf_counter() - t0
    st = eng.stats
    top = eng.topk_segments(0, k=3, by="length")
    print(
        f"answered {checked} verified queries in {dt:.3f}s ({checked / dt:.0f} q/s): "
        f"{st['segment_frames']} segment-domain frames, "
        f"{st['frames_skipped']} skipped, {st['frames_refined']} refined, "
        f"{st['layers_paid']} layers paid "
        f"(serving LRU hits={eng.batcher.stats['frame_hits']})"
    )
    print(f"top-3 longest segments of series 0: {[(r['t0'], r['length']) for r in top]}")
    return 0


def _serve_ingest(args) -> int:
    """Ragged gateway simulation: sensors publish at rates spanning orders
    of magnitude; every tick delivers one chunk per sensor into the
    RaggedBatcher, whose size/deadline admission policy decides when the
    pending ragged batch compresses into SHRKS frames.  Ends with a
    correctness sweep (random range decodes against the raw data)."""
    from ..core import BYTES_PER_ROW, ShrinkConfig
    from ..core.streaming import decode_range
    from ..data.synthetic import ragged_sensor_traffic
    from ..serving import RaggedBatcher

    s = args.series
    traffic = ragged_sensor_traffic(s, args.ticks, seed=0)
    history: dict[int, list[np.ndarray]] = {i: [] for i in range(s)}

    cfg = ShrinkConfig(eps_b=0.4, lam=1e-4)
    eps = args.eps * 8.0  # value walks live in roughly [-4, 4]
    batcher = RaggedBatcher(
        cfg, eps_targets=[eps], backend="rans",
        flush_samples=args.flush_samples,
        flush_deadline_s=args.flush_deadline,
        max_buckets=args.buckets,
    )
    t0 = time.perf_counter()
    frames = 0
    for tick in traffic:
        for sid, chunk in tick:
            history[sid].append(chunk)
            frames += len(batcher.submit(sid, chunk))
        frames += len(batcher.poll())
    blob = batcher.finalize()
    dt = time.perf_counter() - t0
    st = batcher.stats()
    mb = st["samples_ingested"] * BYTES_PER_ROW / 1e6
    print(
        f"ingested {st['samples_ingested']:,} samples from {st['series']} sensors "
        f"in {dt:.2f}s ({mb/dt:.1f} MB/s), {st['frames']} frames / "
        f"{st['flushes']} flushes, CR={st['samples_ingested']*BYTES_PER_ROW/len(blob):.1f}, "
        f"kb={st['kb']}"
    )

    worst = 0.0
    qrng = np.random.default_rng(1)
    checked = 0
    for sid in range(s):
        full = np.concatenate(history[sid]) if history[sid] else np.zeros(0)
        if full.size < 2:
            continue
        for _ in range(args.verify_queries):
            lo = int(qrng.integers(0, full.size - 1))
            hi = int(min(full.size, lo + 1 + qrng.integers(0, 4096)))
            got = decode_range(blob, sid, lo, hi, eps)
            worst = max(worst, float(np.abs(got - full[lo:hi]).max()))
            checked += 1
    print(f"verified {checked} range decodes, max |err|={worst:.2e} (eps={eps:.2e})")
    return 0 if worst <= eps * (1 + 1e-9) else 1


def _serve_chaos(args) -> int:
    """Seeded chaos campaign against the fault-tolerant gateway.

    Phase 1 (corruption): each round injects ONE random fault (byte flip,
    truncation, frame-CRC smash, or frame drop) into a fresh copy of a
    pristine SHRKS container and fires range queries at a gateway over the
    mutant.  Every completed answer is differentially checked against the
    raw data: it must either carry a typed error, or be within its own
    reported ``achieved`` bound.  An answer outside its bound with no
    error flag is a SILENT CORRUPTION and fails the run.

    Phase 2 (transient faults + overload): the pristine container is
    served through a flaky decode path (seeded ``TransientError`` at
    ``--fault-rate``) with a deliberately tiny admission queue, exercising
    retry-with-backoff, the per-frame circuit breaker, deadline
    enforcement, and shed-to-coarse backpressure — again with every
    answer differentially checked.

    Phase 3 (KB store): faults against the cross-archive store path —
    byte flips and truncations of SHKS snapshot blobs must raise typed
    errors, a stale ``kb_snapshot_ref`` must fall back to the inline
    footer KB (both-mode container) or raise ``StaleSnapshotError``
    (ref-only), and decode of the faulted containers must stay exact.
    """
    from ..core import BYTES_PER_ROW, ShrinkConfig, ShrinkStreamCodec
    from ..core.errors import ShrinkError
    from ..serving import FaultTolerantGateway, RangeQuery, RetryPolicy
    from ..testing import ChaosInjector

    rng = np.random.default_rng(0)
    s, n = args.series, args.points
    v = np.cumsum(rng.standard_normal((s, n)) * 0.05, axis=1)
    v += rng.standard_normal((s, n)) * 0.02
    v = np.round(v, 4)
    vmin, vmax = float(v.min()), float(v.max())
    cfg = ShrinkConfig(eps_b=0.05 * max(vmax - vmin, 1e-12), lam=1e-4)
    eps = args.eps * (vmax - vmin)
    codec = ShrinkStreamCodec(
        cfg, eps_targets=[eps], backend="rans",
        value_range=(vmin, vmax), frame_len=args.frame_len,
    )
    for sid in range(s):
        codec.ingest(v[sid], series_id=sid)
    blob = codec.finalize()
    print(
        f"pristine container: {s} series x {n} samples, "
        f"{codec.stats()['frames']} frames, {len(blob)} bytes, "
        f"CR={s*n*BYTES_PER_ROW/len(blob):.1f}"
    )

    def check(q) -> str:
        """Classify one completed query: 'error' (typed, fine), 'ok'
        (within requested eps), 'degraded' (flagged, within its own
        achieved bound), or 'SILENT' (out of bound, unflagged)."""
        if q.error is not None:
            return "error"
        err = float(np.abs(q.result - v[q.series_id, q.t0 : q.t1]).max())
        bound = max(q.achieved, q.eps)
        if err > bound * (1 + 1e-9):
            return "SILENT"
        return "degraded" if q.degraded else "ok"

    chaos = ChaosInjector(seed=args.chaos_seed)
    qrng = np.random.default_rng(2)
    tally = {"ok": 0, "degraded": 0, "error": 0, "SILENT": 0}
    by_kind: dict[str, int] = {}
    unreadable = 0
    t0 = time.perf_counter()
    for _ in range(args.corruptions):
        mutant, fault = chaos.corrupt(blob)
        by_kind[fault.kind] = by_kind.get(fault.kind, 0) + 1
        try:
            gw = FaultTolerantGateway(mutant, seed=args.chaos_seed)
        except ShrinkError:
            unreadable += 1  # detected at parse: typed, never silent
            continue
        for qid in range(args.queries_per_fault):
            sid = int(qrng.integers(0, s))
            lo = int(qrng.integers(0, n - 16))
            hi = int(min(n, lo + qrng.integers(16, 2 * args.frame_len)))
            gw.submit(RangeQuery(qid=qid, series_id=sid, t0=lo, t1=hi, eps=eps))
        for q in gw.run(deadline_s=10.0):
            tally[check(q)] += 1
    dt = time.perf_counter() - t0
    kinds = ", ".join(f"{k}={c}" for k, c in sorted(by_kind.items()))
    print(
        f"phase 1: {args.corruptions} corrupt containers ({kinds}) in {dt:.2f}s — "
        f"{unreadable} rejected at parse; per-query: {tally['ok']} ok, "
        f"{tally['degraded']} degraded, {tally['error']} typed errors, "
        f"{tally['SILENT']} SILENT"
    )

    gw = FaultTolerantGateway(
        blob,
        retry=RetryPolicy(max_attempts=4, base_delay_s=1e-4, max_delay_s=1e-3),
        max_queue=args.queries // 4 or 1,
        seed=args.chaos_seed,
    )
    gw.frame_decode = chaos.flaky(gw.frame_decode, fail_rate=args.fault_rate)
    tally2 = {"ok": 0, "degraded": 0, "error": 0, "SILENT": 0}
    t0 = time.perf_counter()
    for qid in range(args.queries):
        sid = int(qrng.integers(0, s))
        lo = int(qrng.integers(0, n - 16))
        hi = int(min(n, lo + qrng.integers(16, 2 * args.frame_len)))
        gw.submit(RangeQuery(qid=qid, series_id=sid, t0=lo, t1=hi, eps=eps))
        # drain in bursts only once the bounded queue has overflowed, so
        # the tail of each burst is shed to the coarse tier
        if len(gw.queue) >= gw.max_queue + 4:
            for q in gw.run(deadline_s=5.0):
                tally2[check(q)] += 1
    for q in gw.run(deadline_s=5.0):
        tally2[check(q)] += 1
    dt = time.perf_counter() - t0
    st = gw.stats
    print(
        f"phase 2: {st['queries']} queries through flaky decode "
        f"(fault rate {args.fault_rate:g}) in {dt:.2f}s — "
        f"{st['retries']} retries, {st['transient_failures']} transient faults, "
        f"{st['breaker_opens']} breaker opens, {st['shed']} shed to coarse, "
        f"{st['deadline_exceeded']} deadline misses; per-query: "
        f"{tally2['ok']} ok, {tally2['degraded']} degraded, "
        f"{tally2['error']} typed errors, {tally2['SILENT']} SILENT"
    )
    # phase 3: the KB-store path — snapshot corruption and stale refs
    from ..core.errors import StaleSnapshotError
    from ..core.streaming import decode_series
    from ..serving import KBStore
    from ..serving.kbstore import resolve_container_kb, snapshot_from_bytes
    from ..testing import flip_byte, stale_snapshot_ref, truncate

    store = KBStore(cfg)

    def _store_codec(source, inline):
        sc = ShrinkStreamCodec(
            cfg, eps_targets=[eps], backend="rans",
            value_range=(vmin, vmax), frame_len=args.frame_len,
            kb_store=store, inline_kb=inline, source=source,
        )
        sc.ingest(v[0])
        return sc.finalize()

    ref_only = _store_codec("ref-only", None)
    both = _store_codec("both", True)
    snap = store.snapshots[-1].blob
    frng = np.random.default_rng(args.chaos_seed + 3)
    tally3 = {"typed": 0, "fallback": 0, "SILENT": 0}
    n_snap_faults = max(16, args.corruptions)
    for _ in range(n_snap_faults):
        if frng.random() < 0.5:
            bad, _ = flip_byte(snap, int(frng.integers(0, len(snap))),
                               bit=int(frng.integers(0, 8)))
        else:
            bad, _ = truncate(snap, int(frng.integers(0, len(snap))))
        try:
            snapshot_from_bytes(bad)
            tally3["SILENT"] += 1  # corrupt snapshot decoded without complaint
        except ShrinkError:
            tally3["typed"] += 1
    pristine = decode_series(ref_only, 0, eps)
    stale_ref_only, _ = stale_snapshot_ref(ref_only)
    try:
        resolve_container_kb(stale_ref_only, store)
        tally3["SILENT"] += 1  # a stale ref bound to the wrong snapshot
    except StaleSnapshotError:
        tally3["typed"] += 1
    stale_both, _ = stale_snapshot_ref(both)
    _, origin = resolve_container_kb(stale_both, store)
    if origin == "inline-fallback":
        tally3["fallback"] += 1
    else:
        tally3["SILENT"] += 1
    for mutant in (stale_ref_only, stale_both):
        if not np.array_equal(decode_series(mutant, 0, eps), pristine):
            tally3["SILENT"] += 1  # a footer fault must never move frame bytes
    print(
        f"phase 3: {n_snap_faults} snapshot faults + 2 stale refs — "
        f"{tally3['typed']} typed, {tally3['fallback']} inline fallbacks, "
        f"{tally3['SILENT']} SILENT"
    )

    silent = tally["SILENT"] + tally2["SILENT"] + tally3["SILENT"]
    print(f"silent corruptions: {silent}" + ("" if silent == 0 else "  <-- FAIL"))
    return 0 if silent == 0 else 1


def _serve_kbstore(args) -> int:
    """Cross-archive KB store demo: many small archives tiling a shared
    motif bank are encoded twice — self-contained (inline footer KB) and
    in ref mode against one shared :class:`KBStore` — then every archive
    is decoded both ways and compared exactly.  The store is then
    exercised through its whole lifecycle: detach a third of the corpus,
    ``compact()`` (re-basing the survivors, decode re-verified), spill the
    snapshots to disk, and reload; refs from the re-based containers must
    resolve against the loaded store to the writers' exact KB views.
    Exits nonzero on any decode or KB-view mismatch."""
    import tempfile

    from ..core import ShrinkConfig, ShrinkStreamCodec
    from ..core.errors import StaleSnapshotError
    from ..core.semantics import global_range
    from ..core.serialize import parse_framed_container, read_snapshot_ref
    from ..core.streaming import decode_series
    from ..serving import KBStore

    n_arch = 8 if args.quick else 32
    rng = np.random.default_rng(args.chaos_seed)
    motif_len, tiles = 128, 2
    bank = []
    for _ in range(8):  # piecewise-linear motifs: recurring KB lines
        knots = np.sort(rng.choice(np.arange(4, motif_len - 4), 15, replace=False))
        xs = np.concatenate([[0], knots, [motif_len - 1]])
        ys = np.round(rng.uniform(-4.0, 4.0, size=xs.size), 1)
        bank.append(np.round(np.interp(np.arange(motif_len), xs, ys), 3))
    series = [
        np.concatenate([bank[rng.integers(0, len(bank))] for _ in range(tiles)])
        for _ in range(n_arch)
    ]
    vr = global_range(np.concatenate(series))
    cfg = ShrinkConfig(eps_b=0.05 * (vr[1] - vr[0]), lam=1e-3)
    eps = 0.02 * (vr[1] - vr[0])

    def encode(v, store=None, source=None):
        sc = ShrinkStreamCodec(
            cfg, eps_targets=[eps], decimals=3, backend="best",
            value_range=vr, frame_len=tiles * motif_len,
            kb_store=store, source=source,
        )
        sc.ingest(v)
        return sc, sc.finalize()

    inline = [encode(v)[1] for v in series]
    store = KBStore(cfg)
    writers = [encode(v, store, f"ar{i}")[0] for i, v in enumerate(series)]
    inline_bytes = sum(len(b) for b in inline)
    shared_bytes = (
        sum(len(store.container(f"ar{i}")) for i in range(n_arch))
        + len(store.snapshots[-1].blob)
    )
    st = store.stats()
    print(
        f"corpus: {n_arch} archives x {tiles * motif_len} samples; "
        f"inline={inline_bytes:,}B (KB share "
        f"{sum(len(parse_framed_container(b)[1]) for b in inline) / inline_bytes:.1%}), "
        f"shared={shared_bytes:,}B -> CR={shared_bytes / inline_bytes:.3f}"
    )
    print(
        f"store: {st['live']} live entries, dedup {st['dedup_ratio']:.1f}x, "
        f"{st['snapshots']} snapshots"
    )

    bad = 0
    for i in range(n_arch):
        if not np.array_equal(
            decode_series(inline[i], 0, eps),
            decode_series(store.container(f"ar{i}"), 0, eps),
        ):
            bad += 1
    print(f"differential decode (ref vs inline): {n_arch - bad}/{n_arch} exact")

    dropped = list(range(0, n_arch, 3))
    old_refs = {i: read_snapshot_ref(store.container(f"ar{i}")) for i in dropped}
    for i in dropped:
        store.detach(f"ar{i}")
    rep = store.compact()
    survivors = [i for i in range(n_arch) if i not in dropped]
    for i in survivors:
        if not np.array_equal(
            decode_series(store.container(f"ar{i}"), 0, eps),
            decode_series(inline[i], 0, eps),
        ):
            bad += 1
    stale_ok = 0
    for ref in old_refs.values():
        try:
            store.resolve(ref)
        except StaleSnapshotError:
            stale_ok += 1
    print(
        f"compact: dropped {rep['dropped']} entries "
        f"({rep['entries_before']} -> {rep['entries_after']}), rebased "
        f"{len(rep['rebased'])} containers, decode exact; "
        f"{stale_ok}/{len(old_refs)} retired refs typed stale"
    )
    bad += len(old_refs) - stale_ok

    with tempfile.TemporaryDirectory() as d:
        paths = store.spill(d)
        loaded = KBStore.load(d)
        kb_bad = 0
        for i in survivors:
            ref = read_snapshot_ref(store.container(f"ar{i}"))
            kb = loaded.container_kb(ref)
            if kb.canonical() != writers[i].kb.canonical():
                kb_bad += 1
        print(
            f"spill/load: {len(paths)} snapshot file(s), sem_id match: "
            f"{loaded.sem_id() == store.sem_id()}, "
            f"{len(survivors) - kb_bad}/{len(survivors)} KB views exact"
        )
        bad += kb_bad
        if loaded.sem_id() != store.sem_id():
            bad += 1

    print(f"mismatches: {bad}" + ("" if bad == 0 else "  <-- FAIL"))
    return 0 if bad == 0 else 1


class _SimClock:
    """Deterministic monotonic clock for quota/deadline decisions: the sim
    advances it a fixed step per tick, so admission outcomes replay
    byte-identically from the seed (wall latencies are measured separately
    with ``perf_counter``)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _gen_traffic(series: int, ticks: int, seed: int):
    """Poisson sensor mix: per-series arrival probability and mean chunk
    size span an order of magnitude; each admitted chunk continues that
    series' random walk.  Returns (per-tick [(sid, chunk)], full history)."""
    rng = np.random.default_rng(seed)
    rates = 10.0 ** rng.uniform(-0.8, 0.0, size=series)
    means = rng.integers(24, 160, size=series)
    last = np.zeros(series)
    traffic, history = [], {i: [] for i in range(series)}
    for _ in range(ticks):
        tick = []
        for sid in range(series):
            if rng.random() < rates[sid]:
                m = 1 + int(rng.poisson(means[sid]))
                chunk = np.round(last[sid] + np.cumsum(rng.standard_normal(m) * 0.05), 4)
                last[sid] = chunk[-1]
                tick.append((sid, chunk))
                history[sid].append(chunk)
        traffic.append(tick)
    return traffic, history


def _pcts(ms: list[float]) -> dict:
    if not ms:
        return {"p50_ms": 0.0, "p99_ms": 0.0}
    a = np.asarray(ms)
    return {"p50_ms": float(np.percentile(a, 50)), "p99_ms": float(np.percentile(a, 99))}


def _ingest_fleet(traffic, n_shards: int, flush_samples: int, tick_dt: float = 0.01):
    """Drive one fleet through the traffic, attributing each submit's wall
    time to the owning shard (the critical-path throughput model: on one
    host the shards run sequentially; a real fleet runs them on the mesh's
    "data" axis, so aggregate rate = total bytes / max per-shard busy)."""
    from ..core import ShrinkConfig
    from ..core.errors import QuotaExceededError
    from ..serving import ShrinkFleet, TenantQuota

    clk = _SimClock()
    # four tenants round-robin over series; t3 runs on a tight bucket so
    # quota rejection/shed paths are exercised deterministically
    quotas = {
        f"t{k}": TenantQuota(rate_per_s=4e6, burst=4e6, clock=clk) for k in range(3)
    }
    quotas["t3"] = TenantQuota(rate_per_s=2_000.0, burst=3_000.0, clock=clk)
    fleet = ShrinkFleet(
        ShrinkConfig(eps_b=0.4, lam=1e-4),
        eps_targets=[8e-3],
        n_shards=n_shards,
        flush_samples=flush_samples,
        tenant_of=lambda sid: f"t{sid % 4}",
        quotas=quotas,
        clock=clk,
    )
    busy = [0.0] * n_shards
    lat_ms, admitted, rejected = [], {}, 0
    for tick in traffic:
        for sid, chunk in tick:
            shard = fleet.shard_of(sid)
            t0 = time.perf_counter()
            try:
                fleet.submit(sid, chunk)
            except QuotaExceededError:
                rejected += 1
                continue
            finally:
                dt = time.perf_counter() - t0
                busy[shard] += dt
            lat_ms.append(dt * 1e3)
            admitted.setdefault(sid, []).append(chunk)
        clk.t += tick_dt
        fleet.poll()
    # seal: each shard pays for compressing its own residual pending pool
    # (finalize is idempotent, so fleet.seal() below reuses these containers)
    for i, b in enumerate(fleet.batchers):
        t0 = time.perf_counter()
        with fleet.device_scope(i):
            b.finalize()
        busy[i] += time.perf_counter() - t0
    fleet.seal()
    return fleet, busy, lat_ms, admitted, rejected


def run_fleet_sim(
    n_shards: int = 4,
    series: int = 32,
    ticks: int = 120,
    queries: int = 192,
    flush_samples: int = 2048,
    seed: int = 0,
    check: bool = True,
    kill: bool = True,
) -> dict:
    """The fleet simulation behind ``--mode fleet`` and the ``fleet``
    BENCH section: Poisson mixed workload through a sharded multi-tenant
    fleet, p50/p99 ingest+query latency, critical-path aggregate MB/s,
    cross-shard differential vs the 1-shard oracle (``check``), and a
    shard-kill chaos tail (``kill``).  Everything is seeded; the returned
    dict's ``silent``/``byte_mismatch`` MUST be zero."""
    from ..core import BYTES_PER_ROW
    from ..serving import RangeQuery
    from ..testing import ChaosInjector

    eps = 8e-3
    traffic, _ = _gen_traffic(series, ticks, seed)
    fleet, busy, ingest_ms, admitted, rejected = _ingest_fleet(
        traffic, n_shards, flush_samples
    )
    full = {sid: np.concatenate(cs) for sid, cs in admitted.items()}
    samples = sum(v.size for v in full.values())
    mb = samples * BYTES_PER_ROW / 1e6
    critical = max(busy) if busy else 1e-12

    def check_range(q, tally) -> None:
        if q.error is not None:
            tally["error"] += 1
            return
        err = float(np.abs(q.result - full[q.series_id][q.t0 : q.t1]).max())
        if err > max(q.achieved, q.eps) * (1 + 1e-9):
            tally["SILENT"] += 1
        else:
            tally["degraded" if q.degraded else "ok"] += 1

    # mixed query workload: 70% range / 20% aggregate / 10% threshold count
    qrng = np.random.default_rng(seed + 1)
    sids = sorted(s for s, v in full.items() if v.size >= 16)
    tally = {"ok": 0, "degraded": 0, "error": 0, "SILENT": 0}
    query_ms = []
    for qid in range(queries):
        sid = int(qrng.choice(sids))
        n = full[sid].size
        lo = int(qrng.integers(0, n - 8))
        hi = int(min(n, lo + 8 + qrng.integers(0, 4096)))
        kind = qid % 10
        t0 = time.perf_counter()
        if kind < 7:
            q = fleet.query(RangeQuery(qid=qid, series_id=sid, t0=lo, t1=hi, eps=eps))
            query_ms.append((time.perf_counter() - t0) * 1e3)
            check_range(q, tally)
            continue
        sl = full[sid][lo:hi]
        if kind < 9:
            ans = fleet.aggregate(sid, ("sum", "min")[kind % 2], lo, hi, eps=eps)
            truth = float(sl.sum() if kind % 2 == 0 else sl.min())
        else:
            c = float(qrng.uniform(sl.min(), sl.max() + 1e-9))
            ans = fleet.count_where(sid, "gt", c, lo, hi, eps=None)
            truth = float((sl > c).sum())
        query_ms.append((time.perf_counter() - t0) * 1e3)
        if ans.lo - 1e-9 <= truth <= ans.hi + 1e-9:
            tally["degraded" if ans.degraded else "ok"] += 1
        else:
            tally["SILENT"] += 1

    # cross-shard differential: every series' frames byte-identical to the
    # 1-shard oracle built from the same traffic
    byte_mismatch = 0
    if check and n_shards > 1:
        oracle, _, _, _, _ = _ingest_fleet(traffic, 1, flush_samples)
        for sid in sorted(full):
            if fleet.series_frames(sid) != oracle.series_frames(sid):
                byte_mismatch += 1
        if fleet.global_kb.canonical() != oracle.global_kb.canonical():
            byte_mismatch += 1

    # shard-kill chaos tail: corrupt one shard, healthy shards must stay
    # exact and the dead shard typed/flagged — never silent
    kill_tally = {"ok": 0, "degraded": 0, "error": 0, "SILENT": 0}
    fault_detail = ""
    if kill and n_shards > 1:
        chaos = ChaosInjector(seed=seed + 7)
        fault = chaos.kill_shard(fleet, shard=0, mode="corrupt")
        fault_detail = fault.detail
        for qid in range(min(queries, 64)):
            sid = int(qrng.choice(sids))
            n = full[sid].size
            lo = int(qrng.integers(0, n - 8))
            hi = int(min(n, lo + 8 + qrng.integers(0, 2048)))
            q = fleet.query(
                RangeQuery(qid=10_000 + qid, series_id=sid, t0=lo, t1=hi, eps=eps)
            )
            check_range(q, kill_tally)

    st = fleet.fleet_stats()
    return {
        "n_shards": n_shards,
        "series": series,
        "samples": samples,
        "mb": mb,
        "ingest": {
            "chunks": len(ingest_ms),
            "rejected_quota": rejected,
            "busy_s": [round(b, 4) for b in busy],
            "critical_path_s": critical,
            "agg_mb_s": mb / critical,
            **_pcts(ingest_ms),
        },
        "query": {"count": queries, **_pcts(query_ms), **tally},
        "kill": {"fault": fault_detail, **kill_tally},
        "kb": {
            "syncs": st["kb_syncs"],
            "global_entries": fleet.global_kb.epoch,
            "semantic_id": fleet.global_kb.snapshot_id(),
        },
        "byte_mismatch": byte_mismatch,
        "silent": tally["SILENT"] + kill_tally["SILENT"],
    }


def _serve_fleet(args) -> int:
    """Sharded fleet simulation (see :func:`run_fleet_sim`); prints the
    latency/throughput summary and fails on any silent corruption or
    cross-shard byte divergence."""
    scale = 0.25 if args.quick else 1.0
    r = run_fleet_sim(
        n_shards=args.shards,
        series=max(8, int(args.series * 4 * scale)),
        ticks=max(30, int(args.ticks * scale)),
        queries=max(48, int(args.queries * scale)),
        flush_samples=args.flush_samples,
        seed=args.chaos_seed,
    )
    ing, q, k = r["ingest"], r["query"], r["kill"]
    print(
        f"fleet: {r['n_shards']} shards, {r['series']} series, "
        f"{r['samples']:,} samples ({r['mb']:.1f} MB), "
        f"{ing['chunks']} chunks admitted, {ing['rejected_quota']} quota-rejected"
    )
    print(
        f"ingest: p50={ing['p50_ms']:.2f}ms p99={ing['p99_ms']:.2f}ms, "
        f"critical path {ing['critical_path_s']:.2f}s -> {ing['agg_mb_s']:.1f} MB/s "
        f"aggregate (busy per shard: {ing['busy_s']})"
    )
    print(
        f"query: {q['count']} mixed (range/aggregate/count) "
        f"p50={q['p50_ms']:.2f}ms p99={q['p99_ms']:.2f}ms — "
        f"{q['ok']} ok, {q['degraded']} degraded, {q['error']} typed errors, "
        f"{q['SILENT']} SILENT"
    )
    if k["fault"]:
        print(
            f"shard-kill [{k['fault']}]: {k['ok']} ok, {k['degraded']} degraded, "
            f"{k['error']} typed errors, {k['SILENT']} SILENT"
        )
    print(
        f"kb: {r['kb']['syncs']} syncs, {r['kb']['global_entries']} global entries; "
        f"cross-shard diff vs 1-shard oracle: {r['byte_mismatch']} mismatches"
    )
    bad = r["silent"] + r["byte_mismatch"]
    print(f"silent corruptions + byte mismatches: {bad}" + ("" if bad == 0 else "  <-- FAIL"))
    return 0 if bad == 0 else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--mode",
        choices=["model", "range", "ingest", "analytics", "chaos", "fleet", "kbstore"],
        default="model",
    )
    # model mode
    ap.add_argument("--arch")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    # range mode
    ap.add_argument("--series", type=int, default=8)
    ap.add_argument("--points", type=int, default=65536)
    ap.add_argument("--frame-len", type=int, default=8192)
    ap.add_argument("--chunk", type=int, default=4096)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--eps", type=float, default=1e-3, help="fraction of value range")
    ap.add_argument("--cache-frames", type=int, default=32)
    # ingest mode
    ap.add_argument("--ticks", type=int, default=100, help="gateway polling rounds")
    ap.add_argument("--flush-samples", type=int, default=131_072)
    ap.add_argument("--flush-deadline", type=float, default=None)
    ap.add_argument("--buckets", type=int, default=4)
    ap.add_argument("--verify-queries", type=int, default=2)
    # chaos mode
    ap.add_argument("--fault-rate", type=float, default=0.01,
                    help="transient decode failure probability (phase 2)")
    ap.add_argument("--corruptions", type=int, default=48,
                    help="corrupt containers to generate (phase 1)")
    ap.add_argument("--queries-per-fault", type=int, default=8)
    ap.add_argument("--chaos-seed", type=int, default=0)
    # fleet mode
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--quick", action="store_true",
                    help="scaled-down fleet sim (CI smoke)")
    args = ap.parse_args(argv)
    from .compile_cache import use_compile_cache

    use_compile_cache()

    if args.mode == "kbstore":
        return _serve_kbstore(args)
    if args.mode == "fleet":
        return _serve_fleet(args)
    if args.mode == "chaos":
        return _serve_chaos(args)
    if args.mode == "ingest":
        return _serve_ingest(args)
    if args.mode == "analytics":
        return _serve_analytics(args)
    if args.mode == "range":
        return _serve_range(args)
    if not args.arch:
        ap.error("--arch is required in --mode model")
    return _serve_model(args)


if __name__ == "__main__":
    raise SystemExit(main())
