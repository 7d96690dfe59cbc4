#!/usr/bin/env python3
"""Chip smoke check: the gateway path end to end on one TPU chip.

    python chip_smoke.py            # one chip: ingest -> seal -> query
    python chip_smoke.py --fleet    # four chips: 4-shard fleet vs 1-shard replay
    python chip_smoke.py --small    # rehearsal size, on any backend

Everything runs in this one process.  Phases:

* ingest  — 1,024 sensors publishing log-uniform 2..512 samples per tick
  for 200 ticks (about 19M samples) through ``RaggedBatcher`` (tiers 8e-3
  and 0.0, rANS, 4M-sample flushes), sealed by ``finalize``; then every
  series is decoded in full at both tiers and compared with the raw
  samples (within 8e-3, and bit-exact at 0.0).
* query   — 512 seeded range, aggregate and ``count_where`` queries over
  the sealed container through ``RangeQueryBatcher`` and
  ``AnalyticsEngine``, each checked against numpy on the raw samples.
* entropy — the residual streams of the first flush, re-encoded by the
  device rANS route and by the numpy coder: the wire bytes must be equal.
  The same flush compressed with the host (numpy) cone scan gives the
  sanity ratio: the device route may not be more than 2% worse.
* fleet   — (``--fleet`` only) the ``run_fleet_sim`` workload on a 4-shard
  ``ShrinkFleet``, one shard per device, byte for byte against the 1-shard
  replay; every device must have run cone-scan kernels.

Each phase prints one JSON line with the device, samples, compression
ratio, wall seconds (the first call, which compiles, apart from the warm
ones) and the device kernel calls it made.  These timings are set-up
evidence, not benchmark numbers.  The last line is
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``,
and the exit code is 0 only when every check passed on a TPU.  Without a
TPU the script stops at once unless ``--small`` asks for a rehearsal.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

EPS = 8e-3
TIERS = [EPS, 0.0]
DECIMALS = 4
RATIO_SLACK = 0.98  # device-route ratio may be at most 2% below the host's
KERNELS = ("cone_scan", "rans_encode", "rans_decode")


def _calls() -> collections.Counter:
    """Device kernel calls so far, summed over devices."""
    from repro.kernels.calls import call_counts

    out: collections.Counter = collections.Counter()
    for (name, _platform, _dev), n in call_counts().items():
        out[name] += n
    return out


class Phase:
    """One phase's JSON line: device, checks, kernel calls and wall time."""

    def __init__(self, name: str, device: dict):
        self.line = {
            "phase": name,
            "platform": device["platform"],
            "device_kind": device["kind"],
            "device_count": device["count"],
        }
        self.failures: list[str] = []
        self._calls0 = _calls()
        self._t0 = time.perf_counter()

    def check(self, cond: bool, what: str) -> None:
        if not cond:
            self.failures.append(what)

    def calls(self) -> dict:
        now = _calls()
        return {k: now[k] - self._calls0[k] for k in KERNELS}

    def finish(self, **fields) -> bool:
        self.line.update(fields)
        self.line["kernel_calls"] = self.calls()
        self.line["wall_s"] = time.perf_counter() - self._t0
        self.line["ok"] = not self.failures
        self.line["failures"] = self.failures
        print(json.dumps(self.line), flush=True)
        return not self.failures


def _config():
    from repro.core import ShrinkConfig

    return ShrinkConfig(eps_b=0.4, lam=1e-4)  # as ``serve --mode ingest``


def ingest_phase(args, device: dict, on_tpu: bool, cache: dict):
    from repro.core import BYTES_PER_ROW
    from repro.data.synthetic import ragged_sensor_traffic
    from repro.serving import RangeQuery, RangeQueryBatcher, RaggedBatcher

    sensors, ticks, flush = (64, 80, 262_144) if args.small else (1024, 200, 4_194_304)
    traffic = ragged_sensor_traffic(sensors, ticks, seed=args.seed)
    ph = Phase("ingest", device)
    batcher = RaggedBatcher(
        _config(), eps_targets=TIERS, decimals=DECIMALS, backend="rans",
        flush_samples=flush, semantics="auto",
    )
    history: dict[int, list[np.ndarray]] = {}
    flush_s: list[float] = []
    first_flush: list[tuple[int, int, int]] = []
    for tick in traffic:
        for sid, chunk in tick:
            history.setdefault(sid, []).append(chunk)
            t0 = time.perf_counter()
            sealed = batcher.submit(sid, chunk)
            if sealed:
                flush_s.append(time.perf_counter() - t0)
                first_flush = first_flush or sealed
    t0 = time.perf_counter()
    blob = batcher.finalize()
    flush_s.append(time.perf_counter() - t0)
    first_flush = first_flush or batcher.sealed_frames
    raw = {sid: np.concatenate(chunks) for sid, chunks in sorted(history.items())}
    samples = sum(v.size for v in raw.values())
    ingest_calls = ph.calls()

    # every series in full at both tiers, through the serving decoder
    t0 = time.perf_counter()
    dec = RangeQueryBatcher(blob, cache_frames=64)
    worst, inexact, errors = 0.0, 0, 0
    for sid, v in raw.items():
        for qid, eps in enumerate(TIERS):
            dec.submit(RangeQuery(qid=qid, series_id=sid, t0=0, t1=v.size, eps=eps))
        lossy, exact = dec.run()
        errors += (lossy.error is not None) + (exact.error is not None)
        if lossy.error is None:
            worst = max(worst, float(np.abs(lossy.result - v).max()))
        if exact.error is None:
            inexact += not np.array_equal(exact.result, v)
    verify_s = time.perf_counter() - t0

    ph.check(errors == 0, f"{errors} full-series decodes raised")
    ph.check(worst <= EPS * (1 + 1e-9), f"tier {EPS}: max |err| {worst} > {EPS}")
    ph.check(inexact == 0, f"tier 0.0: {inexact} series not bit-exact")
    if on_tpu:
        # the rehearsal's rANS jobs stay under the device engine's size
        # threshold, so only the full size must show device rANS calls
        for k in KERNELS[:1] if args.small else KERNELS:
            ph.check(ph.calls()[k] > 0, f"no device {k} calls")
    ok = ph.finish(
        sensors=sensors,
        ticks=ticks,
        samples=samples,
        series=len(raw),
        flushes=batcher.stats()["flushes"],
        frames=len(batcher.sealed_frames),
        compression_ratio=samples * BYTES_PER_ROW / len(blob),
        compile_cache=cache,
        first_flush_s=flush_s[0],
        warm_flush_s=flush_s[1:],
        ingest_kernel_calls=ingest_calls,
        verify_s=verify_s,
        max_err_lossy=worst,
        series_inexact_lossless=inexact,
    )
    return ok, blob, raw, first_flush


def query_phase(args, device: dict, blob: bytes, raw: dict):
    from repro.analytics import AnalyticsEngine
    from repro.serving import RangeQuery, RangeQueryBatcher

    ph = Phase("query", device)
    engine = AnalyticsEngine(RangeQueryBatcher(blob, cache_frames=64))
    rng = np.random.default_rng(args.seed + 1)
    sids = [sid for sid, v in raw.items() if v.size >= 16]
    ops = ["min", "max", "sum", "mean", "stddev"]
    n_queries = 128 if args.small else 512
    kinds = collections.Counter()
    bad: list[int] = []
    lat: list[float] = []
    for qid in range(n_queries):
        sid = int(rng.choice(sids))
        v = raw[sid]
        lo = int(rng.integers(0, v.size - 8))
        hi = int(min(v.size, lo + 8 + rng.integers(0, 4096)))
        sl = v[lo:hi]
        kind = qid % 6
        t0 = time.perf_counter()
        if kind < 2:  # range decode at each tier
            eps = TIERS[kind]
            batcher = engine.batcher
            batcher.submit(RangeQuery(qid=qid, series_id=sid, t0=lo, t1=hi, eps=eps))
            (q,) = batcher.run()
            good = q.error is None and (
                np.array_equal(q.result, sl) if eps == 0.0
                else float(np.abs(q.result - sl).max()) <= eps * (1 + 1e-9)
            )
            name = f"range@{eps}"
        elif kind < 5:  # aggregate: segment sketch, lossy tier, exact tier
            eps = (None, EPS, 0.0)[kind - 2]
            op = ops[qid % len(ops)]
            ans = engine.aggregate(sid, op, lo, hi, eps=eps)
            truth = {"min": sl.min, "max": sl.max, "sum": sl.sum,
                     "mean": sl.mean, "stddev": sl.std}[op]()
            good = ans.lo - 1e-9 <= truth <= ans.hi + 1e-9
            # a tier answer is no wider than the tier allows: per point
            # (times the sample count for a sum), plus float rounding
            terms = sl.size if op == "sum" else 1
            rounding = 1e-9 * terms * max(1.0, float(np.abs(sl).max()))
            if eps is not None:
                good &= ans.hi - ans.lo <= 2 * terms * eps + rounding
            name = f"aggregate@{eps}"
        else:  # exact threshold count
            c = float(rng.uniform(sl.min(), sl.max() + 1e-9))
            ans = engine.count_where(sid, "gt", c, lo, hi, eps=0.0)
            truth = float((sl > c).sum())
            good = ans.exact and ans.lo - 1e-9 <= truth <= ans.hi + 1e-9
            name = "count_where@0.0"
        lat.append(time.perf_counter() - t0)
        kinds[name] += 1
        if not good:
            bad.append(qid)
    ph.check(not bad, f"{len(bad)} queries wrong: {bad[:10]}")
    return ph.finish(
        queries=n_queries,
        verified=n_queries - len(bad),
        by_kind=dict(kinds),
        first_query_s=lat[0],
        warm_query_s_median=float(np.median(lat[1:])),
        warm_query_s_max=float(np.max(lat[1:])),
    )


def entropy_phase(args, device: dict, on_tpu: bool, blob: bytes, raw: dict, first_flush):
    from repro.core import BYTES_PER_ROW, ShrinkCodec, cs_from_bytes, cs_to_bytes, entropy
    from repro.core.serialize import frame_payload, parse_framed_container

    ph = Phase("entropy", device)
    metas, _ = parse_framed_container(blob)
    wanted = {(sid, lo) for sid, lo, _hi in first_flush}
    payloads = [
        layer.payload
        for m in metas
        if (m.series_id, m.t_lo) in wanted
        for layer in cs_from_bytes(frame_payload(blob, m)).pyramid.layers
        if layer.payload is not None
    ]
    streams = [entropy.decode_ints(p) for p in payloads]

    encodes0 = _calls()["rans_encode"]
    t0 = time.perf_counter()
    device_blobs = entropy.encode_ints_batch(streams, backend="rans")
    device_s = time.perf_counter() - t0
    device_encodes = _calls()["rans_encode"] - encodes0
    threshold = entropy._RANS_DEVICE_MIN
    entropy._RANS_DEVICE_MIN = sys.maxsize  # the numpy coder only
    try:
        t0 = time.perf_counter()
        numpy_blobs = entropy.encode_ints_batch(streams, backend="rans")
        numpy_s = time.perf_counter() - t0
    finally:
        entropy._RANS_DEVICE_MIN = threshold
    mismatched = sum(a != b for a, b in zip(device_blobs, numpy_blobs))
    ph.check(mismatched == 0, f"{mismatched} streams differ device vs numpy")
    ph.check(device_blobs == payloads, "re-encoded streams differ from the sealed frames")
    if on_tpu and not args.small:
        ph.check(device_encodes > 0, "the device route ran no rANS encode")

    # sanity ratio: the same flush through the device and the host cone scan
    arrs = [raw[sid][lo:hi] for sid, lo, hi in first_flush]
    raw_bytes = BYTES_PER_ROW * sum(a.size for a in arrs)
    codec = ShrinkCodec(config=_config(), backend="rans")
    ratios, flush_s = {}, {}
    for semantics in ("auto", "numpy"):
        t0 = time.perf_counter()
        css = codec.compress_batch(arrs, eps_targets=TIERS, decimals=DECIMALS,
                                   semantics=semantics)
        flush_s[semantics] = time.perf_counter() - t0
        ratios[semantics] = raw_bytes / sum(len(cs_to_bytes(cs)) for cs in css)
    ph.check(
        ratios["auto"] >= RATIO_SLACK * ratios["numpy"],
        f"device-route ratio {ratios['auto']} more than 2% below host {ratios['numpy']}",
    )
    return ph.finish(
        streams=len(streams),
        symbols=int(sum(s.size for s in streams)),
        device_encode_calls=device_encodes,
        streams_mismatched=mismatched,
        device_route_encode_s=device_s,
        numpy_encode_s=numpy_s,
        samples=int(sum(a.size for a in arrs)),
        compression_ratio_device_scan=ratios["auto"],
        compression_ratio_host_scan=ratios["numpy"],
        warm_flush_s_device_scan=flush_s["auto"],
        flush_s_host_scan=flush_s["numpy"],
    )


def fleet_phase(args, device: dict, on_tpu: bool) -> bool:
    import jax

    from repro.kernels.calls import call_counts
    from repro.launch.serve import run_fleet_sim

    ph = Phase("fleet", device)
    devices = jax.devices()
    ph.check(len(devices) >= 4, f"the fleet needs 4 devices, found {len(devices)}")
    size = dict(series=8, ticks=30, queries=48) if args.small else {}
    r = run_fleet_sim(n_shards=4, seed=args.seed, **size)
    ph.check(r["byte_mismatch"] == 0, f"{r['byte_mismatch']} series differ from the 1-shard replay")
    ph.check(r["silent"] == 0, f"{r['silent']} silent corruptions")
    scans = collections.Counter()
    for (name, _platform, dev), n in call_counts().items():
        if name == "cone_scan":
            scans[dev] += n
    peaks = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices}
    if on_tpu:
        for d in devices[:4]:
            ph.check(scans[d.id] > 0, f"device {d.id} ran no cone scan")
            ph.check(peaks[d.id] > 0, f"device {d.id} allocated nothing")
    return ph.finish(
        shards=r["n_shards"],
        series=r["series"],
        samples=r["samples"],
        byte_mismatch=r["byte_mismatch"],
        silent=r["silent"],
        queries=r["query"],
        cone_scan_calls_by_device={str(k): v for k, v in sorted(scans.items())},
        peak_bytes_by_device={str(k): v for k, v in sorted(peaks.items())},
        ingest_p50_ms=r["ingest"]["p50_ms"],
        ingest_p99_ms=r["ingest"]["p99_ms"],
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--small", action="store_true",
                    help="rehearsal size (64 sensors x 80 ticks); runs on any backend")
    ap.add_argument("--fleet", action="store_true",
                    help="only the 4-shard fleet phase (needs four chips)")
    args = ap.parse_args(argv)

    import jax

    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.small:
        print(f"chip_smoke: no TPU (JAX found {device}); use --small to rehearse",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    # what the first compiles can find: the first flush's time depends on it
    cache = {
        "dir": cache_dir,
        "from_env": "JAX_COMPILATION_CACHE_DIR" in os.environ,
        "entries_at_start": len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0,
    }
    if args.fleet:
        ok = fleet_phase(args, device, on_tpu)
    else:
        ok, blob, raw, first_flush = ingest_phase(args, device, on_tpu, cache)
        ok &= query_phase(args, device, blob, raw)
        ok &= entropy_phase(args, device, on_tpu, blob, raw, first_flush)
    ok &= on_tpu
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
