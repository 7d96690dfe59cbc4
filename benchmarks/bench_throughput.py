"""Fig. 11 + Table III: compression throughput / latency, plus the repo's
own engine benchmarks (entropy backends, batched multi-series pipeline).

All methods are measured under the same harness (pure Python/numpy, one
CPU), so the paper's claim is validated as a RELATIVE ordering (SHRINK ~3x
Sim-Piece/APCA, comparable to LFZip/HIRE), not absolute MB/s.  Table III's
base-vs-residual split is reproduced by timing build_base separately from
residual encoding at eps in {0, 0.001, 0.01}.

``entropy_backends`` and ``batched_pipeline`` track this reproduction's own
perf surface: the vectorized rANS engine against the per-symbol adaptive
range coder, and ``ShrinkCodec.compress_batch`` against a python loop of
``compress``.  ``throughput_json`` assembles both into the machine-readable
trajectory written to BENCH_throughput.json at the repo root.
"""
from __future__ import annotations

import time

import numpy as np

from repro.baselines import LOSSLESS, LOSSY
from repro.core import ShrinkCodec, compute_residuals, quantize_exact, quantize_residuals
from repro.core import entropy as entropy_mod
from repro.data.synthetic import DATASETS

from .datasets import NINE, Timer, bench_series, save_result


def fig11_throughput(n=50_000, datasets=("FaceFour", "MoteStrain", "ECG", "WindSpeed", "Pressure")) -> dict:
    """MB/s per lossy compressor, averaged over eps in {1e-2, 1e-3} of range."""
    out = {}
    for name in datasets:
        v = bench_series(name, n)
        rng = float(v.max() - v.min())
        mb = len(v) * 16 / 1e6
        row = {}
        for method in ("SimPiece", "APCA", "LFZip", "HIRE"):
            ts = []
            for rel in (1e-2, 1e-3):
                with Timer() as t:
                    LOSSY[method](v, rel * rng)
                ts.append(t.seconds)
            row[method] = mb / np.mean(ts)
        ts = []
        for rel in (1e-2, 1e-3):
            codec = ShrinkCodec.from_fraction(v, frac=0.05, backend="rans")
            with Timer() as t:
                codec.compress(v, eps_targets=[rel * rng])
            ts.append(t.seconds)
        row["SHRINK"] = mb / np.mean(ts)
        out[name] = row
    save_result("fig11_throughput", out)
    return out


def table3_latency(n=50_000, datasets=NINE) -> dict:
    """Lossless baselines vs SHRINK split into base construction + residual
    encoding at eps in {0 (lossless), 0.001, 0.01} of range."""
    out = {}
    for name in datasets:
        v = bench_series(name, n)
        d = DATASETS[name].decimals
        rng = float(v.max() - v.min())
        row = {}
        for method in ("GZip", "TRC", "BZip2", "Gorilla", "GD"):
            with Timer() as t:
                LOSSLESS[method](v, d)
            row[method] = t.seconds
        codec = ShrinkCodec.from_fraction(v, frac=0.05, backend="rans")
        with Timer() as t:
            base = codec.build_base(v)
        row["SHRINK_base"] = t.seconds
        r = compute_residuals(v, base)
        res_times = {}
        for eps_rel in (0.0, 0.001, 0.01):
            with Timer() as t:
                if eps_rel == 0.0:
                    stream = quantize_exact(v, base, d)
                else:
                    stream = quantize_residuals(r, eps_rel * rng)
                entropy_mod.encode_ints(stream.q, backend="rans")
            res_times[str(eps_rel)] = t.seconds
        row["SHRINK_residual"] = res_times
        out[name] = row
    save_result("table3_latency", out)
    return out


def _best_of(fn, reps: int = 3) -> float:
    """Best wall-clock of ``reps`` runs — the standard defense against a
    noisy shared-CPU box."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def entropy_backends(n: int = 50_000, reps: int = 3) -> dict:
    """Encode+decode MB/s per entropy backend on a gaussian residual stream
    (the shape residual quantization emits).  MB/s counts 8 B/symbol (the
    int64 payload)."""
    rng = np.random.default_rng(0)
    q = np.round(rng.standard_normal(n) * 200).astype(np.int64)
    mb = q.size * 8 / 1e6
    out = {"symbols": n, "bytes_per_symbol": 8}
    for backend in entropy_mod.available_backends():
        blob = entropy_mod.encode_ints(q, backend=backend)
        t_enc = _best_of(lambda: entropy_mod.encode_ints(q, backend=backend), reps)
        t_dec = _best_of(lambda: entropy_mod.decode_ints(blob), reps)
        out[backend] = {
            "encode_mb_s": mb / t_enc,
            "decode_mb_s": mb / t_dec,
            "roundtrip_mb_s": mb / (t_enc + t_dec),
            "bytes": len(blob),
        }
    if "rans" in out and "rc" in out:
        out["rans_vs_rc_roundtrip_speedup"] = (
            out["rans"]["roundtrip_mb_s"] / out["rc"]["roundtrip_mb_s"]
        )
    save_result("entropy_backends", out)
    return out


def entropy_kernel(n: int = 50_000, reps: int = 3) -> dict:
    """Device rANS engine (kernels.rans) vs the numpy coder on the same
    stream: the stream is past the engine's size threshold, and raising
    the threshold past it runs the numpy coder.  Both emit the same wire
    format; ``bytes_identical`` asserts it per run so a silent format
    divergence fails the benchmark, not just the tests."""
    import sys

    from repro.kernels.calls import call_counts

    def _engine_encodes() -> int:
        return sum(v for k, v in call_counts().items() if k[0] == "rans_encode")

    rng = np.random.default_rng(7)
    q = np.round(rng.standard_normal(n) * 200).astype(np.int64)
    mb = q.size * 8 / 1e6
    threshold = entropy_mod._RANS_DEVICE_MIN
    entropy_mod._RANS_DEVICE_MIN = sys.maxsize  # the numpy coder only
    try:
        blob_np = entropy_mod.encode_ints(q, backend="rans")
        t_enc_np = _best_of(lambda: entropy_mod.encode_ints(q, backend="rans"), reps)
        t_dec_np = _best_of(lambda: entropy_mod.decode_ints(blob_np), reps)
    finally:
        entropy_mod._RANS_DEVICE_MIN = threshold

    encodes0 = _engine_encodes()
    entropy_mod.decode_ints(entropy_mod.encode_ints(q, backend="rans"))  # warm jit
    blob_dev = entropy_mod.encode_ints(q, backend="rans")
    t_enc_dev = _best_of(lambda: entropy_mod.encode_ints(q, backend="rans"), reps)
    t_dec_dev = _best_of(lambda: entropy_mod.decode_ints(blob_dev), reps)
    engaged = _engine_encodes() > encodes0

    out = {
        "symbols": n,
        "bytes_per_symbol": 8,
        "device_engaged": bool(engaged),
        "bytes_identical": blob_np == blob_dev,
        "numpy": {
            "encode_mb_s": mb / t_enc_np,
            "decode_mb_s": mb / t_dec_np,
            "roundtrip_mb_s": mb / (t_enc_np + t_dec_np),
        },
        "device": {
            "encode_mb_s": mb / t_enc_dev,
            "decode_mb_s": mb / t_dec_dev,
            "roundtrip_mb_s": mb / (t_enc_dev + t_dec_dev),
        },
        "vs_numpy": (t_enc_np + t_dec_np) / (t_enc_dev + t_dec_dev),
    }
    save_result("entropy_kernel", out)
    return out


def batched_pipeline(s: int = 64, t: int = 8192, reps: int = 3) -> dict:
    """compress_batch vs a python loop of compress on S synthetic gateway
    streams (random walk + sensor noise), same eps targets, rans backend.
    The numpy batch path is byte-identical to the loop, so this is a pure
    throughput comparison."""
    rng = np.random.default_rng(42)
    v = np.cumsum(rng.standard_normal((s, t)) * 0.05, axis=1)
    v += rng.standard_normal((s, t)) * 0.02
    v = np.round(v, 4)
    codec = ShrinkCodec.from_fraction(v, frac=0.05, backend="rans")
    rngv = float(v.max() - v.min())
    eps_ts = [1e-2 * rngv, 1e-3 * rngv, 0.0]
    mb = s * t * 16 / 1e6

    codec.compress_batch(v[:2], eps_targets=eps_ts, decimals=4)  # warm caches
    t_batch = _best_of(lambda: codec.compress_batch(v, eps_targets=eps_ts, decimals=4), reps)
    t_loop = _best_of(
        lambda: [codec.compress(v[i], eps_targets=eps_ts, decimals=4) for i in range(s)],
        reps,
    )
    out = {
        "series": s,
        "points_per_series": t,
        # 16 B/row (timestamp, value) — the repo-wide CR/throughput
        # accounting shared with fig11 (see core.shrink.BYTES_PER_ROW)
        "bytes_per_row": 16,
        "batch_mb_s": mb / t_batch,
        "loop_mb_s": mb / t_loop,
        "batch_speedup": t_loop / t_batch,
    }
    save_result("batched_pipeline", out)
    return out


def throughput_json(quick: bool = False) -> dict:
    """The machine-readable perf trajectory (BENCH_throughput.json).  The
    workload sizes are embedded so trajectories from --quick runs are never
    mistaken for (or diffed against) full-size numbers."""
    n = 20_000 if quick else 50_000
    s, t = (16, 4096) if quick else (64, 8192)
    return {
        "workload": "quick" if quick else "full",
        "entropy_backends": entropy_backends(n=n),
        "entropy_kernel": entropy_kernel(n=n),
        "batched_pipeline": batched_pipeline(s=s, t=t),
    }


def validate_claims(fig11) -> dict:
    ratios = []
    for name, row in fig11.items():
        ratios.append(row["SHRINK"] / max(min(row["SimPiece"], row["APCA"]), 1e-9))
    checks = {
        "C6_shrink_faster_than_piecewise": {
            "median_speedup_vs_slowest_piecewise": float(np.median(ratios)),
            "pass": bool(np.median(ratios) >= 1.5),
        }
    }
    save_result("claims_throughput", checks)
    return checks


# the numpy coder's roundtrip MB/s at the seed of this claim (pre-kernel,
# pre-vectorized-normalize) — the device engine is ratcheted against this
# fixed baseline, not the live numpy path, which also got faster
_SEED_NUMPY_ROUNDTRIP_MB_S = 6.5


def validate_engine_claims(engine: dict) -> dict:
    """Ratcheted claims over the repo's own engine trajectory: the device
    entropy kernel must hold >= 5x the seed numpy coder's 6.5 MB/s
    roundtrip, and the rect batch pipeline must stay >= 1.2x over the
    python loop (the PR-7 regression retired at 0.88x must never come
    back)."""
    ek = engine["entropy_kernel"]
    bp = engine["batched_pipeline"]
    dev_rt = float(ek["device"]["roundtrip_mb_s"])
    checks = {
        "C_entropy_kernel_5x": {
            "device_roundtrip_mb_s": round(dev_rt, 2),
            "seed_numpy_roundtrip_mb_s": _SEED_NUMPY_ROUNDTRIP_MB_S,
            "vs_live_numpy": round(float(ek["vs_numpy"]), 2),
            "bytes_identical": bool(ek["bytes_identical"]),
            "device_engaged": bool(ek["device_engaged"]),
            "pass": bool(
                ek["device_engaged"]
                and ek["bytes_identical"]
                and dev_rt >= 5.0 * _SEED_NUMPY_ROUNDTRIP_MB_S
            ),
        },
        "C_rect_batch_faster": {
            "batch_speedup": round(float(bp["batch_speedup"]), 2),
            "pass": bool(bp["batch_speedup"] >= 1.2),
        },
    }
    save_result("claims_engine", checks)
    return checks
