"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Runs one benchmark per paper table/figure + the roofline assembly, prints
compact tables, validates the paper's claims (C1..C6), and writes JSON to
artifacts/bench/.  ``--quick`` shrinks sizes for CI-speed runs; ``--full``
uses Table II row counts where tractable.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

from . import (
    bench_analytics,
    bench_backends,
    bench_compression,
    bench_fleet,
    bench_kbstore,
    bench_progressive,
    bench_ragged,
    bench_robustness,
    bench_roofline,
    bench_scaling,
    bench_sensitivity,
    bench_streaming,
    bench_throughput,
)

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _fmt_cr_table(fig, methods) -> str:
    lines = []
    for name, row in fig.items():
        if not row["eps"]:
            continue
        strict = {m: row[m][-1] for m in methods if m in row}
        loose = {m: row[m][0] for m in methods if m in row}
        lines.append(
            f"  {name:14s} loosest: "
            + "  ".join(f"{m}={loose[m]:7.1f}" for m in loose)
            + f"   strictest: "
            + "  ".join(f"{m}={strict[m]:7.1f}" for m in strict)
            + f"   lossless(SHRINK)={row['SHRINK_lossless']:.1f}"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small sizes (CI)")
    ap.add_argument("--full", action="store_true", help="Table II row counts")
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    n6 = 20_000 if args.quick else (None if args.full else 100_000)
    n7 = 10_000 if args.quick else (50_000 if not args.full else 200_000)
    n8 = 20_000 if args.quick else (100_000 if not args.full else None)
    n_sens = 30_000 if args.quick else 200_000
    sizes10 = (
        (20_000, 50_000, 100_000)
        if args.quick
        else (50_000, 100_000, 250_000, 500_000, 1_000_000, 2_000_000)
    )
    n11 = 10_000 if args.quick else 50_000

    t0 = time.time()
    print("== Fig 6: vs Sim-Piece / APCA (piecewise lossy) ==")
    fig6 = bench_compression.fig6_piecewise_lossy(n=n6)
    print(_fmt_cr_table(fig6, ["SHRINK", "SimPiece", "APCA"]))

    print("\n== Fig 7: vs LFZip / HIRE (general lossy) ==")
    fig7 = bench_compression.fig7_general_lossy(n=n7)
    print(_fmt_cr_table(fig7, ["SHRINK", "LFZip", "HIRE"]))

    print("\n== Fig 8: lossless ==")
    fig8 = bench_compression.fig8_lossless(n=n8)
    for name, row in fig8.items():
        print("  " + name.ljust(14) + "  ".join(f"{k}={v:6.2f}" for k, v in sorted(row.items())))

    checks = bench_compression.validate_claims(fig6, fig7, fig8)

    print("\n== Fig 9: eps_b sensitivity ==")
    fig9 = bench_sensitivity.fig9_eps_b_effect(n=n_sens)
    for k, v in fig9.items():
        if k != "eps":
            print(f"  {k}: CR={['%.1f' % c for c in v['cr']]} base={v['base_bytes']}B k={v['k_subbases']}")

    print("\n== Fig 12: lambda sensitivity ==")
    fig12 = bench_sensitivity.fig12_lambda_effect(n=n_sens)
    for k, v in fig12.items():
        print(f"  lambda={k}: CR={v['cr']:.1f} latency={v['latency_s']:.2f}s segments={v['segments']}")
    checks.update(bench_sensitivity.validate_claims(fig9, fig12))

    print("\n== Fig 10: size scaling ==")
    fig10 = bench_scaling.fig10_size_scaling(sizes=sizes10)
    for i, n in enumerate(fig10["sizes"]):
        print(
            f"  n={n:9d} dict={fig10['dict_bytes'][i]:7d}B (k={fig10['k_subbases'][i]:5d}) "
            f"timestamps={fig10['timestamp_bytes'][i]:9d}B residual={fig10['residual_bytes'][i]:10d}B "
            f"CR(lossless)={fig10['cr_lossless'][i]:6.2f}"
        )
    checks.update(bench_scaling.validate_claims(fig10))

    print("\n== Fig 11 / Table III: throughput ==")
    fig11 = bench_throughput.fig11_throughput(n=n11)
    for name, row in fig11.items():
        print("  " + name.ljust(14) + "  ".join(f"{k}={v:6.2f}MB/s" for k, v in sorted(row.items())))
    t3 = bench_throughput.table3_latency(n=n11)
    checks.update(bench_throughput.validate_claims(fig11))

    print("\n== Engine throughput (entropy backends + batched pipeline) ==")
    engine = bench_throughput.throughput_json(quick=args.quick)
    for backend, row in engine["entropy_backends"].items():
        if isinstance(row, dict):
            print(
                f"  entropy[{backend:4s}] enc={row['encode_mb_s']:8.2f}MB/s "
                f"dec={row['decode_mb_s']:8.2f}MB/s size={row['bytes']}B"
            )
    ek = engine["entropy_kernel"]
    print(
        f"  kernel[n={ek['symbols']}] "
        f"device={ek['device']['roundtrip_mb_s']:.2f}MB/s "
        f"numpy={ek['numpy']['roundtrip_mb_s']:.2f}MB/s "
        f"({ek['vs_numpy']:.2f}x, bytes_identical={ek['bytes_identical']})"
    )
    bp = engine["batched_pipeline"]
    print(
        f"  batch[{bp['series']}x{bp['points_per_series']}] "
        f"batch={bp['batch_mb_s']:.2f}MB/s loop={bp['loop_mb_s']:.2f}MB/s "
        f"speedup={bp['batch_speedup']:.2f}x"
    )
    checks.update(bench_throughput.validate_engine_claims(engine))

    print("\n== Adaptive entropy dispatch (cost-model routing vs all-rans) ==")
    adaptive = bench_backends.adaptive_json(quick=args.quick)
    engine["adaptive"] = adaptive
    mix = "  ".join(
        f"{b}={d['streams']}" for b, d in sorted(adaptive["adaptive"]["routing"].items())
    )
    print(
        f"  corpus[{adaptive['series']}x{adaptive['points_per_series']}] "
        f"adaptive={adaptive['adaptive']['archive_bytes']:,}B "
        f"all-rans={adaptive['forced_rans']['archive_bytes']:,}B "
        f"(cr_ratio={adaptive['cr_ratio']:.3f})"
    )
    print(
        f"  encode: adaptive={adaptive['adaptive']['encode_mb_s']:.2f}MB/s "
        f"all-rans={adaptive['forced_rans']['encode_mb_s']:.2f}MB/s "
        f"(speed_ratio={adaptive['speed_ratio']:.2f})  streams: {mix}"
    )
    checks.update(bench_backends.validate_claims(adaptive))

    print("\n== Streaming ingest (chunked scan + framed container) ==")
    stream = bench_streaming.streaming_json(quick=args.quick)
    engine["streaming"] = stream
    ing = stream["ingest"]
    chunk_cols = "  ".join(
        f"{k.removeprefix('chunk_').removesuffix('_mb_s')}={v:.1f}MB/s"
        for k, v in ing.items() if k.startswith("chunk_")
    )
    print(
        f"  ingest[{ing['series']}x{ing['points_per_series']}] "
        f"one-shot={ing['one_shot_mb_s']:.1f}MB/s  {chunk_cols} "
        f"({ing['stream_vs_one_shot']:.2f}x one-shot)"
    )
    crg = stream["cr_growth"]
    for i, n in enumerate(crg["lengths"]):
        print(
            f"  n={n:8d}  CR(lossless)={crg['cr_lossless'][i]:6.2f} "
            f"CR(eps=1e-3)={crg['cr_eps1e-3'][i]:6.2f}"
        )
    checks.update(bench_streaming.validate_claims(stream))

    print("\n== Ragged multi-series ingest (bucketed batch + scheduler) ==")
    ragged = bench_ragged.ragged_json(quick=args.quick)
    engine["ragged"] = ragged
    rp = ragged["pipeline"]
    print(
        f"  ragged[{rp['series']} series, len {rp['len_min']}..{rp['len_max']}] "
        f"batch={rp['batch_mb_s']:.2f}MB/s loop={rp['loop_mb_s']:.2f}MB/s "
        f"speedup={rp['batch_speedup']:.2f}x"
    )
    rs = ragged["scheduler"]
    print(
        f"  scheduler[{rs['series']} sensors, {rs['samples']} samples] "
        f"ingest={rs['ingest_mb_s']:.2f}MB/s (admission + SHRKS assembly)"
    )
    checks.update(bench_ragged.validate_claims(ragged))

    print("\n== Progressive pyramid (layered archive + tiered decode) ==")
    prog = bench_progressive.progressive_json(quick=args.quick)
    engine["progressive"] = prog
    for name, row in prog["archive"]["datasets"].items():
        print(
            f"  {name:10s} pyramid={row['pyramid_residual_bytes']:9,d}B "
            f"independent={row['independent_residual_bytes']:9,d}B "
            f"({row['pyramid_vs_independent']:.2f}x)"
        )
    dec = prog["decode"]
    tier_cols = "  ".join(
        f"{k}={v:.1f}MB/s" for k, v in dec["decode_mb_s"].items()
    )
    print(f"  decode[{dec['dataset']}] {tier_cols}")
    print(
        f"  refine coarse->lossless {dec['refine_coarse_to_lossless_mb_s']:.1f}MB/s "
        f"vs cold {dec['cold_lossless_mb_s']:.1f}MB/s "
        f"({dec['refine_vs_cold']:.2f}x)"
    )
    checks.update(bench_progressive.validate_claims(prog))

    print("\n== Compressed-domain analytics (segment algebra + refine planner) ==")
    analytics = bench_analytics.analytics_json(quick=args.quick)
    engine["analytics"] = analytics
    for name, row in analytics["segment_vs_decode"]["datasets"].items():
        worst = min(row["ops"], key=lambda o: row["ops"][o]["speedup"])
        print(
            f"  {name:10s} segments={row['segments']:6d} "
            f"min speedup={row['min_speedup']:6.1f}x (op={worst}) "
            f"eps_b={row['eps_b_practical']:.3g} <= eps_q={row['eps_query']:.3g}"
        )
    pred = analytics["predicate"]
    print(
        f"  predicate[{pred['dataset']}] {pred['queries_per_s']:.0f} q/s exact counts, "
        f"refined {pred['frames_refined']}/{pred['frames_touched']} frames "
        f"({pred['mb_covered_per_s']:.0f} MB/s covered)"
    )
    checks.update(bench_analytics.validate_claims(analytics))

    print("\n== Robustness (CRC overhead, degraded path, chaos campaign) ==")
    rob = bench_robustness.robustness_json(quick=args.quick)
    engine["robustness"] = rob
    io_ = rob["integrity_overhead"]
    print(
        f"  integrity[{io_['series']}x{io_['points_per_series']}] "
        f"decode={io_['decode_mb_s']:.1f}MB/s "
        f"crc sweep={io_['crc_sweep_s']*1e3:.2f}ms "
        f"({io_['crc_overhead_frac']*100:.1f}% of decode)"
    )
    dp = rob["degraded_path"]
    print(
        f"  degraded path: healthy={dp['healthy_ms']:.2f}ms "
        f"corrupt-layer={dp['degraded_ms']:.2f}ms "
        f"({dp['degraded_vs_healthy']:.2f}x)"
    )
    cc = rob["chaos_campaign"]
    print(
        f"  chaos[{cc['rounds']} faults] {cc['queries_checked']} answers checked "
        f"({cc['queries_per_s']:.0f} q/s): {cc['ok']} ok, {cc['degraded']} degraded, "
        f"{cc['typed_error']} typed errors, {cc['rejected_at_parse']} parse rejects, "
        f"{cc['silent']} SILENT"
    )
    checks.update(bench_robustness.validate_claims(rob))

    print("\n== Sharded serving fleet (scaling, tenancy, cross-shard diff) ==")
    fl = bench_fleet.fleet_json(quick=args.quick)
    engine["fleet"] = fl
    one, four = fl["one_shard"], fl["four_shards"]
    print(
        f"  workload[{fl['workload']['series']} series, "
        f"{fl['workload']['samples']:,} samples, {fl['workload']['mb']:.1f}MB, "
        f"{fl['workload']['quota_rejected_ingest']} quota-rejected]"
    )
    print(
        f"  1 shard : {one['agg_mb_s']:6.1f}MB/s  "
        f"ingest p50={one['ingest_p50_ms']:.2f}ms p99={one['ingest_p99_ms']:.2f}ms  "
        f"query p50={one['query_p50_ms']:.2f}ms p99={one['query_p99_ms']:.2f}ms"
    )
    print(
        f"  4 shards: {four['agg_mb_s']:6.1f}MB/s  "
        f"ingest p50={four['ingest_p50_ms']:.2f}ms p99={four['ingest_p99_ms']:.2f}ms  "
        f"query p50={four['query_p50_ms']:.2f}ms p99={four['query_p99_ms']:.2f}ms  "
        f"(critical-path scaling {fl['scaling_1_to_4']:.2f}x)"
    )
    q, k = four["queries"], four["shard_kill"]
    print(
        f"  diff: {q['ok']} ok / {q['degraded']} degraded / {q['error']} typed / "
        f"{q['SILENT']} SILENT; shard-kill [{k.get('fault', '')}] "
        f"{k['ok']} ok / {k['degraded']} degraded / {k['error']} typed / "
        f"{k['SILENT']} SILENT; byte mismatches={fl['byte_mismatch']}"
    )
    checks.update(bench_fleet.validate_claims(fl))

    print("\n== Cross-archive KB store (shared dictionary vs per-archive) ==")
    kbs = bench_kbstore.kbstore_json(quick=args.quick)
    engine["kbstore"] = kbs
    print(
        f"  corpus[{kbs['corpus']['archives']} archives, "
        f"{kbs['corpus']['samples']:,} samples]  "
        f"inline={kbs['inline']['total_bytes']:,}B "
        f"(KB share {kbs['inline']['kb_share']:.1%})"
    )
    print(
        f"  shared={kbs['shared']['total_bytes']:,}B "
        f"({kbs['shared']['container_bytes']:,}B containers + "
        f"{kbs['shared']['snapshot_bytes']:,}B snapshot; "
        f"{kbs['shared']['store_live_entries']} live entries, "
        f"dedup {kbs['shared']['store_dedup_ratio']:.1f}x)  "
        f"CR={kbs['cr_shared_over_inline']:.3f}"
    )
    print(
        f"  lifecycle: compacted {kbs['compaction']['dropped_entries']} entries, "
        f"rebased {kbs['compaction']['rebased_containers']} containers; "
        f"decode mismatches={kbs['decode_mismatches']}, "
        f"KB-view mismatches={kbs['kb_view_mismatches']}"
    )
    checks.update(bench_kbstore.validate_claims(kbs))
    # machine-readable perf trajectory for future PRs to diff against; only
    # full-size runs update the repo-root trajectory (quick numbers live in
    # artifacts/bench via save_result and must not clobber the baseline)
    if not args.quick:
        (_REPO_ROOT / "BENCH_throughput.json").write_text(json.dumps(engine, indent=2))
        print(f"  wrote {_REPO_ROOT / 'BENCH_throughput.json'}")

    if not args.skip_roofline:
        print("\n== Roofline (from dry-run artifacts) ==")
        try:
            bench_roofline.run()
        except Exception as e:  # dry-run artifacts may not exist yet
            print(f"  (skipped: {e})")

    print("\n== Paper-claim checks ==")
    ok = True
    for k, v in checks.items():
        status = "PASS" if v.get("pass") else "FAIL"
        ok = ok and v.get("pass", False)
        print(f"  [{status}] {k}: { {kk: vv for kk, vv in v.items() if kk != 'pass'} }")
    print(f"\ntotal bench time: {time.time()-t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
