"""Record the small chip trace that ``test_trace_reduce.test_recorded_chip_trace``
reduces: one day-long flush of 8 TSBS hosts (80 series of 8,640 samples)
through ``RaggedBatcher`` on a TPU, traced the way a ``--trace 1`` run traces
its window.  Run it on a machine with a TPU, from the root of the checkout:

    python3 bench/tests/record_trace.py

It warms the flush's shapes with a first day, traces the second, and writes
``bench/tests/data/tsbs_flush.xplane.pb``.
"""
from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import by_name, generate, run  # noqa: E402

OUT = ROOT / "bench" / "tests" / "data" / "tsbs_flush.xplane.pb"
HOSTS = 8


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 3
    spec = run.load_spec()
    _, cfg, _ = run.load_cell(spec, "tsbs_cpu.ingest")
    cfg["data"]["hosts"] = HOSTS
    day = 86400 // int(cfg["data"]["interval_s"])
    cfg["flush"]["flush_samples"] = HOSTS * len(cfg["data"]["fields"]) * day
    ticks = 86400 // int(cfg["data"]["deliver_every_s"])
    pool = generate.pool_ticks(cfg["data"], 1, 2 * ticks)
    system = by_name("systems", cfg["system"]).build(cfg)

    def submit_day(first_tick: int) -> int:
        sealed = 0
        for k in range(first_tick, first_tick + ticks):
            for sid, chunk in pool.tick(k):
                sealed += len(system.submit(sid, chunk))
        return sealed

    assert submit_day(0), "the warm-up day sealed nothing"
    trace_dir = run.TRACE_DIR / "record"
    wt = run.WindowTrace(trace_dir, None)
    assert submit_day(ticks), "the traced day sealed nothing"
    wt.stop(time.perf_counter_ns())
    OUT.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(run._trace_file(trace_dir), OUT)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"record_trace: {OUT} ({OUT.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
