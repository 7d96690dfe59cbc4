"""Tiny sizes for running the cells on the CPU through the harness's own
code (``run_cell(..., require_tpu=False)``)."""
import io
import json

from bench import run

SEED = 2**31 + 99  # past 32 signed bits, as the benchmark's seeds are

# The fleet entry point (bench/systems/shrink_fleet.py) has no cell yet
# (PERF.md, section 7).  The rehearsal runs it as a cell of its own over the
# TSBS configuration, with the ingest cells' metrics.
FLEET = "tsbs_cpu_fleet4.ingest"
PENDING = [{"name": FLEET, "config": "tsbs_cpu", "traffic": "ingest", "chips": 4}]


def spec() -> dict:
    """BENCHMARK.json with the pending cells added."""
    s = run.load_spec()
    cells = {w["name"] for w in s["workloads"]}
    new = [w for w in PENDING if w["name"] not in cells]
    s["workloads"] += new
    for m in s["end_to_end"] + s["per_layer"]:
        if "tsbs_cpu.ingest" in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [w["name"] for w in new]
    return s


def shrink(cfg: dict, mix: dict, fleet: bool = False) -> None:
    data = cfg["data"]
    data["hosts"] = 8
    cfg["flush"]["flush_samples"] = 80 * 8640 // 4  # quarter-day frames
    if fleet:
        cfg["system"] = "shrink_fleet"
        cfg["flush"] = {"flush_samples": 720, "scope": "series"}
        cfg["fleet"] = {"n_shards": 4, "kb_sync_every": 4}
    if "ingest" in cfg:
        cfg["ingest"].update(pool_samples_per_s=8_000_000, warm_max_samples=400_000,
                             warm_min_seals=2, warm_quiet_seals=1)
    if mix["kind"] == "ingest":
        mix["verify_samples"] = 10**9  # every frame of the window
    else:
        mix.update(warm_min_queries=2, warm_quiet_queries=1, warm_max_queries=4)


def run_tiny(workload: str, trace: bool = False, control: bool = False,
             seconds: float = 1.0, mix: dict | None = None) -> tuple[dict, dict]:
    """(info line, result line) of one tiny run on the CPU; ``mix`` updates
    the traffic mix."""
    def override(c, m):
        shrink(c, m, fleet=workload == FLEET)
        m.update(mix or {})

    out = io.StringIO()
    rc = run.run_cell(spec(), workload, SEED, seconds, trace, control=control,
                      cfg_override=override, require_tpu=False, out=out)
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-2])["info"], json.loads(lines[-1])
