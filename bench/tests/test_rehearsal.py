"""Every cell at a tiny size on the CPU, through the same harness code the
chip runs, and the harness's refusals."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run
from bench.tests import tiny
from bench.tests.tiny import run_tiny

ROOT = Path(__file__).resolve().parents[2]
SPEC = run.load_spec()
CELLS = [w["name"] for w in tiny.spec()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_reports(workload, trace):
    info, line = run_tiny(workload, trace=trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert info["compiles_in_window"] == 0
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    spec = tiny.spec()
    want = {m["name"] for m in run.cell_metrics(spec, workload, trace)}
    assert want
    if trace:
        assert {"busy_s", "window_s"} <= set(dev) and "breakdown" in line
        # no device on the CPU: the device-trace metrics find nothing to read
        host_read = {m["name"] for m in run.cell_metrics(spec, workload, trace)
                     if m["source"] != "device_trace"}
        assert set(line["metrics"]) == host_read
    else:
        assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]


def test_trace_stops_after_trace_seconds():
    """A mix's ``trace_seconds`` bounds the traced part; the window runs on."""
    info, line = run_tiny("tsbs_cpu.query", trace=True, seconds=2.0,
                          mix={"trace_seconds": 0.5})
    assert line["correct"] is True
    traced = info["trace_cost"]["traced_s"]
    assert 0.5 <= traced < info["window_s"]
    assert line["device"]["window_s"] == pytest.approx(traced, rel=0.05)
    assert "frame_miss_pct" in line["metrics"]


def _bench(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_no_tpu_no_result():
    p = _bench(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"], tmp_path)
    assert p.returncode != 0 and "metrics" not in p.stdout


def test_unknown_workload_is_refused():
    p = _bench(["--workload", "no.such_cell", "--seed", "1", "--seconds", "1"], ROOT)
    assert p.returncode == 2 and not p.stdout


def test_benchmark_json_names_a_file_for_everything():
    bench = ROOT / "bench"
    for c in SPEC["configs"]:
        with open(ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert (bench / "data" / f"{cfg['data']['kind']}.py").is_file()
        assert (bench / "systems" / f"{cfg['system']}.py").is_file()
    for w in SPEC["workloads"]:
        with open(bench / "traffic" / f"{w['traffic']}.json") as f:
            mix = json.load(f)
        assert (bench / "loops" / f"{mix['kind']}.py").is_file()
        for qt in mix.get("types", ()):
            assert (bench / "ops" / f"{qt['op']}.py").is_file()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
