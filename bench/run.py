#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: its entry
point ``bench/systems/<system>.py``, its settings, its data kind
``bench/data/<kind>.py`` and its limits) and a traffic mix
(``bench/traffic/<mix>.json``, whose ``kind`` selects the load loop
``bench/loops/<kind>.py`` and whose query types name ``bench/ops/<op>.py``).
Every metric is read by ``bench/metrics/<name>.py``.  The run makes its data from
``--seed``, sets up and warms the system (``setup_s``), measures a window of
``--seconds``, seals, and then checks what the window produced against the
generated data.  With ``--trace 1`` the window runs under the profiler (all
of it, or its first ``trace_seconds`` where the mix sets them) and the line
carries the per-layer metrics and the trace's breakdown instead of the
end-to-end ones.

Earlier lines of standard output carry what the metrics do not: compiles in
the window (there should be none), compile-cache entries, the warm-up and
the check's sizes.  The last line is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, optionally
``breakdown``, and ``checks`` last: each compared number with its limit);
the checks are also the last lines of standard error.  Without a TPU, or
with fewer chips than the cell needs, the run prints no result and exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# where JAX_COMPILATION_CACHE_DIR says, else a fixed directory of the
# checkout: the path is part of the cache key
CACHE_DIR = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or ROOT / ".jax_cache")
TRACE_DIR = ROOT / ".bench_trace"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_MISS = "/jax/compilation_cache/cache_misses"

for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import by_name  # noqa: E402


class Spans:
    """The benchmark's own host spans, ``(name, start_ns, end_ns)`` on
    ``time.perf_counter_ns``, kept in memory.  ``on_add(end_ns)``, where
    set, is called after each span is added."""

    def __init__(self):
        self.items: list[tuple[str, int, int]] = []
        self.on_add = None

    def add(self, name: str, start: int, end: int) -> None:
        self.items.append((name, start, end))
        if self.on_add is not None:
            self.on_add(end)

    @contextlib.contextmanager
    def span(self, name: str):
        a = time.perf_counter_ns()
        try:
            yield
        finally:
            self.add(name, a, time.perf_counter_ns())

    def count(self, name: str) -> int:
        return sum(1 for s in self.items if s[0] == name)


class CompileWatch:
    """Counts XLA compiles (fresh or loaded from the persistent cache) and
    persistent-cache misses, from JAX's monitoring events."""

    def __init__(self):
        self.compiles = 0
        self.misses = 0

    def on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def on_event(self, event: str, **_kw) -> None:
        if event == CACHE_MISS:
            self.misses += 1


class WindowTrace:
    """The profiler over the window, or over its first ``limit_s`` seconds
    where the traffic mix sets ``trace_seconds``: then the trace stops
    after the first span that ends past them.  Stopping the profiler costs
    time for every device event recorded, so a mix whose device work is
    many small operations bounds it, and a traced run stays short however
    fast the program gets.  The ``bench.window`` annotation spans exactly
    the traced part and puts the harness's clock on the trace's."""

    def __init__(self, directory: Path, limit_s: float | None):
        import jax

        self.jax, self.directory, self.limit_s = jax, directory, limit_s
        self.end = None
        shutil.rmtree(directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1  # the benchmark's annotation, not every host op
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(directory), profiler_options=opts)
        self.annotation = jax.profiler.TraceAnnotation("bench.window")
        self.annotation.__enter__()
        self.w0 = time.perf_counter_ns()

    def on_add(self, end_ns: int) -> None:
        if self.limit_s is not None and end_ns - self.w0 >= self.limit_s * 1e9:
            self.stop(end_ns)

    def stop(self, end_ns: int) -> None:
        if self.end is not None:
            return
        self.annotation.__exit__(None, None, None)
        self.end = end_ns
        t = time.perf_counter()
        self.jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(spec: dict, workload: str, root: Path = ROOT) -> tuple[dict, dict, dict]:
    """The cell's entry, its configuration and its traffic mix."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    with open(root / conf["file"]) as f:
        cfg = json.load(f)
    with open(BENCH / "traffic" / f"{cell['traffic']}.json") as f:
        mix = json.load(f)
    return cell, cfg, mix


def cell_metrics(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: end to end, or per layer when traced."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def read_metric(name: str, run) -> dict | None:
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", BENCH / "metrics" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(run)
    if value is None:
        return None
    return dict(value) if isinstance(value, dict) else {"value": float(value)}


def _cache_entries() -> int:
    return sum(1 for _ in CACHE_DIR.iterdir()) if CACHE_DIR.is_dir() else 0


def _trace_file(directory: Path) -> Path:
    found = sorted(directory.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"the profiler wrote no trace under {directory}")
    return found[-1]


def run_cell(
    spec: dict,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    control: bool = False,
    cfg_override=None,
    require_tpu: bool = True,
    out=sys.stdout,
) -> int:
    """Run one cell once; prints the lines and returns the exit code.
    ``cfg_override(cfg, mix)`` and ``require_tpu=False`` serve the CPU
    rehearsal, which runs tiny sizes through this same code."""
    import jax

    cell, cfg, mix = load_cell(spec, workload)
    if cfg_override is not None:
        cfg_override(cfg, mix)
    if require_tpu:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    dev0 = devices[0]
    if require_tpu and dev0.platform != "tpu":
        print(f"bench: no TPU; JAX found {dev0.platform} ({dev0.device_kind})", file=sys.stderr)
        return 3
    if require_tpu and len(devices) < int(cell["chips"]):
        print(f"bench: {workload} needs {cell['chips']} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 3
    with open(BENCH / "peaks.json") as f:
        peaks_by_kind = json.load(f)
    peaks = peaks_by_kind.get(dev0.device_kind)
    if require_tpu and peaks is None:
        print(f"bench: no peaks for device kind {dev0.device_kind!r} in peaks.json",
              file=sys.stderr)
        return 2
    used = devices[: int(cell["chips"])]

    watch = CompileWatch()
    jax.monitoring.register_event_duration_secs_listener(watch.on_duration)
    jax.monitoring.register_event_listener(watch.on_event)
    try:
        return _run(spec, workload, cell, cfg, mix, seed, seconds, trace, control,
                    devices, used, peaks, watch, out)
    finally:
        jax.monitoring.unregister_event_duration_listener(watch.on_duration)
        jax.monitoring.unregister_event_listener(watch.on_event)


def _run(spec, workload, cell, cfg, mix, seed, seconds, trace, control,
         devices, used, peaks, watch, out) -> int:
    dev0 = devices[0]
    entries0 = _cache_entries()
    loop = by_name("loops", mix["kind"])
    c = loop.Cell(cfg, mix, seed, seconds, watch)
    spans = Spans()
    c.setup(spans)
    setup_s = time.perf_counter() - T_START

    compiles0, misses0 = watch.compiles, watch.misses
    trace_dir = TRACE_DIR / workload
    gc0 = [g["collections"] for g in gc.get_stats()]
    if trace:
        wt = WindowTrace(trace_dir, mix.get("trace_seconds"))
        spans.on_add = wt.on_add
    window_s = c.window(spans)
    gc_in_window = [g["collections"] - n for g, n in zip(gc.get_stats(), gc0)]
    if trace:
        spans.on_add = None
        wt.stop(time.perf_counter_ns())
    compiles = watch.compiles - compiles0
    misses = watch.misses - misses0
    c.close()
    memory_peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used))

    t_check = time.perf_counter()
    checks, attempted, failed = c.verify(control, trace)
    check_s = time.perf_counter() - t_check

    reduced = None
    if trace:
        from bench import trace_reduce
        from jax.profiler import ProfileData

        t0 = time.perf_counter()
        tfile = _trace_file(trace_dir)
        trace_bytes = tfile.stat().st_size
        pd = ProfileData.from_file(str(tfile))
        win = trace_reduce.window_of(pd)
        shift = win[0] - wt.w0
        host = [(n, a + shift, b + shift) for n, a, b in spans.items
                if b > wt.w0 and a < wt.end]
        reduced = trace_reduce.reduce(pd, win, host, devices=[d.id for d in used])
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_cost = {"traced_s": (wt.end - wt.w0) * 1e-9, "stop_s": wt.stop_s,
                      "reduce_s": time.perf_counter() - t0, "trace_bytes": trace_bytes}

    run = types.SimpleNamespace(
        setup_s=setup_s, window_s=window_s, counters=c.counters, trace=reduced,
        peaks=peaks,
    )
    metrics = {}
    for m in cell_metrics(spec, workload, trace):
        value = read_metric(m["name"], run)
        if value is not None:
            value["unit"] = m["unit"]
            metrics[m["name"]] = value
    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(memory_peak),
    }
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    info = {
        "workload": workload, "seed": seed, "seconds": seconds, "trace": bool(trace),
        "control": bool(control), "window_s": window_s, "setup_s": setup_s,
        "check_s": check_s,
        "compiles_in_window": compiles, "cache_misses_in_window": misses,
        "gc_collections_in_window": gc_in_window,
        "cache_entries_at_start": entries0, "cache_entries_at_end": _cache_entries(),
        "spans": {k: spans.count(k) for k in sorted({s[0] for s in spans.items})},
        "counters": {k: v for k, v in c.counters.items() if not isinstance(v, list)},
        **c.info,
    }
    if reduced is not None:
        info["kernel_s"] = reduced["kernel_s"]
        info["trace_cost"] = trace_cost
        info["dropped_traces"] = reduced["dropped_traces"]
        info["busy_s_by_device"] = [d["busy_s"] for d in reduced["devices"]]
    print(json.dumps({"info": info}), file=out, flush=True)
    correct = all(value <= limit for value, limit in checks.values())
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        line["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    line["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    print(json.dumps(line), file=out, flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="check the reference in float32 in the program's place "
                         "(the control of the check; the benchmark's runs never set it)")
    args = ap.parse_args(argv)
    # libtpu otherwise logs to a fixed directory under /tmp, outside the run's own
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    try:
        spec = load_spec()
        load_cell(spec, args.workload)
    except (OSError, KeyError, StopIteration, ValueError) as e:
        print(f"bench: {e!r}", file=sys.stderr)
        return 2
    return run_cell(spec, args.workload, args.seed, args.seconds, bool(args.trace),
                    control=bool(args.control))


if __name__ == "__main__":
    raise SystemExit(main())
