"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

``bench/kernels.json`` says which planes are devices, which of their lines
carry the operations, and which events belong to each kernel.  From one
trace and the measured window this gives, per device:

* busy time: the union of the intervals in which an operation ran;
* each kernel's device time: the summed durations of its events;
* the operations that took most time, and the idle gaps, attributed to the
  benchmark's host span open during each (``bench.admit``, ``bench.flush``,
  ``bench.query.<type>``, ...; what no span covers is ``host.other``).

A device records a bounded number of events: past it, the profiler drops
its trace buffers and says so in the plane's ``dropped_traces`` stat, and
the device's timeline ends with its last recorded operation.  The window
then ends there too, so that what follows does not count as idle.

Host spans come in on the trace's clock: the harness opens one
``bench.window`` annotation around the traced part of the window and passes
its own spans shifted by that annotation's offset.
"""
from __future__ import annotations

import collections
import json
import re
from pathlib import Path

KERNELS = Path(__file__).resolve().parent / "kernels.json"


def load_kernels(path: Path = KERNELS) -> dict:
    with open(path) as f:
        return json.load(f)


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _gaps(busy: list[tuple[int, int]], lo: int, hi: int) -> list[tuple[int, int]]:
    out, cur = [], lo
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return out


def _attribute(gaps, spans) -> collections.Counter:
    """Idle nanoseconds by the host span open during them."""
    spans = sorted(spans, key=lambda s: s[1])
    out: collections.Counter = collections.Counter()
    j = 0
    for a, b in gaps:
        covered = 0
        while j < len(spans) and spans[j][2] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][1] < b:
            name, s0, s1 = spans[k]
            ov = min(b, s1) - max(a, s0)
            if ov > 0:
                out[name] += ov
                covered += ov
            k += 1
        if b - a - covered > 0:
            out["host.other"] += b - a - covered
    return out


def window_of(pd, name: str = "bench.window") -> tuple[int, int] | None:
    """The trace-clock interval of the harness's window annotation."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    return int(ev.start_ns), int(ev.end_ns)
    return None


def _dropped(plane) -> int:
    """Trace buffers the profiler dropped on this device plane."""
    for name, value in getattr(plane, "stats", ()):
        if name == "dropped_traces":
            return int(value)
    return 0


def _device_id(plane_name: str, prefix: str) -> int | None:
    rest = plane_name[len(prefix):] if plane_name.startswith(prefix) else ""
    return int(rest) if rest.isdigit() else None


def reduce(pd, window: tuple[int, int], spans=(), kernels: dict | None = None,
           devices=None) -> dict:
    """Device numbers of the window ``[lo, hi)`` (trace clock, ns) from a
    ``jax.profiler.ProfileData``; ``spans`` are ``(name, start, end)`` host
    spans on the same clock.  ``devices`` are the ids of the chips the cell
    uses (all device planes where None): the others' planes are left out,
    so a chip the cell does not use never counts as idle."""
    kernels = kernels or load_kernels()
    lo, hi = window
    prefix = kernels["device_plane_prefix"]
    keep = None if devices is None else set(devices)
    devices = sorted(
        (p for p in pd.planes
         if _device_id(p.name, prefix) is not None
         and (keep is None or _device_id(p.name, prefix) in keep)),
        key=lambda p: _device_id(p.name, prefix),
    )
    rules = {k: re.compile(v["match"]) for k, v in kernels["kernels"].items()}
    dropped = 0
    for plane in devices:
        if _dropped(plane):
            dropped += _dropped(plane)
            ops = next((ln for ln in plane.lines if ln.name == kernels["ops_line"]), None)
            last = max((int(ev.end_ns) for ev in ops.events), default=lo) if ops else lo
            hi = max(lo, min(hi, last))
    per_device = []
    ops_s: collections.Counter = collections.Counter()
    kernel_s: collections.Counter = collections.Counter()
    idle_by: collections.Counter = collections.Counter()
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get(kernels["ops_line"])
        intervals = []
        for ev in ops.events if ops is not None else ():
            a, b = max(int(ev.start_ns), lo), min(int(ev.end_ns), hi)
            if b > a:
                intervals.append((a, b))
                ops_s[ev.name] += (b - a) * 1e-9
        busy = _union(intervals)
        for name, rule in rules.items():
            line = lines.get(kernels["kernels"][name]["line"])
            for ev in line.events if line is not None else ():
                a, b = max(int(ev.start_ns), lo), min(int(ev.end_ns), hi)
                if b > a and rule.search(ev.name):
                    kernel_s[name] += (b - a) * 1e-9
        idle_by.update(_attribute(_gaps(busy, lo, hi), spans))
        per_device.append({"plane": plane.name, "busy_s": sum(b - a for a, b in busy) * 1e-9})
    n = max(len(per_device), 1)
    return {
        "window_s": (hi - lo) * 1e-9,
        "dropped_traces": dropped,
        "devices": per_device,
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        # kernel time summed over the devices, as the work is
        "kernel_s": dict(kernel_s),
        "device_ops": [[k, v] for k, v in ops_s.most_common(10)],
        "idle_gaps": [[k, v * 1e-9 / n] for k, v in idle_by.most_common(10)],
    }


def reduce_file(path: str, spans=(), kernels: dict | None = None, devices=None) -> dict:
    """Reduce a trace file over its ``bench.window`` annotation."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    window = window_of(pd)
    if window is None:
        raise ValueError(f"{path}: no bench.window annotation")
    return reduce(pd, window, spans, kernels, devices)
