"""Roofline shares of the kernels on the codec's path.

Each ``bench/roofline/<kernel>.py`` gives the operations and bytes the
kernel's algorithm needs for the work a run did, counted from the real,
unpadded sizes (samples scanned, symbols encoded), and the peak its
operations run against.  The least time the chip could take is the larger
of operations over that peak and bytes over the memory bandwidth; the share
is that time over the kernel's device time in the trace.
"""
from __future__ import annotations

from bench import by_name


def share(kernel: str, run) -> dict | None:
    """``{"value": %, "bound": "memory" | "compute"}`` for ``kernel``, or
    None where the trace shows no time for it, the run counted no work, or
    the trace lost events (its kernel time no longer covers the work)."""
    if run.trace is None or run.peaks is None or run.trace["dropped_traces"]:
        return None
    t = run.trace["kernel_s"].get(kernel)
    mod = by_name("roofline", kernel)
    work = mod.work(run)
    if not t or not work:
        return None
    ops, nbytes = work
    t_ops = ops / run.peaks[mod.PEAK]
    t_mem = nbytes / run.peaks["hbm_bytes_per_s"]
    return {
        "value": 100.0 * max(t_ops, t_mem) / t,
        "bound": "compute" if t_ops > t_mem else "memory",
    }
