"""Batch compression: time in the ``submit`` calls that sealed frames (the
flush, the frame seal and, in a fleet, its knowledge-base sync) over the
millions of samples they sealed."""


def read(run):
    n = run.counters.get("samples_sealed")
    return run.counters["flush_ns"] * 1e-9 / (n * 1e-6) if n else None
