"""Admission: time in the ``submit`` calls that sealed nothing, over the
chunks they admitted, in microseconds."""


def read(run):
    n = run.counters.get("admit_chunks")
    return run.counters["admit_ns"] * 1e-3 / n if n else None
