"""Batch compression: bytes of the frames sealed in the window (every tier)
over the samples they hold, from the container's frame directory."""


def read(run):
    n = run.counters.get("samples_sealed")
    return run.counters["frame_bytes"] / n if n and "frame_bytes" in run.counters else None
