"""Samples sealed into frames during the window over the window's seconds.
Samples still pending at its close do not count; the window closes with the
first sealing call that ends after ``--seconds``, so it holds whole
flushes."""


def read(run):
    if "samples_sealed" not in run.counters:
        return None
    return run.counters["samples_sealed"] / run.window_s
