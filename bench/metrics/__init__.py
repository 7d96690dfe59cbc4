"""One reader per metric, found by the metric's name in ``BENCHMARK.json``.

Each ``bench/metrics/<name>.py`` defines ``read(run)``, which returns the
metric's value (a number, or a dict with ``"value"`` and further keys) from
the run's spans, counters and trace reduction, or ``None`` where it finds
nothing to read; the harness then leaves the metric out of the line.
"""
