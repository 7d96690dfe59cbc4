"""Query operations: one file per ``op`` a traffic mix's query types name.

Each ``bench/ops/<op>.py`` defines ``call(engine, series, t0, t1, arg)``,
the program's answer through ``AnalyticsEngine`` (an ``AggregateAnswer``
with ``lo`` and ``hi``), and ``ref(raw, arg)``, the plain numpy answer on
the generated samples ``raw[t0:t1]``.
"""
