"""Data kinds: one seeded generator per file, named by a configuration's
``data.kind``.

Each ``bench/data/<kind>.py`` defines ``pool_ticks(data, seed, ticks)``,
which returns a ``bench.generate.Pool``, and ``mean_samples_per_tick(data)``.
These are the yardstick's own copies: nothing here imports the program.
"""
