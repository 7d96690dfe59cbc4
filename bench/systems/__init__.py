"""Systems under test: one file per entry point, named by a configuration's
``system``.

Each ``bench/systems/<system>.py`` defines ``build(cfg)``, which builds the
entry point with the configuration's own settings and returns an object
with ``submit(series, chunk)`` (the frames that call sealed), ``seal()``
(the containers, one per shard), ``shard_of(series)`` and ``checks()``
(the entry point's own guarantees beyond the frames: name to
``(value, limit)``).
"""


def codec_settings(cfg: dict) -> tuple[object, dict]:
    """The ``ShrinkConfig`` and the codec keywords every entry point takes."""
    from repro.core import ShrinkConfig

    return ShrinkConfig(**cfg["shrink"]), dict(
        eps_targets=list(cfg["tiers"]),
        decimals=cfg["decimals"],
        backend=cfg["backend"],
        flush_samples=cfg["flush"]["flush_samples"],
    )
