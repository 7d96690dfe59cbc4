"""One ``RaggedBatcher``: a single process seals every series' frames into
one SHRKS container."""
from __future__ import annotations

from bench.systems import codec_settings


class RaggedBatcherSystem:
    def __init__(self, cfg: dict):
        from repro.serving import RaggedBatcher

        shrink, common = codec_settings(cfg)
        self.sut = RaggedBatcher(
            shrink, semantics=cfg["semantics"], scope=cfg["flush"]["scope"], **common
        )
        self.submit = self.sut.submit

    def seal(self) -> list[bytes]:
        return [self.sut.finalize()]

    def shard_of(self, series_id: int) -> int:
        return 0

    def checks(self) -> dict:
        return {}


def build(cfg: dict) -> RaggedBatcherSystem:
    return RaggedBatcherSystem(cfg)
