"""A ``ShrinkFleet``: series routed to shards, one per chip, each shard's
flushes on its own chip, and a fleet-global knowledge base rebuilt from the
shards' every ``kb_sync_every`` flushes."""
from __future__ import annotations

from bench.systems import codec_settings

_MASK64 = (1 << 64) - 1


def shard_of(series_id: int, n_shards: int) -> int:
    """The fleet's documented placement, a splitmix64 finaliser of the
    series id modulo the shard count: the routing invariant the check holds
    each shard container to."""
    x = (int(series_id) * 0x9E3779B97F4A7C15) & _MASK64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
    x ^= x >> 31
    return x % n_shards


class FleetSystem:
    def __init__(self, cfg: dict):
        from repro.serving import ShrinkFleet

        shrink, common = codec_settings(cfg)
        self.n_shards = int(cfg["fleet"]["n_shards"])
        self.sut = ShrinkFleet(
            shrink, n_shards=self.n_shards,
            kb_sync_every=cfg["fleet"]["kb_sync_every"], **common,
        )
        self.submit = self.sut.submit

    def seal(self) -> list[bytes]:
        return self.sut.seal()

    def shard_of(self, series_id: int) -> int:
        return shard_of(series_id, self.n_shards)

    def checks(self) -> dict:
        """Every shard container holds only its own series and the entries
        its frames refer to; the global knowledge base is the merge of the
        shards'; on a TPU, each shard's chip ran the shard's kernels."""
        import jax
        from repro.core.streaming import KnowledgeBase

        fleet = self.sut
        bad = sum(
            1 for shard, meta in enumerate(fleet.routing())
            if not meta["self_contained"]
            or any(self.shard_of(sid) != shard for sid in meta["series_ids"])
        )
        merged = KnowledgeBase(fleet.config)
        for b in fleet.batchers:
            merged.merge(b.kb)
        stale = int(merged.snapshot_id() != fleet.global_kb.snapshot_id())
        out = {
            "routing_bad_shards": (bad + len(fleet.shards_down()), 0),
            "kb_sync_stale": (stale, 0),
        }
        if jax.default_backend() == "tpu":
            from repro.kernels.calls import call_counts

            ran = {(p, d) for (_k, p, d), n in call_counts().items() if n}
            idle = {(d.platform, d.id) for d in fleet.plan.devices} - ran
            out["shard_devices_idle"] = (len(idle), 0)
        return out


def build(cfg: dict) -> FleetSystem:
    return FleetSystem(cfg)
