"""Fleet partitioning: deterministic series->shard placement over a
device mesh.

The serving fleet (``repro.serving.fleet``) is a data-parallel system:
each shard owns a disjoint set of series end to end (ingest batcher,
container, gateway, analytics engine), so the only cross-shard traffic is
the periodic knowledge-base sync.  This module supplies the placement
math, kept separate from the serving logic so tests can drive ANY
assignment (the cross-shard differential suites quantify over it):

* :func:`shard_of` — the default stable hash (splitmix64 finalizer) from
  series id to shard.  Consecutive ids land on different shards, so the
  common "sensor 0..N-1" numbering balances without coordination.
* :class:`FleetPlan` — the frozen topology: shard count, the mesh the
  fleet runs over (built with ``launch.mesh.make_local_mesh`` over the
  process' devices, "data" axis = fleet parallelism), the shard->device
  placement, and the assignment function actually in force.
* :func:`plan_fleet` — build a plan; ``assignment`` overrides the hash
  with an explicit ``{series_id: shard}`` map (unknown ids fall back to
  the hash) or any callable.

``ShrinkFleet`` runs each shard's flushes and decodes on that shard's
device (``ShrinkFleet.device_scope``).  On a single device every shard
maps to device 0.  Either way one process drives the shards one after
another, which is why the fleet benchmark models aggregate throughput as
the critical path over per-shard busy time (see docs/fleet.md).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Optional, Union

import jax

from ..launch.mesh import make_local_mesh

__all__ = ["FleetPlan", "plan_fleet", "shard_of"]

_MASK64 = (1 << 64) - 1


def shard_of(series_id: int, n_shards: int) -> int:
    """Deterministic, stable series->shard hash (splitmix64 finalizer):
    uniform over shards, independent of process/interpreter state, and
    identical across every node that routes for the fleet."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    x = (int(series_id) * 0x9E3779B97F4A7C15) & _MASK64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
    return (x ^ (x >> 31)) % n_shards


@dataclasses.dataclass(frozen=True)
class FleetPlan:
    """Frozen fleet topology: who routes where, on which device."""

    n_shards: int
    mesh: object  # jax Mesh with a "data" axis = fleet parallelism
    devices: tuple  # shard i runs on devices[i]
    assign: Callable[[int], int]  # series_id -> shard

    def shard_of(self, series_id: int) -> int:
        s = int(self.assign(int(series_id)))
        if not 0 <= s < self.n_shards:
            raise ValueError(
                f"assignment sent series {series_id} to shard {s} "
                f"outside [0, {self.n_shards})"
            )
        return s

    def device_of(self, shard: int) -> object:
        return self.devices[shard]

    def describe(self) -> dict:
        return {
            "n_shards": self.n_shards,
            "mesh_devices": int(len(self.mesh.devices.flat)),
            "devices": [str(d) for d in self.devices],
        }


def plan_fleet(
    n_shards: int,
    assignment: Optional[Union[Mapping[int, int], Callable[[int], int]]] = None,
) -> FleetPlan:
    """Build the fleet topology: a local mesh whose "data" axis spans the
    process' devices, shard->device placement (round-robin when shards
    outnumber devices — the single-host regime), and the series->shard
    assignment (default: :func:`shard_of`; a mapping overrides specific
    ids and falls back to the hash for the rest)."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    n_dev = jax.device_count()
    mesh = make_local_mesh(data=min(n_shards, n_dev), model=1)
    mesh_devs = list(mesh.devices.flat)
    devices = tuple(mesh_devs[i % len(mesh_devs)] for i in range(n_shards))
    if assignment is None:
        assign = lambda sid: shard_of(sid, n_shards)  # noqa: E731
    elif callable(assignment):
        assign = assignment
    else:
        table = {int(k): int(v) for k, v in assignment.items()}
        assign = lambda sid: table.get(sid, shard_of(sid, n_shards))  # noqa: E731
    return FleetPlan(n_shards=n_shards, mesh=mesh, devices=devices, assign=assign)
