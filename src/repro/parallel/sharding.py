"""Logical-axis sharding: models annotate activations with logical names;
a mesh-specific rule set maps names to mesh axes.  Outside a rules context
the annotations are no-ops, so the same model code runs in CPU smoke tests
(1 device) and in the 512-device dry-run.

Logical activation axes:
    batch     -> ("pod", "data") on the multi-pod mesh, ("data",) single-pod
    heads     -> "model"
    kv_heads  -> "model"   (pads when kv < 16; see DESIGN.md §5 + §Perf)
    ffn       -> "model"
    experts   -> "model"   (expert parallelism)
    vocab     -> "model"
    seq_model -> "model"   (sequence parallelism, hillclimb lever)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "AxisRules",
    "abstract_mesh",
    "axis_rules",
    "current_rules",
    "shard",
    "make_rules",
]

_STATE = threading.local()


class AxisRules:
    def __init__(self, mesh: Mesh, mapping: dict[str, object]):
        self.mesh = mesh
        self.mapping = dict(mapping)

    def resolve(self, name: Optional[str]):
        if name is None:
            return None
        return self.mapping.get(name)

    def spec(self, *names) -> P:
        return P(*[self.resolve(n) for n in names])


def make_rules(mesh: Mesh, cfg=None, overrides: Optional[dict] = None) -> AxisRules:
    """cfg (a ModelConfig) gates head axes by divisibility: forcing 8 kv
    heads onto a 16-way axis makes GSPMD fall back to 'involuntary full
    rematerialization' (replicate + repartition) per layer — replicating
    the small KV activations instead is strictly cheaper."""
    axes = mesh.axis_names
    batch = tuple(a for a in ("pod", "data") if a in axes) or None
    fsdp = "data" if "data" in axes else None
    msize = mesh.shape.get("model", 1) if "model" in axes else 1

    def fits(n: Optional[int]) -> Optional[str]:
        if "model" not in axes:
            return None
        if cfg is None or n is None:
            return "model"
        return "model" if (n % msize == 0) else None

    n_heads = getattr(cfg, "n_heads", None)
    n_kv = getattr(cfg, "n_kv_heads", None)
    force = bool(getattr(cfg, "force_head_sharding", False))
    mapping = {
        "batch": batch,
        "heads": ("model" if ("model" in axes and force) else fits(n_heads)),
        "kv_heads": fits(n_kv),
        "ffn": "model" if "model" in axes else None,
        "experts": "model" if "model" in axes else None,
        "vocab": "model" if "model" in axes else None,
        "seq_model": None,  # flipped to "model" by the sequence-parallel lever
        "fsdp": fsdp,
    }
    if overrides:
        mapping.update(overrides)
    return AxisRules(mesh, mapping)


def current_rules() -> Optional[AxisRules]:
    return getattr(_STATE, "rules", None)


@contextlib.contextmanager
def axis_rules(rules: Optional[AxisRules]):
    prev = current_rules()
    _STATE.rules = rules
    try:
        yield
    finally:
        _STATE.rules = prev


def shard(x: jax.Array, *names) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names (None = unsheared
    dim).  No-op when no rules are active (CPU smoke tests).  Inside a
    shard_map region (Manual axes) the constraint must be spec-only so it
    canonicalizes against the context AbstractMesh."""
    rules = current_rules()
    if rules is None:
        return x
    if len(names) != x.ndim:
        raise ValueError(f"shard(): {len(names)} names for rank-{x.ndim} array")
    spec = rules.spec(*names)
    am = jax.sharding.get_abstract_mesh()
    if am is not None and not am.empty:
        return jax.lax.with_sharding_constraint(x, spec)
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))


def abstract_mesh(axis_sizes, axis_names):
    """An ``AbstractMesh`` with ``Auto`` axes, so logical-axis constraints
    apply inside it as they do on a concrete mesh."""
    return jax.sharding.AbstractMesh(
        tuple(axis_sizes),
        tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names),
    )
