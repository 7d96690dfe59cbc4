"""jit-ready train / serve steps with sharding.

Two train-step flavours:

* ``make_train_step``            — global-batch loss; GSPMD inserts the
  gradient all-reduce over ("pod","data").  The baseline.
* ``make_compressed_train_step`` — shard_map manual over "pod": each pod
  computes local gradients, the cross-pod reduction rides the SHRINK
  compressed collective (grad_compress.py), with error feedback carried in
  the step state.  Only for pod-replicated params (dcn_fsdp=False).

Both return functions ready for jax.jit with in/out shardings derived from
partition.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeSpec
from ..models import Model
from ..parallel.partition import param_specs, fsdp_axes_for
from ..parallel.sharding import AxisRules, axis_rules, make_rules
from .optimizer import AdamWConfig, adamw_init, adamw_update, clip_by_global_norm
from .grad_compress import GradCompressConfig, compressed_psum_tree

__all__ = [
    "make_train_step",
    "make_compressed_train_step",
    "make_prefill_step",
    "make_decode_step",
    "batch_specs",
    "cache_specs",
    "train_state_specs",
]


# ---------------------------------------------------------------- spec maps
def batch_specs(batch_tree, mesh: Mesh, batch_axes) -> Any:
    """Shard dim0 of every batch leaf over the batch axes (if divisible)."""
    size = 1
    for a in (batch_axes if isinstance(batch_axes, tuple) else (batch_axes,)):
        size *= mesh.shape[a]

    def spec(leaf):
        b = leaf.shape[0] if leaf.ndim else 0
        if leaf.ndim == 0 or b % size:
            return P()
        return P(batch_axes, *([None] * (leaf.ndim - 1)))

    return jax.tree.map(spec, batch_tree)


def cache_specs(cache_tree, mesh: Mesh, batch_axes, seq_axis: str = "model") -> Any:
    """KV caches: batch over the data axes, cache SEQUENCE over the model
    axis (flash-decoding layout: decode scores/AV reduce over the sharded
    sequence with tiny per-step collectives, and per-device cache memory is
    S/16 — always divisible, unlike kv-head counts).  Recurrent states shard
    their width/head dims over model."""
    bsz = 1
    for a in (batch_axes if isinstance(batch_axes, tuple) else (batch_axes,)):
        bsz *= mesh.shape[a]
    msz = mesh.shape.get("model", 1)
    # canonicalize ("data",) -> "data": new jax normalizes singleton spec
    # entries itself, 0.4.x keeps the tuple and the specs stop comparing equal
    if isinstance(batch_axes, tuple) and len(batch_axes) == 1:
        batch_axes = batch_axes[0]

    def spec(path, leaf):
        name = None
        for entry in reversed(path):
            k = getattr(entry, "name", None) or getattr(entry, "key", None)
            if isinstance(k, str):
                name = k
                break
        stacked = 0
        base = []
        batch = batch_axes if (leaf.ndim and leaf.shape[0] % bsz == 0) else None
        b2 = (
            batch_axes
            if (leaf.ndim > 1 and leaf.shape[1] % bsz == 0)
            else None
        )

        def seq_ok(dim_size):
            return seq_axis if dim_size % msz == 0 else None

        if name in ("k", "v") and leaf.ndim == 4:  # [B, S, KV, D]
            return P(batch, seq_ok(leaf.shape[1]), None, None)
        if name in ("k", "v") and leaf.ndim == 5:  # stacked [G, B, S, KV, D]
            return P(None, b2, seq_ok(leaf.shape[2]), None, None)
        if name == "kpos" and leaf.ndim == 2:
            return P(batch, seq_ok(leaf.shape[1]))
        if name == "kpos" and leaf.ndim == 3:
            return P(None, b2, seq_ok(leaf.shape[2]))
        if name in ("c_kv", "k_rope") and leaf.ndim == 3:  # [B, S, R]
            return P(batch, seq_ok(leaf.shape[1]), None)
        if name in ("c_kv", "k_rope") and leaf.ndim == 4:
            return P(None, b2, seq_ok(leaf.shape[2]), None)
        if name == "wkv" and leaf.ndim == 4:  # [B, H, K, V]
            return P(batch, "model" if leaf.shape[1] % msz == 0 else None, None, None)
        if name == "wkv" and leaf.ndim == 5:
            return P(None, b2, "model" if leaf.shape[2] % msz == 0 else None, None, None)
        if name == "h" and leaf.ndim == 2:
            return P(batch, "model" if leaf.shape[1] % msz == 0 else None)
        if name == "h" and leaf.ndim == 3:
            return P(None, b2, "model" if leaf.shape[2] % msz == 0 else None)
        if name == "conv" and leaf.ndim == 3:
            return P(batch, None, "model" if leaf.shape[2] % msz == 0 else None)
        if name == "conv" and leaf.ndim == 4:
            return P(None, b2, None, "model" if leaf.shape[3] % msz == 0 else None)
        if name in ("shift_t", "shift_c") and leaf.ndim == 2:
            return P(batch, None)
        if name in ("shift_t", "shift_c") and leaf.ndim == 3:
            return P(None, b2, None)
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec, cache_tree)


def train_state_specs(params_shapes, cfg: ModelConfig, mesh: Mesh):
    ps = param_specs(params_shapes, cfg, mesh)
    return {
        "m": ps,
        "v": ps,
        "step": P(),
    }


# ------------------------------------------------------------- train steps
def make_train_step(model: Model, mesh: Mesh, opt_cfg: AdamWConfig = AdamWConfig()):
    cfg = model.cfg
    rules = make_rules(mesh, cfg)

    def step(params, opt_state, batch):
        def loss_fn(p):
            with axis_rules(rules):
                return model.loss(p, batch)

        (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads, gnorm = clip_by_global_norm(grads, opt_cfg.grad_clip)
        params, opt_state = adamw_update(opt_cfg, params, grads, opt_state)
        metrics = {"loss": loss, "grad_norm": gnorm, **parts}
        return params, opt_state, metrics

    return step


def make_compressed_train_step(
    model: Model,
    mesh: Mesh,
    opt_cfg: AdamWConfig = AdamWConfig(),
    comp_cfg: GradCompressConfig = GradCompressConfig(),
):
    """Cross-pod SHRINK-compressed data parallelism (DESIGN.md §6)."""
    cfg = model.cfg
    assert "pod" in mesh.axis_names, "compressed step needs a pod axis"
    assert not cfg.dcn_fsdp, "compressed collective targets pod-replicated params"
    # inside shard_map the pod axis is manual: batch rides ("data",) only
    rules = make_rules(mesh, cfg, overrides={"batch": "data"})

    def pod_step(params, opt_state, ef, batch):
        def loss_fn(p):
            with axis_rules(rules):
                return model.loss(p, batch)  # mean over the POD-LOCAL batch

        (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads, ef = compressed_psum_tree(grads, ef, comp_cfg)
        grads, gnorm = clip_by_global_norm(grads, opt_cfg.grad_clip)
        params, opt_state = adamw_update(opt_cfg, params, grads, opt_state)
        n = jax.lax.psum(1, comp_cfg.axis)
        # every metric must be pod-replicated to satisfy out_specs P()
        metrics = {"loss": loss, "grad_norm": gnorm, **parts}
        metrics = jax.tree.map(lambda v: jax.lax.psum(v, comp_cfg.axis) / n, metrics)
        return params, opt_state, ef, metrics

    def batch_in_specs(batch):
        return jax.tree.map(lambda _: P("pod"), batch)

    def step(params, opt_state, ef, batch):
        fn = jax.shard_map(
            pod_step,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: P(), params),
                jax.tree.map(lambda _: P(), opt_state),
                jax.tree.map(lambda _: P(), ef),
                batch_in_specs(batch),
            ),
            out_specs=(
                jax.tree.map(lambda _: P(), params),
                jax.tree.map(lambda _: P(), opt_state),
                jax.tree.map(lambda _: P(), ef),
                P(),
            ),
            axis_names={"pod"},
            check_vma=False,
        )
        return fn(params, opt_state, ef, batch)

    return step


def make_ef_state(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


# ------------------------------------------------------------- serve steps
def make_prefill_step(model: Model, mesh: Mesh):
    rules = make_rules(mesh, model.cfg)

    def step(params, batch):
        with axis_rules(rules):
            return model.prefill(params, batch)

    return step


def make_decode_step(model: Model, mesh: Mesh):
    # seq_model: decode attention runs against sequence-sharded caches
    rules = make_rules(mesh, model.cfg, overrides={"seq_model": "model"})

    def step(params, tokens, caches, cache_index):
        with axis_rules(rules):
            return model.decode_step(params, tokens, caches, cache_index)

    return step
