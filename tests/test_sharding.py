"""Distribution-layer units: partition specs, divisibility-gated rules,
batch/cache spec fallbacks, compressed-exchange math on a real (tiny) mesh,
and the documented XLA partitioner-bug workaround."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import ARCHS, get_config, reduced_config
from repro.models import build_model
from repro.parallel.partition import fsdp_axes_for, param_specs
from repro.parallel.sharding import abstract_mesh, make_rules


def _fake_mesh_16x16():
    # AbstractMesh: lets us build 256-device specs without devices (the
    # repro-side helper papers over the 0.4.x/0.5+ constructor drift)
    return abstract_mesh((16, 16), ("data", "model"))


def test_param_specs_cover_all_archs():
    mesh = _fake_mesh_16x16()
    for name, cfg in ARCHS.items():
        model = build_model(cfg)
        shapes = model.init_shapes()
        specs = param_specs(shapes, cfg, mesh)
        for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(shapes)[0],
            jax.tree_util.tree_flatten_with_path(specs, is_leaf=lambda x: isinstance(x, P))[0],
        ):
            assert len(spec) <= leaf.ndim, f"{name}: spec rank > leaf rank at {path}"
            # every sharded dim must divide by its axis size
            for dim, axis in enumerate(spec):
                if axis is None:
                    continue
                axes = axis if isinstance(axis, tuple) else (axis,)
                total = 1
                for a in axes:
                    total *= dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
                assert leaf.shape[dim] % total == 0, (
                    f"{name}: {path} dim{dim}={leaf.shape[dim]} not divisible by {axis}={total}"
                )


def test_rules_gate_heads_by_divisibility():
    mesh = _fake_mesh_16x16()
    llama3 = make_rules(mesh, get_config("llama3-8b"))
    assert llama3.resolve("heads") == "model"  # 32 % 16 == 0
    assert llama3.resolve("kv_heads") is None  # 8 % 16 != 0 -> replicate
    llama4 = get_config("llama4-maverick-400b-a17b")
    rules4 = make_rules(mesh, llama4)
    # 40 % 16 != 0, but the config opts into GSPMD-padded head sharding
    # (EXPERIMENTS.md §Perf-extended); without the flag it replicates.
    assert rules4.resolve("heads") == ("model" if llama4.force_head_sharding else None)
    import dataclasses
    no_force = dataclasses.replace(llama4, force_head_sharding=False)
    assert make_rules(mesh, no_force).resolve("heads") is None
    assert rules4.resolve("experts") == "model"


def test_moe_expert_specs_distinct_from_stacked_mlp():
    mesh = _fake_mesh_16x16()
    cfg = get_config("llama4-maverick-400b-a17b")
    shapes = build_model(cfg).init_shapes()
    specs = param_specs(shapes, cfg, mesh)
    moe_wd = specs["groups"]["pos1"]["moe"]["wd"]
    assert moe_wd[1] == "model", "expert dim must be expert-parallel"
    mlp_wd = specs["groups"]["pos0"]["mlp"]["wd"]
    assert mlp_wd == P(None, "model", "data"), f"stacked mlp wd got {mlp_wd}"


def test_fsdp_axes_respects_dcn_flag():
    mesh3 = abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    assert fsdp_axes_for(get_config("llama3-8b"), mesh3) == "data"
    assert fsdp_axes_for(get_config("llama4-maverick-400b-a17b"), mesh3) == ("pod", "data")


def test_vocab_dim_sharded_workaround():
    """The compressed path re-lays the embedding (None, d-sharded) — the
    vocab-sharded-gather partitioner crash workaround (DESIGN.md §6)."""
    mesh = _fake_mesh_16x16()
    cfg = get_config("qwen3-0.6b")
    shapes = build_model(cfg).init_shapes()
    s_default = param_specs(shapes, cfg, mesh)["embed"]
    s_comp = param_specs(shapes, cfg, mesh, vocab_dim_sharded=False)["embed"]
    assert s_default[0] == "model"
    assert s_comp[0] is None and s_comp[1] is not None


def test_compressed_exchange_math_single_device():
    """End-to-end exchange on a (1,1,1) mesh: compression must reduce to a
    (near-)identity mean when both pods agree, and the error-feedback must
    capture the quantization residue."""
    from repro.training.grad_compress import GradCompressConfig, make_crosspod_exchange

    mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)}
    gs = {"w": g["w"][None]}  # one pod
    ef = {"w": jnp.zeros((512, 256), jnp.float32)}
    spec = {"w": P(None, None)}
    fn = jax.jit(make_crosspod_exchange(mesh, GradCompressConfig(min_leaf_size=0), spec))
    out, new_ef = fn(gs, ef)
    err = np.abs(np.asarray(out["w"]) - np.asarray(g["w"]))
    scale = np.abs(np.asarray(g["w"])).max()
    assert err.max() < 0.05 * scale  # int8 residual quantization error
    # error feedback == what compression lost
    np.testing.assert_allclose(
        np.asarray(new_ef["w"]), np.asarray(g["w"]) - np.asarray(out["w"]), atol=1e-5
    )


def test_batch_specs_fallback_nondivisible():
    from repro.training.train_step import batch_specs

    mesh = _fake_mesh_16x16()
    batch = {"tokens": jax.ShapeDtypeStruct((1, 128), jnp.int32)}  # long_500k: B=1
    specs = batch_specs(batch, mesh, ("data",))
    assert specs["tokens"] == P()  # replicate instead of padding 1 -> 16


def test_cache_specs_seq_sharding():
    from repro.training.train_step import cache_specs

    mesh = _fake_mesh_16x16()
    cfg = reduced_config(ARCHS["llama3-8b"])
    model = build_model(cfg)
    caches = jax.eval_shape(lambda: model.make_decode_caches(16, 4096))
    specs = cache_specs(caches, mesh, ("data",))
    k_spec = specs["groups"]["pos0"]["self"].k
    assert k_spec == P(None, "data", "model", None, None)  # [G, B, S, KV, D]


def test_moe_ep_matches_dense_path():
    """The expert-parallel shard_map MoE must reproduce the dense
    scatter-dispatch outputs on a 1-device mesh (same routing, same
    capacity arithmetic)."""
    import dataclasses

    from repro.launch.mesh import make_local_mesh
    from repro.parallel.sharding import axis_rules, make_rules
    from repro.models.layers import moe_apply, moe_init

    cfg0 = dataclasses.replace(
        reduced_config(ARCHS["deepseek-v2-lite-16b"]),
        capacity_factor=8.0,  # dropless at this size
        first_dense_layers=0,
    )
    p = moe_init(jax.random.PRNGKey(0), cfg0)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 16, cfg0.d_model)), jnp.float32)

    y_dense, aux_dense = jax.jit(lambda pp, xx: moe_apply(pp, xx, cfg0))(p, x)

    cfg_ep = dataclasses.replace(cfg0, moe_ep=True)
    mesh = make_local_mesh(data=1, model=1)
    rules = make_rules(mesh, cfg_ep)

    def ep(pp, xx):
        with axis_rules(rules):
            return moe_apply(pp, xx, cfg_ep)

    y_ep, aux_ep = jax.jit(ep)(p, x)
    np.testing.assert_allclose(
        np.asarray(y_dense, np.float32), np.asarray(y_ep, np.float32), atol=2e-2, rtol=2e-2
    )
    np.testing.assert_allclose(float(aux_dense), float(aux_ep), rtol=1e-4)
