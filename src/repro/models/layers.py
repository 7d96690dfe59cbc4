"""Model layers: norms, RoPE, GQA/MQA/MLA/local/cross attention, SwiGLU,
MoE (Switch/GShard scatter dispatch + shared experts), RG-LRU, RWKV6.

Functional style: ``*_init(key, cfg) -> params dict``; apply fns are pure.
Activations are computed in bfloat16 (TPU realism), softmax/norm statistics
in float32.  Sharding is annotated through ``repro.parallel.sharding.shard``
(logical names; a no-op outside a mesh context).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..parallel.sharding import shard

Params = dict
COMPUTE_DTYPE = jnp.bfloat16


def _pdtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


def dense_init(key, in_dim: int, out_dim: int, cfg: ModelConfig, scale: float = 1.0):
    std = scale / (in_dim**0.5)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * std).astype(_pdtype(cfg))


def rmsnorm_init(dim: int, cfg: ModelConfig):
    return jnp.ones((dim,), _pdtype(cfg))


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


# --------------------------------------------------------------------- RoPE
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x[..., S, H, D]; positions[..., S] (int).  Rotates pairs (d, d+D/2)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :].astype(x.dtype)  # [..., S, 1, half]
    sin = jnp.sin(angles)[..., None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------- attention
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AttnCache:
    """Decode-time KV cache.  k/v: [B, S_buf, KV, D]; kpos: [B, S_buf] abs
    positions (-1 = empty).  S_buf = max_seq (full) or window (local)."""

    k: jax.Array
    v: jax.Array
    kpos: jax.Array


def attention_init(key, cfg: ModelConfig, cross: bool = False) -> Params:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko, kn1, kn2 = jax.random.split(key, 6)
    p = {
        "wq": dense_init(kq, d, cfg.n_heads * hd, cfg),
        "wk": dense_init(kk, d, cfg.n_kv_heads * hd, cfg),
        "wv": dense_init(kv, d, cfg.n_kv_heads * hd, cfg),
        "wo": dense_init(ko, cfg.n_heads * hd, d, cfg),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, cfg)
        p["k_norm"] = rmsnorm_init(hd, cfg)
    return p


def _sdpa(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, KV, D]
    v: jax.Array,
    mask: Optional[jax.Array],  # [B, 1|H, Sq, Sk] additive or None
    q_chunk: int = 1024,
    softmax_bf16: bool = False,
) -> jax.Array:
    """Chunked (over Sq) softmax attention: bounds the score buffer to
    [B, H, q_chunk, Sk] — prefill_32k never materializes 32k x 32k."""
    b, sq, h, d = q.shape
    kv = k.shape[2]
    rep = h // kv
    if sq == 1 and rep > 1:
        # decode + GQA: grouped einsums, NO repeat.  A repeat on the seq-
        # sharded cache lowers to a gather, which makes GSPMD all-gather
        # K/V every layer; contracting against the raw KV heads keeps the
        # cache local and reduces over the sharded sequence with tiny
        # per-step collectives (flash-decoding).  §Perf decode lever.
        scale = d**-0.5
        qg = q.reshape(b, sq, kv, rep, d)
        s = jnp.einsum("bckrd,bskd->bkrcs", qg, k).astype(jnp.float32) * scale
        s = shard(s, "batch", None, None, None, "seq_model")
        if mask is not None:
            s = s + mask[:, None]  # [B,1|H->1,1,C,S] broadcast over (kv, rep)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkrcs,bskd->bckrd", p, v)
        return out.reshape(b, sq, h, d)
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        # train/prefill: constrain to the q-head sharding so each model
        # shard materializes only its own slice of the repeated KV
        k = shard(k, "batch", None, "heads", None)
        v = shard(v, "batch", None, "heads", None)
    scale = d**-0.5

    def one_chunk(qc, mc):
        # qc [B, C, H, D]; mc [B, 1|H, C, Sk] or None
        acc = jnp.bfloat16 if softmax_bf16 else jnp.float32
        s = jnp.einsum("bchd,bkhd->bhck", qc, k).astype(acc) * jnp.asarray(scale, acc)
        if sq == 1:
            # decode: keep scores on the cache's sequence sharding so the
            # softmax + AV run as partial reductions (flash-decoding) instead
            # of GSPMD all-gathering K/V (the decode §Perf lever; seq_model
            # resolves to "model" only under make_decode_step's rules)
            s = shard(s, "batch", None, None, "seq_model")
        if mc is not None:
            s = s + mc.astype(s.dtype)
        # max-subtraction keeps bf16 softmax sane (exp <= 1); the row-sum in
        # bf16 over 32k keys costs ~1e-2 relative — a serving-grade trade
        p = jax.nn.softmax(s, axis=-1).astype(qc.dtype)
        return jnp.einsum("bhck,bkhd->bchd", p, v)

    if sq <= q_chunk:
        return one_chunk(q, mask)
    n_chunks = sq // q_chunk
    assert sq % q_chunk == 0, f"Sq={sq} % chunk={q_chunk}"
    dv = v.shape[-1]  # may differ from the qk head dim (MLA)
    qr = q.reshape(b, n_chunks, q_chunk, h, d).transpose(1, 0, 2, 3, 4)
    if mask is not None:
        mb, mh, _, sk = mask.shape  # leading dims may be broadcast (1)
        mr = mask.reshape(mb, mh, n_chunks, q_chunk, sk).transpose(2, 0, 1, 3, 4)
        out = jax.lax.map(lambda args: one_chunk(*args), (qr, mr))
    else:
        out = jax.lax.map(lambda qc: one_chunk(qc, None), qr)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dv)


def _causal_mask(sq: int, sk: int, dtype=jnp.float32) -> jax.Array:
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    return jnp.where(kpos <= qpos, 0.0, -1e30).astype(dtype)[None, None]


def _local_mask(sq: int, sk: int, window: int, dtype=jnp.float32) -> jax.Array:
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    ok = (kpos <= qpos) & (qpos - kpos < window)
    return jnp.where(ok, 0.0, -1e30).astype(dtype)[None, None]


def attention_apply(
    p: Params,
    x: jax.Array,  # [B, S, D]
    cfg: ModelConfig,
    *,
    positions: jax.Array,  # [B, S]
    mode: str,  # "full" (train/prefill) | "decode"
    mask_kind: str = "causal",  # "causal" | "local" | "none" (encoder)
    cache: Optional[AttnCache] = None,
    cache_index: Optional[jax.Array] = None,
    kv_source: Optional[jax.Array] = None,  # cross-attention context
    window: int = 0,
) -> tuple[jax.Array, Optional[AttnCache]]:
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    xc = x.astype(COMPUTE_DTYPE)
    q = (xc @ p["wq"].astype(COMPUTE_DTYPE)).reshape(b, s, cfg.n_heads, hd)
    is_cross = (kv_source is not None) or mode == "decode_cross"
    if mode == "decode_cross":
        # static cross context: K/V live in the (prefill-built) cache
        assert cache is not None
        k = cache.k.astype(COMPUTE_DTYPE)
        v = cache.v.astype(COMPUTE_DTYPE)
        sk_in = k.shape[1]
    else:
        src = kv_source.astype(COMPUTE_DTYPE) if kv_source is not None else xc
        sk_in = src.shape[1]
        k = (src @ p["wk"].astype(COMPUTE_DTYPE)).reshape(b, sk_in, cfg.n_kv_heads, hd)
        v = (src @ p["wv"].astype(COMPUTE_DTYPE)).reshape(b, sk_in, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        if mode != "decode_cross":
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if not is_cross:
        q = rope(q, positions, cfg.rope_theta)
        kpos_new = positions
        k = rope(k, kpos_new, cfg.rope_theta)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)

    new_cache = None
    if mode == "decode" and not is_cross:
        assert cache is not None and cache_index is not None
        buf = cache.k.shape[1]
        slot = (cache_index % buf) if mask_kind == "local" else cache_index
        if cfg.masked_cache_update:
            # one-hot masked write: elementwise over the (seq-sharded) cache,
            # so every shard updates locally — no GSPMD gather around a
            # dynamic-index store (the decode §Perf lever)
            oh = (jnp.arange(buf, dtype=jnp.int32) == slot)
            ohk = oh[None, :, None, None]
            ck = jnp.where(ohk, k.astype(cache.k.dtype), cache.k)
            cv = jnp.where(ohk, v.astype(cache.v.dtype), cache.v)
            ckpos = jnp.where(oh[None, :], positions.astype(cache.kpos.dtype), cache.kpos)
        else:
            ck = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, slot, 0, 0))
            ckpos = jax.lax.dynamic_update_slice(
                cache.kpos, positions.astype(cache.kpos.dtype), (0, slot)
            )
        new_cache = AttnCache(k=ck, v=cv, kpos=ckpos)
        k, v = ck.astype(COMPUTE_DTYPE), cv.astype(COMPUTE_DTYPE)
        qpos = positions[:, :, None]  # [B, 1, 1]
        kp = ckpos[:, None, :]  # [B, 1, S_buf]
        ok = (kp >= 0) & (kp <= qpos)
        if mask_kind == "local":
            ok &= (qpos - kp) < window
        mask = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)[:, None]  # [B,1,1,S_buf]
    elif mode == "decode_cross":
        ok = cache.kpos[:, None, None, :] >= 0
        mask = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)
    elif is_cross or mask_kind == "none":
        mask = None
    elif mask_kind == "local":
        mask = _local_mask(s, sk_in, window)
    else:
        mask = _causal_mask(s, sk_in)

    y = _sdpa(q, k, v, mask, softmax_bf16=cfg.attn_softmax_bf16)
    y = shard(y, "batch", None, "heads", None)
    out = (y.reshape(b, s, cfg.n_heads * hd) @ p["wo"].astype(COMPUTE_DTYPE)).astype(x.dtype)
    out = shard(out, "batch", None, None)
    if mode == "prefill":
        if is_cross:
            kpos = jnp.broadcast_to(jnp.arange(sk_in, dtype=jnp.int32)[None], (b, sk_in))
        else:
            kpos = positions.astype(jnp.int32)
        new_cache = AttnCache(k=k.astype(COMPUTE_DTYPE), v=v.astype(COMPUTE_DTYPE), kpos=kpos)
    return out, new_cache


# --------------------------------------------------------------------- MLA
def mla_init(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    qk_nope, qk_rope, v_hd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    h = cfg.n_heads
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], d, h * (qk_nope + qk_rope), cfg),
        "w_dkv": dense_init(ks[1], d, cfg.kv_lora_rank, cfg),
        "w_krope": dense_init(ks[2], d, qk_rope, cfg),
        "w_kup": dense_init(ks[3], cfg.kv_lora_rank, h * qk_nope, cfg),
        "w_vup": dense_init(ks[4], cfg.kv_lora_rank, h * v_hd, cfg),
        "wo": dense_init(ks[5], h * v_hd, d, cfg),
        "kv_norm": rmsnorm_init(cfg.kv_lora_rank, cfg),
    }


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MLACache:
    """MLA latent cache: c_kv [B, S, lora] + k_rope [B, S, rope_dim]."""

    c_kv: jax.Array
    k_rope: jax.Array
    kpos: jax.Array


def mla_apply(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    mode: str,
    cache: Optional[MLACache] = None,
    cache_index: Optional[jax.Array] = None,
) -> tuple[jax.Array, Optional[MLACache]]:
    b, s, d = x.shape
    h = cfg.n_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    xc = x.astype(COMPUTE_DTYPE)
    q = (xc @ p["wq"].astype(COMPUTE_DTYPE)).reshape(b, s, h, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    c_kv = rmsnorm(xc @ p["w_dkv"].astype(COMPUTE_DTYPE), p["kv_norm"], cfg.norm_eps)
    k_rope_new = rope(
        (xc @ p["w_krope"].astype(COMPUTE_DTYPE))[:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]
    c_kv = shard(c_kv, "batch", None, None)

    new_cache = None
    if mode == "decode":
        assert cache is not None and cache_index is not None
        if cfg.masked_cache_update:
            oh = (jnp.arange(cache.c_kv.shape[1], dtype=jnp.int32) == cache_index)
            ck = jnp.where(oh[None, :, None], c_kv.astype(cache.c_kv.dtype), cache.c_kv)
            cr = jnp.where(oh[None, :, None], k_rope_new.astype(cache.k_rope.dtype), cache.k_rope)
            cp = jnp.where(oh[None, :], positions.astype(jnp.int32), cache.kpos)
        else:
            ck = jax.lax.dynamic_update_slice(cache.c_kv, c_kv.astype(cache.c_kv.dtype), (0, cache_index, 0))
            cr = jax.lax.dynamic_update_slice(cache.k_rope, k_rope_new.astype(cache.k_rope.dtype), (0, cache_index, 0))
            cp = jax.lax.dynamic_update_slice(cache.kpos, positions.astype(jnp.int32), (0, cache_index))
        new_cache = MLACache(c_kv=ck, k_rope=cr, kpos=cp)
        # absorbed decode: score = q_nope @ W_kup^T @ c_kv^T + q_rope @ k_rope^T
        w_kup = p["w_kup"].astype(COMPUTE_DTYPE).reshape(-1, h, nd)  # [lora, H, nd]
        q_lat = jnp.einsum("bshn,lhn->bshl", q_nope, w_kup)  # [B,1,H,lora]
        s_lat = jnp.einsum("bshl,bkl->bhsk", q_lat, ck.astype(COMPUTE_DTYPE))
        s_rope = jnp.einsum("bshr,bkr->bhsk", q_rope, cr.astype(COMPUTE_DTYPE))
        scores = (s_lat + s_rope).astype(jnp.float32) * ((nd + rd) ** -0.5)
        kp = cp[:, None, None, :]  # [B, 1, 1, Sk]
        qp = positions[:, None, :, None]  # [B, 1, Sq, 1]
        ok = (kp >= 0) & (kp <= qp)
        scores = jnp.where(ok, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(COMPUTE_DTYPE)
        # out = probs @ c_kv @ W_vup  (stay in latent space, expand once)
        ctx_lat = jnp.einsum("bhsk,bkl->bshl", probs, ck.astype(COMPUTE_DTYPE))
        w_vup = p["w_vup"].astype(COMPUTE_DTYPE).reshape(-1, h, vd)
        ctx = jnp.einsum("bshl,lhv->bshv", ctx_lat, w_vup)
    else:
        k_nope = (c_kv @ p["w_kup"].astype(COMPUTE_DTYPE)).reshape(b, s, h, nd)
        vv = (c_kv @ p["w_vup"].astype(COMPUTE_DTYPE)).reshape(b, s, h, vd)
        k_rope_b = jnp.broadcast_to(k_rope_new[:, :, None, :], (b, s, h, rd))
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        kk = jnp.concatenate([k_nope, k_rope_b], axis=-1)
        qq = shard(qq, "batch", None, "heads", None)
        kk = shard(kk, "batch", None, "heads", None)
        ctx = _sdpa(qq, kk, vv, _causal_mask(s, s))
        if mode == "prefill":
            new_cache = MLACache(
                c_kv=c_kv.astype(COMPUTE_DTYPE),
                k_rope=k_rope_new.astype(COMPUTE_DTYPE),
                kpos=positions.astype(jnp.int32),
            )
    out = (ctx.reshape(b, s, h * vd) @ p["wo"].astype(COMPUTE_DTYPE)).astype(x.dtype)
    out = shard(out, "batch", None, None)
    return out, new_cache


# --------------------------------------------------------------------- MLPs
def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    kg, ku, ko = jax.random.split(key, 3)
    return {
        "wg": dense_init(kg, d, ff, cfg),
        "wu": dense_init(ku, d, ff, cfg),
        "wd": dense_init(ko, ff, d, cfg),
    }


def mlp_apply(p: Params, x: jax.Array) -> jax.Array:
    xc = x.astype(COMPUTE_DTYPE)
    h = jax.nn.silu(xc @ p["wg"].astype(COMPUTE_DTYPE)) * (xc @ p["wu"].astype(COMPUTE_DTYPE))
    names = ("batch",) + (None,) * (h.ndim - 2) + ("ffn",)
    h = shard(h, *names)
    out = (h @ p["wd"].astype(COMPUTE_DTYPE)).astype(x.dtype)
    return shard(out, *(("batch",) + (None,) * (out.ndim - 1)))


# --------------------------------------------------------------------- MoE
def moe_init(key, cfg: ModelConfig) -> Params:
    d, ff, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    std = 1.0 / (d**0.5)
    pd = _pdtype(cfg)
    p = {
        "router": dense_init(kr, d, e, cfg, scale=0.1),
        "wg": (jax.random.normal(kg, (e, d, ff), jnp.float32) * std).astype(pd),
        "wu": (jax.random.normal(ku, (e, d, ff), jnp.float32) * std).astype(pd),
        "wd": (jax.random.normal(kd, (e, ff, d), jnp.float32) * (ff**-0.5)).astype(pd),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks, cfg, d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
    return p



def _moe_apply_ep(p: Params, x: jax.Array, cfg: ModelConfig, rules) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via replicated-dispatch shard_map (the §Perf MoE
    lever).  Activations are data-sharded and REPLICATED across the model
    axis, so each model shard already holds every token in its data row: it
    selects the tokens routed to ITS E/msz experts locally, runs its expert
    matmuls, scatters back into token space, and one [T_local, d] psum over
    'model' combines the rows.  Per-device fwd wire: T_local*d bf16 (~16 MB)
    instead of GSPMD's 3.2 GB partial-sum all-reduces of the [T*k, d]
    dispatch tensors (EXPERIMENTS.md §Perf-extended #6)."""
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    mesh = rules.mesh
    msz = mesh.shape.get("model", 1)
    e_local = e // msz
    xt = x.reshape(b * s, d).astype(COMPUTE_DTYPE)

    def body(xt_l, router, wg, wu, wd):
        # xt_l [T_l, d] (data shard, replicated over model); wg/wu/wd local
        # expert shards [E/msz, ...]; router replicated.
        t_l = xt_l.shape[0]
        midx = jax.lax.axis_index("model")
        logits = (xt_l @ router.astype(jnp.float32)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [T_l, k]
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)
        # aux loss (identical on every model shard: inputs are replicated)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_idx, e).sum(axis=1), axis=0) / k
        aux = e * jnp.sum(me * ce)

        cap = max(8, int(cfg.capacity_factor * t_l * k / e))
        eidx = expert_idx.reshape(-1)
        local_e = eidx - midx * e_local  # in [0, e_local) iff mine
        mine = (local_e >= 0) & (local_e < e_local)
        safe_e = jnp.clip(local_e, 0, e_local - 1)
        onehot = jax.nn.one_hot(safe_e, e_local, dtype=jnp.int32) * mine[:, None].astype(jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot
        pos = pos.sum(-1)
        keep = mine & (pos < cap)
        gates = (gate_vals.reshape(-1) * keep).astype(COMPUTE_DTYPE)
        token_src = jnp.repeat(jnp.arange(t_l), k)
        safe_pos = jnp.where(keep, pos, cap - 1)
        buf = jnp.zeros((e_local, cap, d), COMPUTE_DTYPE)
        buf = buf.at[safe_e, safe_pos].add(jnp.where(keep[:, None], xt_l[token_src], 0))
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(COMPUTE_DTYPE)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wu.astype(COMPUTE_DTYPE))
        yb = jnp.einsum("ecf,efd->ecd", h, wd.astype(COMPUTE_DTYPE))
        contrib = yb[safe_e, safe_pos] * gates[:, None]
        y_part = jnp.zeros((t_l, d), COMPUTE_DTYPE).at[token_src].add(contrib)
        y = jax.lax.psum(y_part, "model")
        return y, aux

    wrapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(rules.resolve("batch")),
            P(),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(rules.resolve("batch")), P()),
        check_vma=False,
    )
    y, aux = wrapped(xt, p["router"], p["wg"], p["wu"], p["wd"])
    if "shared" in p:
        y = y + mlp_apply(p["shared"], xt).astype(COMPUTE_DTYPE)
    return y.reshape(b, s, d).astype(x.dtype), aux


def moe_apply(p: Params, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """Scatter-dispatch MoE (Switch/GShard): top-k routing with a capacity
    cap; overflowing tokens fall through on the residual path.  Returns
    (output, aux_loss)."""
    from ..parallel.sharding import current_rules

    rules = current_rules()
    if cfg.moe_ep and rules is not None and "model" in rules.mesh.axis_names \
            and cfg.n_experts % rules.mesh.shape.get("model", 1) == 0:
        return _moe_apply_ep(p, x, cfg, rules)
    b, s, d = x.shape
    n_tok = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = max(8, int(cfg.capacity_factor * n_tok * k / e))
    xt = x.reshape(n_tok, d).astype(COMPUTE_DTYPE)

    logits = (xt @ p["router"].astype(jnp.float32)).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [T, k]
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        (jax.nn.one_hot(expert_idx, e).sum(axis=1)).astype(jnp.float32), axis=0
    ) / k
    aux = e * jnp.sum(me * ce)

    # position of each (token, slot) within its expert, via one-hot cumsum
    onehot = jax.nn.one_hot(expert_idx.reshape(-1), e, dtype=jnp.int32)  # [T*k, E]
    pos_in_e = (jnp.cumsum(onehot, axis=0) - 1) * onehot  # [T*k, E]
    pos = pos_in_e.sum(axis=-1)  # [T*k]
    eidx = expert_idx.reshape(-1)
    keep = pos < cap
    gates = (gate_vals.reshape(-1) * keep).astype(COMPUTE_DTYPE)

    # scatter tokens into [E, cap, d]
    token_src = jnp.repeat(jnp.arange(n_tok), k)
    buf = jnp.zeros((e, cap, d), COMPUTE_DTYPE)
    safe_pos = jnp.where(keep, pos, cap - 1)
    buf = buf.at[eidx, safe_pos].add(jnp.where(keep[:, None], xt[token_src], 0))
    buf = shard(buf, "experts", None, None)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(COMPUTE_DTYPE)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["wu"].astype(COMPUTE_DTYPE))
    h = shard(h, "experts", None, None)
    yb = jnp.einsum("ecf,efd->ecd", h, p["wd"].astype(COMPUTE_DTYPE))

    # gather back: y[token] += gate * yb[expert, pos]
    contrib = yb[eidx, safe_pos] * gates[:, None]  # [T*k, d]
    y = jnp.zeros((n_tok, d), COMPUTE_DTYPE).at[token_src].add(contrib)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], xt).astype(COMPUTE_DTYPE)
    return y.reshape(b, s, d).astype(x.dtype), aux


# ------------------------------------------------------------------- RG-LRU
def rglru_init(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    w = cfg.lru_width or d
    ks = jax.random.split(key, 6)
    pd = _pdtype(cfg)
    return {
        "w_in_x": dense_init(ks[0], d, w, cfg),  # input branch
        "w_in_g": dense_init(ks[1], d, w, cfg),  # gate branch
        "conv": (jax.random.normal(ks[2], (cfg.conv_width, w), jnp.float32) * 0.1).astype(pd),
        "wa": dense_init(ks[3], w, w, cfg, scale=0.5),  # recurrence gate
        "wx": dense_init(ks[4], w, w, cfg, scale=0.5),  # input gate
        "lam": (jnp.ones((w,), jnp.float32) * 2.0).astype(pd),  # softplus^-1(a)
        "w_out": dense_init(ks[5], w, d, cfg),
    }


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RecState:
    h: jax.Array  # [B, W] recurrent state
    conv: jax.Array  # [B, conv_width-1, W] conv tail


def _rglru_core(u: jax.Array, p: Params, h0: jax.Array, c: float = 8.0):
    """u [B, S, W]; returns (y [B,S,W], h_final [B,W]).  Associative scan."""
    uc = u.astype(jnp.float32)
    r = jax.nn.sigmoid(uc @ p["wa"].astype(jnp.float32))
    i = jax.nn.sigmoid(uc @ p["wx"].astype(jnp.float32))
    log_a0 = -jax.nn.softplus(-p["lam"].astype(jnp.float32))  # log sigmoid(lam)
    log_a = c * r * log_a0[None, None, :]
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.clip(1.0 - a * a, 1e-9)) * (i * uc)

    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, b1 * a2 + b2

    a_sc, b_sc = jax.lax.associative_scan(combine, (a, b), axis=1)
    h = b_sc + a_sc * h0[:, None, :].astype(jnp.float32)
    return h.astype(u.dtype), h[:, -1, :]


def rglru_apply(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    mode: str,
    state: Optional[RecState] = None,
) -> tuple[jax.Array, Optional[RecState]]:
    b, s, d = x.shape
    w = cfg.lru_width or d
    xc = x.astype(COMPUTE_DTYPE)
    u = xc @ p["w_in_x"].astype(COMPUTE_DTYPE)  # [B, S, W]
    g = jax.nn.gelu(xc @ p["w_in_g"].astype(COMPUTE_DTYPE))
    u = shard(u, "batch", None, "ffn")
    # short depthwise causal conv
    cw = cfg.conv_width
    if mode == "decode":
        assert state is not None
        hist = jnp.concatenate([state.conv.astype(COMPUTE_DTYPE), u], axis=1)  # [B, cw, W]
        conv_out = jnp.einsum("bcw,cw->bw", hist, p["conv"].astype(COMPUTE_DTYPE))[:, None, :]
        new_conv = hist[:, 1:, :]
        y_core, h_fin = _rglru_core(conv_out, p, state.h)
        new_state = RecState(h=h_fin, conv=new_conv.astype(state.conv.dtype))
    else:
        pad = jnp.zeros((b, cw - 1, w), COMPUTE_DTYPE)
        up = jnp.concatenate([pad, u], axis=1)
        stacked = jnp.stack([up[:, i : i + s, :] for i in range(cw)], axis=2)  # [B,S,cw,W]
        conv_out = jnp.einsum("bscw,cw->bsw", stacked, p["conv"].astype(COMPUTE_DTYPE))
        h0 = jnp.zeros((b, w), jnp.float32) if state is None else state.h
        y_core, h_fin = _rglru_core(conv_out, p, h0)
        new_state = (
            RecState(h=h_fin, conv=up[:, -(cw - 1) :, :].astype(COMPUTE_DTYPE))
            if mode == "prefill"
            else None
        )
    y = (y_core * g) @ p["w_out"].astype(COMPUTE_DTYPE)
    y = shard(y, "batch", None, None)
    return y.astype(x.dtype), new_state


def _wkv_chunked(r, k, v, w, u, s0, chunk: int):
    """Chunked-parallel WKV6 (the §Perf hillclimb for rwkv6 train/prefill).

    The naive recurrence makes T sequential HBM round-trips of the [B,H,K,V]
    state.  Splitting T into chunks of C: within a chunk the decay factorizes
    per channel, exp(cl_{t-1} - cl_u) = exp(cl_{t-1}) * exp(-cl_u), so the
    intra-chunk contribution is an attention-like [C,C] product and the state
    advances once per chunk -> T/C sequential steps, ~C x less state traffic,
    ~2x more FLOPs (the C^2 term).  Log-space cumsums with a -60 clamp keep
    exp(-cl_u) finite (pairs spanning >60 nats of decay contribute < 1e-26).

    r,k,v,w: [B,S,H,K] (w = per-step decay in (0,1]); u: [H,K];
    s0: [B,H,K,V].  Returns (S_final, y [B,S,H*K]).
    """
    b, s, h, kd = r.shape
    nc = s // chunk
    clamp = -60.0
    f32 = jnp.float32

    def cshape(x):
        return x.astype(f32).reshape(b, nc, chunk, h, kd).transpose(1, 0, 2, 3, 4)

    rf, kf, vf = cshape(r), cshape(k), cshape(v)
    lw = jnp.log(jnp.clip(cshape(w), 1e-38, 1.0))
    cl = jnp.cumsum(lw, axis=2)  # inclusive within-chunk cumulative log-decay
    cl_before = cl - lw  # exclusive
    r_dec = rf * jnp.exp(jnp.maximum(cl_before, clamp))
    k_dec = kf * jnp.exp(jnp.maximum(-cl, clamp))
    tri = jnp.tril(jnp.ones((chunk, chunk), f32), k=-1)[None, None]  # strict t>u

    uu = u[None, None]  # [1,1,H,K]

    def chunk_step(S, inp):
        rd, kdec, vv_, cl_c, rraw, kraw = inp  # each [B,C,H,K]
        y_inter = jnp.einsum("bchk,bhkv->bchv", rd, S)
        att = jnp.einsum("bchk,bdhk->bhcd", rd, kdec)  # c = t, d = u
        att = att * tri
        y_intra = jnp.einsum("bhcd,bdhv->bchv", att, vv_)
        diag_gate = jnp.sum(rraw * uu * kraw, axis=-1)  # [B,C,H]
        y_diag = diag_gate[..., None] * vv_
        y = y_inter + y_intra + y_diag
        total = cl_c[:, -1]  # [B,H,K]
        k_fold = kraw * jnp.exp(jnp.maximum(total[:, None] - cl_c, clamp))
        S = S * jnp.exp(total)[..., None] + jnp.einsum("bchk,bchv->bhkv", k_fold, vv_)
        return S, y.astype(COMPUTE_DTYPE)

    s_fin, ys = jax.lax.scan(chunk_step, s0, (r_dec, k_dec, vf, cl, rf, kf))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, s, h * kd)
    return s_fin, y


# -------------------------------------------------------------------- RWKV6
def rwkv_init(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    hk = cfg.rwkv_head_dim
    h = d // hk
    ks = jax.random.split(key, 10)
    pd = _pdtype(cfg)
    lora = max(32, d // 16)
    return {
        # token-shift mix coefficients (static lerp + data-dependent lora)
        "mix_rkvwg": (jax.random.uniform(ks[0], (5, d)) * 0.5 + 0.25).astype(pd),
        "w_lora_a": dense_init(ks[1], d, lora, cfg, scale=0.1),
        "w_lora_b": dense_init(ks[2], lora, d, cfg, scale=0.1),
        "decay_base": (jnp.full((h, hk), -6.0, jnp.float32)).astype(pd),
        "bonus_u": (jnp.zeros((h, hk), jnp.float32)).astype(pd),
        "wr": dense_init(ks[3], d, d, cfg),
        "wk": dense_init(ks[4], d, d, cfg),
        "wv": dense_init(ks[5], d, d, cfg),
        "wg": dense_init(ks[6], d, d, cfg),
        "wo": dense_init(ks[7], d, d, cfg),
        "ln_x": rmsnorm_init(d, cfg),
        # channel-mix
        "cm_mix": (jax.random.uniform(ks[8], (2, d)) * 0.5 + 0.25).astype(pd),
        "cm_k": dense_init(ks[9], d, cfg.d_ff, cfg),
        "cm_v": dense_init(jax.random.fold_in(key, 99), cfg.d_ff, d, cfg),
        "cm_r": dense_init(jax.random.fold_in(key, 98), d, d, cfg),
    }


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RwkvState:
    wkv: jax.Array  # [B, H, K, V]
    shift_t: jax.Array  # [B, D] last token (time-mix)
    shift_c: jax.Array  # [B, D] last token (channel-mix)


def rwkv_apply(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    mode: str,
    state: Optional[RwkvState] = None,
) -> tuple[jax.Array, Optional[RwkvState]]:
    """RWKV6 (Finch) block: time-mix with data-dependent decay + channel-mix.

    Sequential lax.scan over time (O(T) state recurrence).  Decode consumes
    one token with O(1) state — the long_500k cell.
    """
    b, s, d = x.shape
    hk = cfg.rwkv_head_dim
    h = d // hk
    xc = x.astype(COMPUTE_DTYPE)
    prev_t = (
        state.shift_t.astype(COMPUTE_DTYPE)[:, None, :]
        if state is not None
        else jnp.zeros((b, 1, d), COMPUTE_DTYPE)
    )
    x_prev = jnp.concatenate([prev_t, xc[:, :-1, :]], axis=1)

    mix = p["mix_rkvwg"].astype(COMPUTE_DTYPE)  # [5, D]
    def lerp(i):
        return xc + (x_prev - xc) * mix[i][None, None, :]

    def _heads(x):
        return shard(x.reshape(b, s, h, hk), "batch", None, "heads", None)

    r = _heads(lerp(0) @ p["wr"].astype(COMPUTE_DTYPE))
    kk = _heads(lerp(1) @ p["wk"].astype(COMPUTE_DTYPE))
    vv = _heads(lerp(2) @ p["wv"].astype(COMPUTE_DTYPE))
    g = jax.nn.silu(shard(lerp(4) @ p["wg"].astype(COMPUTE_DTYPE), "batch", None, "ffn"))
    # data-dependent decay (v6): w = exp(-exp(base + lora(x)))
    dd = (lerp(3) @ p["w_lora_a"].astype(COMPUTE_DTYPE)) @ p["w_lora_b"].astype(COMPUTE_DTYPE)
    decay = jnp.exp(
        -jnp.exp(jnp.clip(p["decay_base"].astype(jnp.float32).reshape(1, 1, d)
                          + dd.astype(jnp.float32), -20.0, 2.0))
    ).reshape(b, s, h, hk)
    u = p["bonus_u"].astype(jnp.float32)  # [H, K]

    s0 = (
        state.wkv.astype(jnp.float32)
        if state is not None
        else jnp.zeros((b, h, hk, hk), jnp.float32)
    )

    if cfg.rwkv_chunked and s >= 2 * cfg.rwkv_chunked and s % cfg.rwkv_chunked == 0:
        s_fin, y = _wkv_chunked(r, kk, vv, decay, u, s0, cfg.rwkv_chunked)
    else:
        def step(S, inp):
            r_t, k_t, v_t, w_t = inp  # [B,H,K] x3, [B,H,K]
            kv = jnp.einsum("bhk,bhv->bhkv", k_t.astype(jnp.float32), v_t.astype(jnp.float32))
            y = jnp.einsum("bhk,bhkv->bhv", r_t.astype(jnp.float32), S + u[None, :, :, None] * kv)
            S = S * w_t.astype(jnp.float32)[..., None] + kv
            return S, y.astype(COMPUTE_DTYPE)

        xs = (
            r.transpose(1, 0, 2, 3),
            kk.transpose(1, 0, 2, 3),
            vv.transpose(1, 0, 2, 3),
            decay.transpose(1, 0, 2, 3),
        )
        s_fin, ys = jax.lax.scan(step, s0, xs)
        y = ys.transpose(1, 0, 2, 3).reshape(b, s, d)
    y = y.reshape(b, s, d)
    y = rmsnorm(y, p["ln_x"], cfg.norm_eps) * g
    att = (y @ p["wo"].astype(COMPUTE_DTYPE)).astype(x.dtype)
    att = shard(att, "batch", None, None)

    # channel-mix (with its own shift)
    xa = xc + att.astype(COMPUTE_DTYPE)
    prev_c = (
        state.shift_c.astype(COMPUTE_DTYPE)[:, None, :]
        if state is not None
        else jnp.zeros((b, 1, d), COMPUTE_DTYPE)
    )
    xa_prev = jnp.concatenate([prev_c, xa[:, :-1, :]], axis=1)
    cmix = p["cm_mix"].astype(COMPUTE_DTYPE)
    xk = xa + (xa_prev - xa) * cmix[0][None, None, :]
    xr = xa + (xa_prev - xa) * cmix[1][None, None, :]
    kq = jnp.square(jax.nn.relu(xk @ p["cm_k"].astype(COMPUTE_DTYPE)))
    kq = shard(kq, "batch", None, "ffn")
    cm = jax.nn.sigmoid(xr @ p["cm_r"].astype(COMPUTE_DTYPE)) * (kq @ p["cm_v"].astype(COMPUTE_DTYPE))
    cm = shard(cm, "batch", None, None)
    out = (att.astype(COMPUTE_DTYPE) + cm).astype(x.dtype)

    new_state = None
    if mode in ("prefill", "decode"):
        new_state = RwkvState(
            wkv=s_fin,
            shift_t=xc[:, -1, :],
            shift_c=xa[:, -1, :],
        )
    return out, new_state
