"""Pallas TPU kernel: flash attention (online-softmax over KV blocks).

The prefill cells' memory term is dominated by materialized score buffers
(EXPERIMENTS.md §Roofline); a fused attention keeps the working set at
[bq, bk] in VMEM with running (max, sum, acc) scratch carried across the
sequential kv grid dimension — the same TPU sequential-grid idiom as
cone_scan.  HBM traffic drops from O(S^2) scores to Q+K+V+O.

Single-head kernel over [S, D]; ops.flash_attention vmaps over (batch,
heads).  Causal masking skips fully-masked kv blocks via pl.when and
iota-masks the diagonal block.  Validated against ref.flash_attention_ref
in interpret mode (tests/test_kernels.py); on this CPU container it is a
correctness artifact — the dry-run keeps the XLA attention so the roofline
instrument sees real ops (a Mosaic custom call would hide them; DESIGN.md
§7 records the analytic-injection follow-up).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, causal: bool, scale: float, nk: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (not causal) or (j * bk <= i * bq + bq - 1)

    @pl.when(run)
    def _body():
        q = q_ref[...].astype(jnp.float32)  # [bq, D]
        k = k_ref[...].astype(jnp.float32)  # [bk, D]
        v = v_ref[...].astype(jnp.float32)
        s = (q @ k.T) * scale  # [bq, bk]
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, _NEG)
        m_prev = m_ref[...]  # [bq, 1]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_pallas(
    q: jax.Array,  # [S, D]
    k: jax.Array,  # [S_k, D]
    v: jax.Array,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    *,
    interpret: bool,
):
    sq, d = q.shape
    sk = k.shape[0]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, f"S={sq}/{sk} % blocks {bq}/{bk}"
    nq, nk = sq // bq, sk // bk
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, scale=d**-0.5, nk=nk
    )
    return pl.pallas_call(
        kernel,
        grid=(nq, nk),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
