"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are tested against
(interpret=True on CPU, shape/dtype sweeps in tests/test_kernels.py).

Conventions (shared with the kernels):

* ``interval_stats``:  x[T, S] time-major, S independent series in lanes;
  fixed window W along T.  Returns per-window (min, max) -> [T//W, S].
* ``residual_quant``:  per-row linear base (theta + slope * t) over blocks
  x[M, N]; emits clipped round((x-pred)/step) plus the error-feedback term.
* ``cone_scan``:       the SHRINK shrinking-cone recurrence, vectorized over
  S series in lanes.  Emits per-point break flags, the origin of the segment
  starting at each break, and the span of the segment that closed there.
* ``dequant_reconstruct``: inverse of residual_quant.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "flash_attention_ref",
    "interval_stats_ref",
    "residual_quant_ref",
    "dequant_reconstruct_ref",
    "pyramid_quant_ref",
    "pyramid_reconstruct_ref",
    "cone_scan_ref",
    "segment_agg_ref",
    "rans_encode_ref",
    "rans_decode_ref",
]


def interval_stats_ref(x: jax.Array, window: int) -> tuple[jax.Array, jax.Array]:
    """x[T, S] -> (mins[T//W, S], maxs[T//W, S]); T must divide by W."""
    t, s = x.shape
    assert t % window == 0, f"T={t} not divisible by window={window}"
    xr = x.reshape(t // window, window, s)
    return xr.min(axis=1), xr.max(axis=1)


def residual_quant_ref(
    x: jax.Array,
    theta: jax.Array,
    slope: jax.Array,
    step: jax.Array,
    qmax: int = 127,
    lengths: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """x[M, N]; theta/slope/step[M, 1] per-row base-line params.

    Returns (q int32 in [-qmax, qmax], err = x - (pred + q*step)).
    ``lengths`` [M] marks each row's ragged tail: positions >= lengths[m]
    emit q = 0 and err = 0 (padding carries no symbols and no feedback).
    """
    m, n = x.shape
    t = jnp.arange(n, dtype=x.dtype)[None, :]
    pred = theta + slope * t
    r = x - pred
    q = jnp.clip(jnp.round(r / step), -qmax, qmax).astype(jnp.int32)
    err = r - q.astype(x.dtype) * step
    if lengths is not None:
        valid = jnp.arange(n, dtype=jnp.int32)[None, :] < jnp.asarray(
            lengths, jnp.int32
        ).reshape(m, 1)
        q = jnp.where(valid, q, 0)
        err = jnp.where(valid, err, 0.0)
    return q, err


def dequant_reconstruct_ref(
    q: jax.Array,
    theta: jax.Array,
    slope: jax.Array,
    step: jax.Array,
) -> jax.Array:
    """Inverse of residual_quant: pred + q*step."""
    m, n = q.shape
    t = jnp.arange(n, dtype=theta.dtype)[None, :]
    pred = theta + slope * t
    return pred + q.astype(theta.dtype) * step


def pyramid_quant_ref(
    x: jax.Array,
    theta: jax.Array,
    slope: jax.Array,
    steps: jax.Array,
    qmax: int = 127,
    lengths: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Multi-layer refinement quantization (the device half of the residual
    pyramid): x[M, N]; theta/slope[M, 1] per-row base-line params;
    steps[L] strictly decreasing quantizer steps, layer l quantizing the
    error its predecessors left behind:

        e_0 = x - pred;  q_l = clip(round(e_l / step_l));  e_{l+1} = e_l - q_l*step_l

    Returns (qs int32 [L, M, N], err [M, N] = the error remaining after the
    finest layer).  ``lengths`` [M] marks each row's ragged tail: positions
    >= lengths[m] emit q = 0 across every layer and err = 0.
    """
    m, n = x.shape
    t = jnp.arange(n, dtype=x.dtype)[None, :]
    pred = theta + slope * t
    e = x - pred
    qs = []
    num_layers = int(steps.shape[0])
    for l in range(num_layers):
        step = steps[l].astype(x.dtype)
        q = jnp.clip(jnp.round(e / step), -qmax, qmax).astype(jnp.int32)
        e = e - q.astype(x.dtype) * step
        qs.append(q)
    qs = jnp.stack(qs)
    if lengths is not None:
        valid = jnp.arange(n, dtype=jnp.int32)[None, :] < jnp.asarray(
            lengths, jnp.int32
        ).reshape(m, 1)
        qs = jnp.where(valid[None], qs, 0)
        e = jnp.where(valid, e, 0.0)
    return qs, e


def pyramid_reconstruct_ref(
    qs: jax.Array,
    theta: jax.Array,
    slope: jax.Array,
    steps: jax.Array,
) -> jax.Array:
    """Inverse of pyramid_quant at any layer prefix: feed qs[:k+1] and
    steps[:k+1] to reconstruct through layer k; the full stack gives
    pred + Σ_l q_l * step_l."""
    m, n = qs.shape[1], qs.shape[2]
    t = jnp.arange(n, dtype=theta.dtype)[None, :]
    pred = theta + slope * t
    contrib = (qs.astype(theta.dtype) * steps.astype(theta.dtype)[:, None, None]).sum(0)
    return pred + contrib


def cone_scan_ref(
    x: jax.Array,
    eps_hat: jax.Array,
    lengths: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """SHRINK shrinking-cone scan, vectorized over lanes.

    x[T, S], eps_hat[T, S] (adaptive threshold to use for a segment that
    *starts* at (t, s)).  ``lengths`` [S] optionally marks ragged lanes:
    positions t >= lengths[s] are padding — they never constrain, break,
    or seed a cone, and the lane's state (hence fin_lo/fin_hi) freezes at
    its last valid sample.

    Returns (brk i32[T,S], theta f32[T,S], psi_lo f32[T,S], psi_hi f32[T,S],
             fin_lo f32[1,S], fin_hi f32[1,S]):
      * brk[t]   = 1 iff a new segment starts at t (brk[0] == 1).
      * theta[t] = origin of the segment starting at t   (valid where brk=1).
      * psi_lo/hi[t] = span of the segment that CLOSED at t-1 (valid where
        brk=1 and t>0).
      * fin_lo/hi = span of the still-open segment at the lane end (the host
        closes it when compacting segments).
    """
    big = jnp.float32(3.4e38)
    t_steps, s = x.shape
    len_vec = (
        jnp.full((s,), t_steps, jnp.int32)
        if lengths is None
        else jnp.asarray(lengths, jnp.int32)
    )

    def origin(v, eps):
        return jnp.floor(v / eps) * eps

    def step_fn(carry, inp):
        theta, lo, hi, t0, eps_seg = carry
        v, eps_t, t = inp
        dt = (t - t0).astype(x.dtype)
        cand_hi = (v + eps_seg - theta) / jnp.maximum(dt, 1.0)
        cand_lo = (v - eps_seg - theta) / jnp.maximum(dt, 1.0)
        # dt == 0 (the segment's own start point) sets theta only; it is not
        # a slope constraint — same convention as semantics.extract_semantics.
        # t >= lengths is a padded position: the lane freezes there.
        grow = (dt > 0) & (t < len_vec)
        new_hi = jnp.where(grow, jnp.minimum(hi, cand_hi), hi)
        new_lo = jnp.where(grow, jnp.maximum(lo, cand_lo), lo)
        brk = (new_lo > new_hi) & grow
        out_lo, out_hi = lo, hi  # span of the closing segment
        theta_new = origin(v, eps_t)
        theta = jnp.where(brk, theta_new, theta)
        eps_seg = jnp.where(brk, eps_t, eps_seg)
        lo = jnp.where(brk, -big, new_lo)
        hi = jnp.where(brk, big, new_hi)
        t0 = jnp.where(brk, t, t0)
        return (theta, lo, hi, t0, eps_seg), (
            brk.astype(jnp.int32),
            theta,
            out_lo,
            out_hi,
        )

    v0 = x[0]
    eps0 = eps_hat[0]
    carry0 = (
        origin(v0, eps0),
        jnp.full((s,), -big, x.dtype),
        jnp.full((s,), big, x.dtype),
        jnp.zeros((s,), jnp.int32),
        eps0,
    )
    ts = jnp.arange(t_steps, dtype=jnp.int32)
    (_, lo_f, hi_f, _, _), (brk, theta, psi_lo, psi_hi) = jax.lax.scan(
        step_fn, carry0, (x, eps_hat, ts)
    )
    brk = brk.at[0].set(jnp.ones((s,), jnp.int32))
    theta = theta.at[0].set(origin(v0, eps0))
    return brk, theta, psi_lo, psi_hi, lo_f[None, :], hi_f[None, :]


def segment_agg_ref(
    theta: jax.Array,
    slope: jax.Array,
    a: jax.Array,
    b: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Closed-form per-segment aggregates (the compressed-domain analytics
    primitive): theta/slope/a/b [M, 1] line params + local overlap window
    [a, b).  Returns (sum, sumsq, min, max) [M, 1] of the segment's
    predictions over the window; rows with b <= a emit the aggregate
    identity (0, 0, +3.4e38, -3.4e38)."""
    big = jnp.asarray(3.4e38, theta.dtype)
    m = jnp.maximum(b - a, 0.0)
    d1 = (b * (b - 1.0) - a * (a - 1.0)) * 0.5
    d2 = (b * (b - 1.0) * (2.0 * b - 1.0) - a * (a - 1.0) * (2.0 * a - 1.0)) / 6.0
    live = m > 0.0
    seg_sum = jnp.where(live, m * theta + slope * d1, 0.0)
    seg_sumsq = jnp.where(
        live, m * theta * theta + 2.0 * theta * slope * d1 + slope * slope * d2, 0.0
    )
    va = theta + slope * a
    vb = theta + slope * (b - 1.0)
    seg_min = jnp.where(live, jnp.minimum(va, vb), big)
    seg_max = jnp.where(live, jnp.maximum(va, vb), -big)
    return seg_sum, seg_sumsq, seg_min, seg_max


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True) -> jax.Array:
    """Plain softmax attention over [S, D] single head (flash oracle)."""
    sq, d = q.shape
    sk = k.shape[0]
    s = (q.astype(jnp.float32) @ k.astype(jnp.float32).T) * (d**-0.5)
    if causal:
        qpos = jnp.arange(sq)[:, None]
        kpos = jnp.arange(sk)[None, :]
        s = jnp.where(kpos <= qpos, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return (p @ v.astype(jnp.float32)).astype(q.dtype)


# --------------------------------------------------------------------- #
# Interleaved K-lane rANS (the device entropy engine's step machines)
# --------------------------------------------------------------------- #
#
# Layout shared with core.entropy and kernels/rans.py: symbol i of a stream
# lives in lane i % K at step i // K, states are uint32 in [2^16, 2^32)
# with 16-bit renormalization and M = 2^12 probability bits.  Rows are
# independent (stream, plane) pairs; per-row tables carry a reserved 257th
# "identity" symbol (freq = M, cum = 0) whose rANS transform is exactly
# x -> x and whose renorm threshold (f << 20) - 1 wraps to the uint32 max,
# so padded steps and rows are byte-exact no-ops — that is what lets the
# host pad step counts and row counts up to a shape bucket for jit-cache
# reuse without changing a single emitted word.

_RANS_PROB_BITS = 12
_RANS_M = 1 << _RANS_PROB_BITS
_RANS_L = 1 << 16


def rans_encode_ref(
    sym_cube: jax.Array, f_ext: jax.Array, c_ext: jax.Array, unroll: int = 8
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Encode step machine: walk steps backward (rANS is LIFO), all R*K
    states advancing as one [R, K] vector op per step.

    sym_cube[T, R, K] int32 in [0, 256] (256 = identity pad symbol),
    f_ext/c_ext[R, 257] uint32 (row tables + identity column).  Returns
    (states[R, K] uint32, need[T, R, K] bool, vals[T, R, K] uint16): step
    t's renormalizing lanes are ``need[t]`` and the 16-bit words they
    emitted are ``vals[t][need[t]]`` — already indexed by DECODE step, so
    flat boolean extraction in (row, step asc, lane asc) order yields the
    wire's word stream directly.
    """
    r, k = sym_cube.shape[1], sym_cube.shape[2]
    f_flat = f_ext.reshape(-1)
    c_flat = c_ext.reshape(-1)
    row_off = (jnp.arange(r, dtype=jnp.int32) * 257)[:, None]
    x0 = jnp.full((r, k), _RANS_L, jnp.uint32)

    def body(x, syms):
        idx = syms + row_off
        f = f_flat[idx]
        c = c_flat[idx]
        # renorm threshold minus one: x >= f << 20  <=>  x > (f << 20) - 1;
        # f == 2^12 wraps to 0xFFFFFFFF -> "never renormalize"
        need = x > (f << jnp.uint32(32 - _RANS_PROB_BITS)) - jnp.uint32(1)
        val = x.astype(jnp.uint16)  # truncating low-16 store
        x = jnp.where(need, x >> jnp.uint32(16), x)
        div = x // f
        rem = x - div * f
        x = (div << jnp.uint32(_RANS_PROB_BITS)) + rem + c
        return x, (need, val)

    x, (need, vals) = jax.lax.scan(body, x0, sym_cube, reverse=True, unroll=unroll)
    return x, need, vals


def rans_decode_ref(
    states: jax.Array,
    slot2sym: jax.Array,
    f_tab: jax.Array,
    c_tab: jax.Array,
    words: jax.Array,
    act: jax.Array,
    unroll: int = 4,
) -> jax.Array:
    """Decode step machine: walk steps forward; within a step the
    renormalizing lanes consume words in ascending lane order (a lane-axis
    cumsum indexes the row's word stream).

    states[R, K] uint32 (final encoder states), slot2sym[R, M] int32,
    f_tab/c_tab[R, 256] uint32, words[R, W] uint16 (row-padded),
    act[T, R, K] bool marks live symbol positions — padded steps, padded
    rows, and the last step's tail lanes must neither emit symbols nor
    consume words.  Returns syms[T, R, K] uint8.
    """
    r, k = states.shape
    maxw = words.shape[1]
    s2s_flat = slot2sym.reshape(-1)
    f_flat = f_tab.reshape(-1)
    c_flat = c_tab.reshape(-1)
    w_flat = words.reshape(-1)
    row_off_m = (jnp.arange(r, dtype=jnp.int32) * _RANS_M)[:, None]
    row_off_s = (jnp.arange(r, dtype=jnp.int32) * 256)[:, None]
    row_off_w = (jnp.arange(r, dtype=jnp.int32) * maxw)[:, None]
    pos0 = jnp.zeros((r,), jnp.int32)

    def body(carry, a):
        x, pos = carry
        slot = (x & jnp.uint32(_RANS_M - 1)).astype(jnp.int32)
        s = s2s_flat[slot + row_off_m]
        f = f_flat[s + row_off_s]
        c = c_flat[s + row_off_s]
        x2 = f * (x >> jnp.uint32(_RANS_PROB_BITS)) + slot.astype(jnp.uint32) - c
        need = (x2 < _RANS_L) & a
        kidx = pos[:, None] + jnp.cumsum(need.astype(jnp.int32), axis=1) - 1
        w = w_flat[jnp.clip(kidx, 0, None) + row_off_w]
        x2 = jnp.where(need, (x2 << jnp.uint32(16)) | w.astype(jnp.uint32), x2)
        pos = pos + need.sum(axis=1, dtype=jnp.int32)
        x = jnp.where(a, x2, x)
        return (x, pos), s.astype(jnp.uint8)

    (_, _), syms = jax.lax.scan(body, (states, pos0), act, unroll=unroll)
    return syms
