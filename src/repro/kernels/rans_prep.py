"""Device preparation of a rectangular rANS encode job.

``core.entropy._rans_encode_batch`` codes S equal-length residual streams:
each is zigzagged around its median, split into 8-bit planes, and every
(stream, plane) row is coded with its own normalized histogram.  For a job
whose values all lie in ``[-LIMIT, LIMIT)`` (LIMIT = 2^30) that front end
runs here, on the device, so the data crosses to the chip once, as one
int32 matrix, and only the per-stream medians and maxima and the per-row
histograms come back:

* ``_prep`` — each row's median with ``entropy._median_i64``'s exact
  semantics (the mean of the two middle values, truncated toward zero),
  found by bisection on the value (31 counting passes, no sort), then the
  zigzag around it (it fits 32 bits: at most 4 planes) and its maximum;
* ``_planes`` — the plane-major (stream, plane) rows gathered into the
  ``[T, Rp, K]`` symbol cube ``kernels.rans.encode_cube`` scans, and their
  ``[Rp, 256]`` histograms.

Streams and columns pad to ``rans._bucket`` sizes, so a workload compiles
a bounded set of programs.  Padded columns hold ``_PAD``, which no
bisection step counts, and become the identity symbol in the cube, which
no histogram counts; padded rows are all identity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .rans import _ID, _K, _bucket

__all__ = ["LIMIT", "fits", "prepare"]

LIMIT = 1 << 30
_PAD = np.iinfo(np.int32).max


def fits(qs: np.ndarray) -> bool:
    """True when every value of ``qs`` lies in [-LIMIT, LIMIT): the median,
    the differences from it and their zigzag then fit 32 bits."""
    return qs.size > 0 and int(qs.min()) >= -LIMIT and int(qs.max()) < LIMIT


@jax.jit
def _prep(q: jax.Array, n: jax.Array):
    """q[S, C] int32, columns >= n holding _PAD -> (med[S] int32,
    zmax[S] uint32, zz[S, C] uint32: the zigzag of q - med, 0 past n)."""
    lo = jnp.full(q.shape[:1], -LIMIT, jnp.int32)
    hi = jnp.full(q.shape[:1], LIMIT - 1, jnp.int32)
    k = (n - 1) // 2

    def halve(_, bounds):
        # the k-th smallest value (0-based) is the least v with more than k
        # values <= v; [lo, hi] holds it and halves every pass
        lo, hi = bounds
        mid = lo + ((hi - lo) >> 1)
        ok = jnp.sum(q <= mid[:, None], axis=1, dtype=jnp.int32) > k
        return jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)

    a = jax.lax.fori_loop(0, 31, halve, (lo, hi))[0]
    # the upper middle value: a itself when it repeats past n // 2, else
    # the least value above a
    repeats = jnp.sum(q <= a[:, None], axis=1, dtype=jnp.int32) > n // 2
    above = jnp.min(jnp.where(q > a[:, None], q, _PAD), axis=1)
    b = jnp.where(repeats, a, above)
    med = jax.lax.div(a + b, jnp.int32(2))  # truncates toward zero
    live = jnp.arange(q.shape[1])[None, :] < n
    d = jnp.where(live, q - med[:, None], 0)
    zz = jax.lax.bitcast_convert_type((d << 1) ^ (d >> 31), jnp.uint32)
    return med, zz.max(axis=1), zz


@jax.jit
def _planes(zz: jax.Array, src: jax.Array, shift: jax.Array, n: jax.Array, r: jax.Array):
    """zz[S, C] uint32; src/shift[Rp]: row i is byte plane shift[i] / 8 of
    stream src[i], for the first r rows -> (cube[C / K, Rp, K] int32,
    counts[Rp, 256] int32)."""
    rp, c = src.shape[0], zz.shape[1]
    sym = ((zz[src] >> shift[:, None]) & jnp.uint32(0xFF)).astype(jnp.int32)
    live = (jnp.arange(c)[None, :] < n) & (jnp.arange(rp)[:, None] < r)
    sym = jnp.where(live, sym, _ID)
    counts = jnp.sum(
        sym[:, :, None] == jnp.arange(256, dtype=jnp.int32), axis=1, dtype=jnp.int32
    )
    return sym.reshape(rp, c // _K, _K).transpose(1, 0, 2), counts


def prepare(qs: np.ndarray):
    """The device front end of a rectangular job: qs[S, n] integers with
    ``fits(qs)`` and n >= K.  Returns (med[S] int64, nplanes[S] int64,
    src[R], the stream of each plane-major row — planes ascending, streams
    ascending within a plane — the device-resident ``[T, Rp, K]`` symbol
    cube of those rows, and counts[R, 256], their histograms)."""
    s, n = qs.shape
    cols = _bucket(-(-n // _K)) * _K
    q = np.full((_bucket(s), cols), _PAD, dtype=np.int32)
    q[:s, :n] = qs
    with obs.span("device.rans_prep"):
        med, zmax, zz = _prep(jnp.asarray(q), jnp.int32(n))
        med, zmax = jax.device_get((med, zmax))
    zmax = zmax[:s]
    nplanes = 1 + (zmax >= 1 << 8) + (zmax >= 1 << 16) + (zmax >= 1 << 24)
    rows = [np.flatnonzero(nplanes > p) for p in range(int(nplanes.max()))]
    src = np.concatenate(rows)
    r = src.size
    src_p = np.zeros(_bucket(r), dtype=np.int32)
    shift_p = np.zeros(_bucket(r), dtype=np.uint32)
    src_p[:r] = src
    shift_p[:r] = np.repeat(8 * np.arange(len(rows)), [row.size for row in rows])
    with obs.span("device.rans_prep"):
        cube, counts = _planes(
            zz, jnp.asarray(src_p), jnp.asarray(shift_p), jnp.int32(n), jnp.int32(r)
        )
        counts = np.asarray(counts)[:r]
    return med[:s].astype(np.int64), nplanes.astype(np.int64), src, cube, counts
