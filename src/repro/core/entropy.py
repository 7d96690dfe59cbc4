"""Entropy-coding backends for SHRINK residual streams.

The paper uses Turbo Range Coder (an arithmetic coder).  This module provides:

* ``RangeEncoder`` / ``RangeDecoder`` — a carry-less (Subbotin-style) range
  coder with 32-bit state, byte renormalization.
* ``AdaptiveModel`` — order-0 adaptive frequency model over a bounded
  alphabet, Fenwick-tree cumulative frequencies (O(log A) per symbol).
* ``encode_ints`` / ``decode_ints`` — the production entry points used by the
  codec.  Residual integers are zigzag-mapped around their median and coded
  either with a single adaptive stream (small alphabets) or as split
  low-byte / high-part streams (large alphabets).  A ``zstd`` backend (stand
  -in for TRC's production speed) and a ``raw`` minimal-bit packer are also
  provided; ``backend='best'`` picks the smallest.
* ``rans`` — interleaved static-frequency rANS over byte planes.  Encode and
  decode are O(n) numpy array ops: one histogram/table pass, then a
  vectorized symbol loop over K interleaved 32-bit states (16-bit
  renormalization, one conditional emission per symbol).  This is the fast
  production path; the adaptive range coder stays as the compatibility /
  compression-oracle path.
* ``bitpack`` — tight fixed-width packing at ``span.bit_length()`` bits per
  value (0 bits for constant streams).  No statistical modelling, so it is
  never larger than ``raw`` and runs at memcpy-ish speed — the fast exit for
  low-entropy tails and near-uniform planes where rANS tables don't pay.
* ``backend='best'`` — adaptive dispatch: a one-pass cost model
  (:func:`predict_backend_sizes`) predicts each backend's encoded size from
  byte-plane histograms, a run-length probe, and the max-magnitude bit
  width, and the stream goes to the predicted winner
  (:func:`choose_backend`).  ``exhaustive=True`` restores the old
  encode-with-everything-keep-smallest oracle.  Selection is encode-side
  only — the tag byte keeps decode self-describing, so a mispredict can
  only cost bytes, never correctness.

All backends are lossless on int64 inputs and round-trip tested.
"""
from __future__ import annotations

import os
import struct

import numpy as np

from .errors import CorruptFrameError, FormatError, TruncatedArchiveError

try:  # optional fast backend
    import zstandard as _zstd
except Exception:  # pragma: no cover
    _zstd = None

__all__ = [
    "RangeEncoder",
    "RangeDecoder",
    "AdaptiveModel",
    "encode_ints",
    "decode_ints",
    "encode_ints_batch",
    "decode_ints_batch",
    "available_backends",
    "backend_name",
    "predict_backend_sizes",
    "choose_backend",
]

_MASK = 0xFFFFFFFF
_TOP = 1 << 24
_BOT = 1 << 16


class RangeEncoder:
    def __init__(self) -> None:
        self.low = 0
        self.rng = _MASK
        self.out = bytearray()

    def encode(self, cum_lo: int, freq: int, tot: int) -> None:
        r = self.rng // tot
        self.low = (self.low + r * cum_lo) & _MASK
        self.rng = r * freq
        low, rng, out = self.low, self.rng, self.out
        while True:
            if (low ^ (low + rng)) < _TOP:
                pass
            elif rng < _BOT:
                rng = (-low) & (_BOT - 1)
            else:
                break
            out.append((low >> 24) & 0xFF)
            low = (low << 8) & _MASK
            rng = (rng << 8) & _MASK
        self.low, self.rng = low, rng

    def finish(self) -> bytes:
        for _ in range(4):
            self.out.append((self.low >> 24) & 0xFF)
            self.low = (self.low << 8) & _MASK
        return bytes(self.out)


class RangeDecoder:
    def __init__(self, data: bytes) -> None:
        self.data = data
        self.pos = 4
        self.low = 0
        self.rng = _MASK
        code = 0
        for i in range(4):
            code = (code << 8) | (data[i] if i < len(data) else 0)
        self.code = code

    def decode_freq(self, tot: int) -> int:
        self._r = self.rng // tot
        v = (self.code - self.low) // self._r
        return min(v, tot - 1)

    def decode_update(self, cum_lo: int, freq: int, tot: int) -> None:
        r = self._r
        self.low = (self.low + r * cum_lo) & _MASK
        self.rng = r * freq
        low, rng, code = self.low, self.rng, self.code
        data, pos = self.data, self.pos
        while True:
            if (low ^ (low + rng)) < _TOP:
                pass
            elif rng < _BOT:
                rng = (-low) & (_BOT - 1)
            else:
                break
            nxt = data[pos] if pos < len(data) else 0
            pos += 1
            code = ((code << 8) | nxt) & _MASK
            low = (low << 8) & _MASK
            rng = (rng << 8) & _MASK
        self.low, self.rng, self.code, self.pos = low, rng, code, pos


class AdaptiveModel:
    """Order-0 adaptive model; Fenwick tree over symbol frequencies."""

    def __init__(self, nsym: int, inc: int = 24, max_total: int = 1 << 14) -> None:
        self.nsym = nsym
        self.inc = inc
        self.max_total = max_total
        self.freq = [1] * nsym
        self.total = nsym
        self.tree = [0] * (nsym + 1)
        for i in range(nsym):
            self._tree_add(i, 1)

    def _tree_add(self, i: int, delta: int) -> None:
        i += 1
        tree = self.tree
        while i <= self.nsym:
            tree[i] += delta
            i += i & (-i)

    def cum(self, i: int) -> int:
        """Sum of freq[0:i]."""
        s = 0
        tree = self.tree
        while i > 0:
            s += tree[i]
            i -= i & (-i)
        return s

    def find(self, target: int) -> int:
        """Largest i with cum(i) <= target; returns symbol index."""
        idx = 0
        bitmask = 1 << (self.nsym.bit_length())
        tree = self.tree
        rem = target
        while bitmask:
            nxt = idx + bitmask
            if nxt <= self.nsym and tree[nxt] <= rem:
                idx = nxt
                rem -= tree[nxt]
            bitmask >>= 1
        return idx  # freq[idx] spans [cum(idx), cum(idx)+freq[idx])

    def update(self, sym: int) -> None:
        self.freq[sym] += self.inc
        self.total += self.inc
        self._tree_add(sym, self.inc)
        if self.total > self.max_total:
            # halve all frequencies (keep >= 1), rebuild tree
            freq = self.freq
            tree = self.tree
            for i in range(len(tree)):
                tree[i] = 0
            tot = 0
            for i, f in enumerate(freq):
                nf = (f + 1) >> 1
                freq[i] = nf
                tot += nf
                self._tree_add(i, nf)
            self.total = tot

    def encode_symbol(self, enc: RangeEncoder, sym: int) -> None:
        cum_lo = self.cum(sym)
        enc.encode(cum_lo, self.freq[sym], self.total)
        self.update(sym)

    def decode_symbol(self, dec: RangeDecoder) -> int:
        target = dec.decode_freq(self.total)
        sym = self.find(target)
        cum_lo = self.cum(sym)
        dec.decode_update(cum_lo, self.freq[sym], self.total)
        self.update(sym)
        return sym


# ---------------------------------------------------------------------------
# integer-stream front end
# ---------------------------------------------------------------------------

def _zigzag(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, dtype=np.int64)
    # (x << 1) ^ (x >> 63): branch-free two's-complement zigzag, same values
    # as the where() formulation; the view is a free reinterpretation
    return ((x << 1) ^ (x >> 63)).view(np.uint64)


def _unzigzag(z: np.ndarray) -> np.ndarray:
    # inverse in uint64 space so full-range int64 values survive: the old
    # signed formulation wrapped for |x| >= 2^62
    z = np.asarray(z, dtype=np.uint64)
    half = (z >> np.uint64(1)).view(np.int64)
    sign = (z & np.uint64(1)).astype(np.int64)  # 0 or 1
    return half ^ -sign


def _rc_encode_stream(symbols: np.ndarray, nsym: int) -> bytes:
    enc = RangeEncoder()
    model = AdaptiveModel(nsym)
    es = model.encode_symbol
    for s in symbols.tolist():
        es(enc, s)
    return enc.finish()


def _rc_decode_stream(data: bytes, count: int, nsym: int) -> np.ndarray:
    dec = RangeDecoder(data)
    model = AdaptiveModel(nsym)
    ds = model.decode_symbol
    out = np.empty(count, dtype=np.int64)
    for i in range(count):
        out[i] = ds(dec)
    return out


_SPLIT_ALPHABET = 4096  # above this, split into low-byte + high streams


def _median_i64(q: np.ndarray, axis: int | None = None):
    """``np.median`` truncated to int64, as ``int()`` truncates it.  The
    float median of values near 2**63 rounds up past the int64 range, so it
    is clamped below 2**63 first; scalar and batched coders agree on it."""
    return np.clip(np.median(q, axis=axis), -(2.0**63), 2.0**63 - 1024).astype(np.int64)


def _rc_encode(q: np.ndarray) -> bytes:
    """Zigzag around the median, then byte-plane split until every adaptive
    stream's alphabet is <= _SPLIT_ALPHABET (keeps the Fenwick tree small
    even for pathological residual ranges)."""
    med = int(_median_i64(q)) if q.size else 0
    zz = _zigzag(q - med)
    zmax = int(zz.max()) if zz.size else 0
    planes: list[np.ndarray] = []
    while zmax >= _SPLIT_ALPHABET:
        planes.append((zz & np.uint64(0xFF)).astype(np.int64))
        zz = zz >> np.uint64(8)
        zmax >>= 8
    top = zz.astype(np.int64)
    header = struct.pack("<qQB", med, q.size, len(planes))
    parts = [header]
    for p in planes:
        blob = _rc_encode_stream(p, 256)
        parts.append(struct.pack("<Q", len(blob)))
        parts.append(blob)
    top_max = int(top.max()) if top.size else 0
    blob = _rc_encode_stream(top, top_max + 1)
    parts.append(struct.pack("<QQ", len(blob), top_max))
    parts.append(blob)
    return b"".join(parts)


def _rc_decode(data: bytes) -> np.ndarray:
    med, count, nplanes = struct.unpack_from("<qQB", data, 0)
    off = 17
    planes: list[np.ndarray] = []
    for _ in range(nplanes):
        (ln,) = struct.unpack_from("<Q", data, off)
        off += 8
        planes.append(_rc_decode_stream(data[off : off + ln], count, 256).astype(np.uint64))
        off += ln
    ln, top_max = struct.unpack_from("<QQ", data, off)
    off += 16
    top = _rc_decode_stream(data[off : off + ln], count, top_max + 1).astype(np.uint64)
    zz = top
    for p in reversed(planes):
        zz = (zz << np.uint64(8)) | p
    return _unzigzag(zz) + med


# ---------------------------------------------------------------------------
# interleaved static-frequency rANS (vectorized)
# ---------------------------------------------------------------------------
#
# Classic 32-bit rANS with 16-bit renormalization: states live in
# I = [2^16, 2^32) and the frequency tables are normalized to M = 2^12, so a
# single conditional 16-bit emission per symbol keeps the invariant (the
# standard "at most one renorm" argument: before the state transform
# x < freq << 20, hence after it x < 2^32; after one 16-bit shift x < 2^16).
#
# K states are interleaved round-robin across the symbol stream: symbol i
# belongs to lane i % K at step i // K.  The decoder walks steps forward and,
# within a step, renormalizing lanes read words in increasing lane order; the
# encoder walks steps backward (rANS is LIFO) emitting the same words, and
# the stream is assembled in decoder order.  Every per-step operation is a
# width-K numpy vector op, so a 50k-symbol stream costs ~n/K interpreted
# iterations instead of n.

_RANS_PROB_BITS = 12
_RANS_M = 1 << _RANS_PROB_BITS
_RANS_L = 1 << 16
_RANS_K = 64  # interleaved states
# ragged batch: max dense scratch cells (steps x rows x K, ~5 B/cell) before
# the encoder splits rows into step-count groups to bound memory
_RANS_DENSE_CELLS = 16 << 20

# ------------------------------------------------------------------ #
# device engine gating.  kernels/rans.py runs the same step machines as
# one fused XLA scan (lane axis = the K states) instead of ~n/K
# interpreted numpy dispatches; its wire bytes are identical, so routing
# is purely a perf decision, made from the job size alone:
#   SHRINK_RANS_DEVICE=0     never (numpy machine only)
#   SHRINK_RANS_DEVICE=1     always (parity tests)
#   unset / auto             engage at _RANS_DEVICE_MIN plane symbols
# An engine failure raises: nothing swaps the numpy coder in behind it.
_RANS_DEVICE_MIN = 1 << 14
# ragged mixes split into several padded group dispatches; on the CPU
# backend those only beat the zero-waste dense-prefix numpy machine for jobs
# big enough to amortize the per-dispatch fixed cost (measured: ~780k plane
# symbols over 5 groups lose ~15% to the numpy machine on one core)
_RANS_DEVICE_RAGGED_MIN_CPU = 4 << 20


def _rans_device(total_symbols: int):
    """The device rANS engine (``repro.kernels.rans``) for a job of
    ``total_symbols`` plane symbols, or ``None`` to run the numpy
    machine."""
    mode = os.environ.get("SHRINK_RANS_DEVICE", "auto")
    if mode == "0" or (mode != "1" and total_symbols < _RANS_DEVICE_MIN):
        return None
    from repro.kernels import rans as kernel_rans

    return kernel_rans


def _rans_plane_table(freqs: np.ndarray) -> bytes:
    """Wire bytes of one plane's frequency table: 32B presence bitmap +
    u16 freq per present symbol."""
    present = freqs > 0
    bitmap = np.packbits(present.astype(np.uint8), bitorder="little")
    return bitmap.tobytes() + freqs.astype("<u2")[present].tobytes()


def _rans_normalize_freqs(counts: np.ndarray) -> np.ndarray:
    """Scale histogram ``counts`` to sum exactly _RANS_M, keeping every
    present symbol's frequency >= 1.  Deterministic."""
    counts = counts.astype(np.int64)
    total = int(counts.sum())
    nz = counts > 0
    freqs = np.zeros_like(counts)
    if total == 0:
        return freqs
    freqs[nz] = np.maximum(1, np.rint(counts[nz] * (_RANS_M / total)).astype(np.int64))
    diff = _RANS_M - int(freqs.sum())
    if diff == 0:
        return freqs
    # distribute the rounding drift over the most frequent symbols (closed
    # form of the former round-robin loop, same output bytes):
    order = np.argsort(-counts, kind="stable")
    order = order[counts[order] > 0]
    if diff > 0:
        # +1 round-robin over `order`: everyone gets diff // len, the first
        # diff % len symbols one more
        add, rem = divmod(diff, order.size)
        freqs[order] += add
        freqs[order[:rem]] += 1
    else:
        # greedy steal in `order`: each donor gives at most freq - 1, so no
        # present symbol ever drops to 0.  A deficit means sum > M, which
        # guarantees total donor capacity covers it — assert the invariant
        # rather than silently under-stealing.
        caps = freqs[order] - 1
        cum = np.cumsum(caps)
        if int(cum[-1]) < -diff:
            raise AssertionError(
                "rANS freq normalization stalled: deficit exceeds donor "
                "capacity (histogram invariant violated)"
            )
        freqs[order] -= np.clip(-diff - (cum - caps), 0, caps)
    return freqs


def _rans_normalize_freqs_rows(counts: np.ndarray) -> np.ndarray:
    """Row-vectorized ``_rans_normalize_freqs``: normalize an [R, 256]
    histogram matrix in one pass, byte-identical per row to the scalar
    function.  The batched encoders call this once per row group instead
    of paying R python round-trips."""
    counts = counts.astype(np.int64)
    totals = counts.sum(axis=1)
    nz = counts > 0
    scale = _RANS_M / np.maximum(totals, 1).astype(np.float64)
    scaled = np.rint(counts * scale[:, None]).astype(np.int64)
    freqs = np.where(nz, np.maximum(1, scaled), 0)
    diff = _RANS_M - freqs.sum(axis=1)
    if not diff.any():
        return np.where(totals[:, None] > 0, freqs, 0)
    # ordered space: most-frequent first (stable), absent symbols last
    order = np.argsort(-counts, axis=1, kind="stable")
    freqs_ord = np.take_along_axis(freqs, order, axis=1)
    npres = nz.sum(axis=1)
    pos = np.arange(256)[None, :]
    present_pref = pos < npres[:, None]
    surplus = diff > 0
    deficit = diff < 0
    # surplus rows: +1 round-robin over the present prefix
    np1 = np.maximum(npres, 1)
    addv = np.where(surplus, diff // np1, 0)
    remv = np.where(surplus, diff % np1, 0)
    inc = present_pref * addv[:, None] + (pos < remv[:, None])
    # deficit rows: greedy steal, each donor gives at most freq - 1
    caps = np.where(present_pref, freqs_ord - 1, 0)
    cum = np.cumsum(caps, axis=1)
    need = np.where(deficit, -diff, 0)
    if (need > cum[:, -1]).any():
        raise AssertionError(
            "rANS freq normalization stalled: deficit exceeds donor "
            "capacity (histogram invariant violated)"
        )
    steal = np.clip(need[:, None] - (cum - caps), 0, caps)
    delta = np.where(surplus[:, None], inc, -steal)
    np.put_along_axis(freqs, order, freqs_ord + delta, axis=1)
    return np.where(totals[:, None] > 0, freqs, 0)


def _rans_encode_plane(sym: np.ndarray, freqs: np.ndarray, cums: np.ndarray, k: int) -> bytes:
    """Encode uint8/int64 symbols (< 256) with the given normalized tables.
    Returns states (K u32) + word count (u32) + words (u16 each)."""
    n = int(sym.size)
    steps = -(-n // k) if n else 0
    tail = n - (steps - 1) * k if steps else 0  # active lanes in last step
    f_of = freqs[sym].astype(np.uint64)
    c_of = cums[sym].astype(np.uint64)
    x = np.full(k, _RANS_L, dtype=np.uint64)
    chunks: list[np.ndarray] = []
    for t in range(steps - 1, -1, -1):
        a = tail if t == steps - 1 else k
        lo = t * k
        f = f_of[lo : lo + a]
        c = c_of[lo : lo + a]
        xa = x[:a]
        need = xa >= (f << np.uint64(32 - _RANS_PROB_BITS))
        if need.any():
            chunks.append((xa[need] & np.uint64(0xFFFF)).astype(np.uint16))
            xa = np.where(need, xa >> np.uint64(16), xa)
        x[:a] = ((xa // f) << np.uint64(_RANS_PROB_BITS)) + (xa % f) + c
    words = (
        np.concatenate(chunks[::-1]) if chunks else np.zeros(0, dtype=np.uint16)
    )
    out = bytearray()
    out += x.astype("<u4").tobytes()
    out += struct.pack("<I", words.size)
    out += words.astype("<u2").tobytes()
    return bytes(out)


def _rans_decode_plane(
    data: bytes, off: int, n: int, freqs: np.ndarray, cums: np.ndarray, k: int
) -> tuple[np.ndarray, int]:
    """Inverse of _rans_encode_plane; returns (symbols int64 [n], new off)."""
    x = np.frombuffer(data, dtype="<u4", count=k, offset=off).astype(np.uint64)
    off += 4 * k
    (nwords,) = struct.unpack_from("<I", data, off)
    off += 4
    words = np.frombuffer(data, dtype="<u2", count=nwords, offset=off).astype(np.uint64)
    off += 2 * nwords
    slot2sym = np.repeat(
        np.arange(freqs.size, dtype=np.int64), freqs.astype(np.int64)
    )
    f64 = freqs.astype(np.uint64)
    c64 = cums.astype(np.uint64)
    steps = -(-n // k) if n else 0
    tail = n - (steps - 1) * k if steps else 0
    out = np.empty(n, dtype=np.int64)
    pos = 0
    mask = np.uint64(_RANS_M - 1)
    for t in range(steps):
        a = tail if t == steps - 1 else k
        xa = x[:a]
        slot = xa & mask
        s = slot2sym[slot]
        out[t * k : t * k + a] = s
        xa = f64[s] * (xa >> np.uint64(_RANS_PROB_BITS)) + slot - c64[s]
        need = xa < _RANS_L
        cnt = int(need.sum())
        if cnt:
            w = np.zeros(a, dtype=np.uint64)
            w[need] = words[pos : pos + cnt]
            xa = np.where(need, (xa << np.uint64(16)) | w, xa)
            pos += cnt
        x[:a] = xa
    return out, off


def _rans_encode(q: np.ndarray) -> bytes:
    """Zigzag around the median, split into 8-bit planes, rANS-code each
    plane with its own static table.  Layout:

        i64 med, u64 count, u8 nplanes
        per plane: 32B presence bitmap, u16 freq per present symbol,
                   K u32 states, u32 nwords, u16 words
    """
    med = int(_median_i64(q)) if q.size else 0
    zz = _zigzag(q - med)
    zmax = int(zz.max()) if zz.size else 0
    nplanes = max(1, (zmax.bit_length() + 7) // 8)
    k = max(1, min(_RANS_K, q.size))  # fewer states -> less header on tiny streams
    parts = [struct.pack("<qQBB", med, q.size, nplanes, k)]
    eng = _rans_device(q.size * nplanes) if k == _RANS_K else None
    if eng is not None:
        # one fused device call over all planes (planes = machine rows)
        sym_mat = np.empty((nplanes, q.size), dtype=np.int32)
        freqs_mat = np.empty((nplanes, 256), dtype=np.int64)
        for p in range(nplanes):
            np.copyto(
                sym_mat[p], (zz >> np.uint64(8 * p)) & np.uint64(0xFF),
                casting="unsafe",
            )
            freqs_mat[p] = _rans_normalize_freqs(
                np.bincount(sym_mat[p], minlength=256)
            )
        states, words_list = eng.encode_rows(sym_mat, freqs_mat)
        for p in range(nplanes):
            words = words_list[p]
            parts.append(_rans_plane_table(freqs_mat[p]))
            parts.append(states[p].astype("<u4").tobytes())
            parts.append(struct.pack("<I", words.size))
            parts.append(words.astype("<u2").tobytes())
        return b"".join(parts)
    for p in range(nplanes):
        sym = ((zz >> np.uint64(8 * p)) & np.uint64(0xFF)).astype(np.int64)
        counts = np.bincount(sym, minlength=256)
        freqs = _rans_normalize_freqs(counts)
        cums = np.concatenate(([0], np.cumsum(freqs)[:-1]))
        parts.append(_rans_plane_table(freqs))
        parts.append(_rans_encode_plane(sym, freqs, cums, k))
    return b"".join(parts)


def _rans_encode_batch(qs: np.ndarray) -> list[bytes]:
    """Encode S equal-length int64 streams at once; returns one blob per
    row, each byte-identical to ``_rans_encode(qs[s])``.

    Every (series, plane) pair is one row of a single interleaved state
    machine, so the step loop runs once for the whole batch.  A job on the
    device engine whose values all lie in ``[-2^30, 2^30)`` is prepared on
    the device too (``kernels.rans_prep``: median, zigzag, planes and
    histograms), and only the histograms' normalization runs here; any
    other job is prepared by ``_rans_prep_rect``."""
    qs = np.ascontiguousarray(qs, dtype=np.int64)
    s_count, n = qs.shape
    if s_count == 0:
        return []
    k = max(1, min(_RANS_K, n))
    eng = _rans_device(s_count * n) if k == _RANS_K else None
    if eng is not None:
        from repro.kernels import rans_prep
    if eng is not None and rans_prep.fits(qs):
        med, nplanes, src, cube, counts = rans_prep.prepare(qs)
        freqs = _rans_normalize_freqs_rows(counts)
        states, words_list = eng.encode_cube(cube, freqs, src.size * n)
        prepared = src.size
    else:
        med, nplanes, src, sym, counts = _rans_prep_rect(qs)
        freqs = _rans_normalize_freqs_rows(counts)
        # a job too small to engage by its series may engage by its rows
        eng = _rans_device(sym.size) if k == _RANS_K else None
        if eng is not None:
            states, words_list = eng.encode_rows(sym, freqs)
        else:
            states, words_list = _rans_machine_rect(sym, freqs, k)
        prepared = 0
    if eng is not None:
        from repro.kernels.calls import note_cells  # lazy, as _rans_device

        note_cells("rans_prep", prepared, src.size)
    parts: list[list[bytes]] = [
        [struct.pack("<qQBB", int(med[i]), n, int(nplanes[i]), k)]
        for i in range(s_count)
    ]
    states32 = states.astype("<u4")
    freqs16 = freqs.astype("<u2")
    presents = freqs > 0
    bitmaps = np.packbits(presents, axis=1, bitorder="little")
    native_le = np.little_endian
    for i, s in enumerate(src.tolist()):
        words = words_list[i]
        parts[s].append(bitmaps[i].tobytes())
        parts[s].append(freqs16[i][presents[i]].tobytes())
        parts[s].append(states32[i].tobytes())
        parts[s].append(struct.pack("<I", words.size))
        parts[s].append(words.tobytes() if native_le else words.astype("<u2").tobytes())
    return [b"".join(p) for p in parts]


def _rans_prep_rect(qs: np.ndarray):
    """The host front end of ``_rans_encode_batch``: (med[S], nplanes[S],
    src[R], sym[R, n] int32, counts[R, 256]) for the plane-major rows —
    planes ascending, series ascending within a plane, so each series'
    plane bodies are appended in ascending plane order."""
    s_count, n = qs.shape
    med = _median_i64(qs, axis=1) if n else np.zeros(s_count, np.int64)
    zz = _zigzag(qs - med[:, None])
    zmax = zz.max(axis=1) if n else np.zeros(s_count, np.uint64)
    nplanes = np.array(
        [max(1, (int(z).bit_length() + 7) // 8) for z in zmax], dtype=np.int64
    )
    srcs = []
    sym_blocks = []
    for p in range(int(nplanes.max())):
        sel = np.flatnonzero(nplanes > p)
        srcs.append(sel)
        zsel = zz if sel.size == s_count else zz[sel]
        plane = zsel if p == 0 else zsel >> np.uint64(8 * p)
        # int32 symbols: half the memory traffic of int64 through the
        # histogram and the device cube
        sym_blocks.append((plane & np.uint64(0xFF)).astype(np.int32))
    src = np.concatenate(srcs)
    sym = np.concatenate(sym_blocks, axis=0) if len(srcs) > 1 else sym_blocks[0]
    offsets = np.arange(src.size, dtype=np.int32)[:, None] * 256
    counts = np.bincount((sym + offsets).ravel(), minlength=256 * src.size)
    return med, nplanes, src, sym, counts.reshape(src.size, 256)


def _rans_machine_rect(sym: np.ndarray, freqs: np.ndarray, k: int):
    """The numpy step machine over R equal-length symbol rows: (states[R,
    k] uint32, each row's words in decoder order)."""
    r_count, n = sym.shape
    steps = -(-n // k)
    tail = n - (steps - 1) * k
    cums = np.zeros_like(freqs)
    np.cumsum(freqs[:, :-1], axis=1, out=cums[:, 1:])
    flat_idx = sym + np.arange(r_count, dtype=np.int32)[:, None] * 256

    # All loop state fits in uint32 (x < 2^32, freq <= 2^12): half the
    # memory traffic of a uint64 machine.  Lay the lookups out
    # [steps, R, k] so each step reads a contiguous block.
    def _per_step(table: np.ndarray) -> np.ndarray:
        flat = np.take(table.astype(np.uint32).ravel(), flat_idx)
        if n < steps * k:
            flat = np.pad(flat, ((0, 0), (0, steps * k - n)), constant_values=1)
        return np.ascontiguousarray(
            flat.reshape(r_count, steps, k).transpose(1, 0, 2)
        )

    f3 = _per_step(freqs)
    c3 = _per_step(cums)
    # renorm threshold minus one: x >= f << 20  <=>  x > (f << 20) - 1.
    # For f == 2^12 the shift wraps to 0 and the -1 to 0xFFFFFFFF, which
    # a uint32 state can never exceed — exactly the "never renormalize"
    # semantics the uint64 single-stream coder gets for a whole-table
    # symbol.
    f3_renorm_m1 = (f3 << np.uint32(32 - _RANS_PROB_BITS)) - np.uint32(1)
    sh16 = np.uint32(16)
    sh_prob = np.uint32(_RANS_PROB_BITS)
    x = np.full((r_count, k), _RANS_L, dtype=np.uint32)
    masks = np.zeros((steps, r_count, k), dtype=bool)
    vals = np.zeros((steps, r_count, k), dtype=np.uint16)
    for t in range(steps - 1, -1, -1):
        a = tail if t == steps - 1 else k
        f = f3[t, :, :a]
        xa = x[:, :a]
        need = xa > f3_renorm_m1[t, :, :a]
        masks[t, :, :a] = need
        np.copyto(vals[t, :, :a], xa, casting="unsafe")  # truncating low-16 store
        xa = np.where(need, xa >> sh16, xa)
        div, rem = np.divmod(xa, f)
        x[:, :a] = (div << sh_prob) + rem + c3[t, :, :a]
    # masks/vals are indexed by decode step already, so flat boolean
    # extraction yields decoder order per row: steps asc, lanes asc
    need_t = np.ascontiguousarray(masks.transpose(1, 0, 2))
    flat_w = np.ascontiguousarray(vals.transpose(1, 0, 2))[need_t]
    wcounts = need_t.reshape(r_count, -1).sum(axis=1)
    return x, np.split(flat_w, np.cumsum(wcounts)[:-1])


def _rans_encode_batch_ragged(qs: list[np.ndarray]) -> list[bytes]:
    """Ragged companion to ``_rans_encode_batch``: one blob per stream, each
    byte-identical to ``_rans_encode(qs[i])``, for streams of ANY mix of
    lengths.

    Streams shorter than the full interleave width (n < K) use fewer rANS
    states (the scalar coder's small-stream header saving) and are encoded
    by the scalar path — they are tiny by definition.  The remaining
    (stream, plane) rows run through a shared state machine with no
    per-step masking:

    * rows are sorted by step count so each step operates on the dense
      prefix of still-active rows — total state-machine work is
      sum_r steps_r * K, no row pays for a longer row's symbols;
    * the scratch cube (symbols + renorm masks/words) is dense over
      [max_steps, rows, K]; when a skewed length mix would blow it past
      ``_RANS_DENSE_CELLS`` (one huge stream among many short ones), rows
      are split into power-of-two step-count groups, each padded only to
      its own longest row — memory then stays proportional to the REAL
      symbol total (within 2x) at the cost of one extra set of loop
      iterations, which only the pathological mixes pay;
    * the device engine instead runs every row in a fixed block of its
      step class (``kernels.rans.ragged_blocks``): steps padded to the next
      power of two, at least 32, and 256 rows a block up to 512 steps.  The
      shapes it dispatches then come from a set fixed by the job's bounds,
      not by which lengths happen to meet in one job, so ragged traffic
      whose lengths drift from flush to flush (backlogs arriving) compiles
      while warming up and not while serving.  The price is up to 2x of
      step padding and a partly filled last block per class;
    * padded lane positions carry the **identity symbol** (freq = M = 2^12,
      cum = 0): the rANS transform x -> (x//f << PROB) + x%f + c is then
      exactly x, and the renorm threshold (f << 20) - 1 wraps to the uint32
      max so no word is ever emitted — a padded lane is a true no-op, and
      the inner loop stays byte-for-byte the rectangular machine's."""
    out: list[bytes | None] = [None] * len(qs)
    big: list[int] = []
    for i, q in enumerate(qs):
        if q.size < _RANS_K:
            out[i] = _rans_encode(q)
        else:
            big.append(i)
    if not big:
        return out
    k = _RANS_K
    meds = {}
    zzs = {}
    npls = {}
    # equal-length streams (e.g. the pyramid layers of one series, or
    # same-length series in a batch) share one vectorized median/zigzag
    # pass — one partition per length group instead of one python
    # round-trip per stream
    by_len: dict[int, list[int]] = {}
    for i in big:
        by_len.setdefault(qs[i].size, []).append(i)
    for idxs in by_len.values():
        if len(idxs) == 1:
            i = idxs[0]
            med = int(_median_i64(qs[i]))
            zz = _zigzag(qs[i] - med)
            meds[i], zzs[i] = med, zz
            npls[i] = max(1, (int(zz.max()).bit_length() + 7) // 8)
        else:
            qstack = np.stack([qs[i] for i in idxs])
            gm = _median_i64(qstack, axis=1)
            zzm = _zigzag(qstack - gm[:, None])
            zmaxs = zzm.max(axis=1)
            for row, i in enumerate(idxs):
                meds[i] = int(gm[row])
                zzs[i] = zzm[row]
                npls[i] = max(1, (int(zmaxs[row]).bit_length() + 7) // 8)
    rows: list[tuple[int, int]] = []  # (stream index, plane), plane-ascending
    syms: list[np.ndarray] = []
    for i in big:
        zz = zzs[i]
        for p in range(npls[i]):
            rows.append((i, p))
            syms.append(((zz >> np.uint64(8 * p)) & np.uint64(0xFF)).astype(np.int64))
    r_count = len(rows)
    ns = np.array([sy.size for sy in syms], dtype=np.int64)
    steps_r = -(-ns // k)
    # per-row outputs, indexed by global row id
    row_freqs: list[np.ndarray] = [None] * r_count  # type: ignore[list-item]
    row_states: list[bytes] = [b""] * r_count
    row_words: list[np.ndarray] = [None] * r_count  # type: ignore[list-item]
    # The device engine runs rows in fixed blocks of their step class
    # (``ragged_blocks``), so the set of programs depends on the job's
    # bounds and not on which lengths meet in it.  The numpy machine's
    # dense-prefix loop does no padded work, so it only splits when the
    # scratch cube would blow past _RANS_DENSE_CELLS.
    eng = _rans_device(int(ns.sum()))
    if (
        eng is not None
        and not eng.on_tpu()
        and os.environ.get("SHRINK_RANS_DEVICE") != "1"
        and int(ns.sum()) < _RANS_DEVICE_RAGGED_MIN_CPU
    ):
        # CPU backend: a ragged mix means SEVERAL padded group dispatches,
        # and the dense-prefix numpy machine (zero padded work, one pass)
        # beats them below this size.  On the TPU the engine takes every
        # job past _RANS_DEVICE_MIN; forced mode ("1") keeps parity tests
        # on-engine.
        eng = None
    if eng is not None:
        groups = eng.ragged_blocks(steps_r)
    elif int(steps_r.max()) * r_count * k <= _RANS_DENSE_CELLS:
        groups = [np.arange(r_count)]  # one dense machine: zero work waste
    else:
        # geometric step-count groups: within a group max <= 2 * min steps
        group_of = np.array([int(s).bit_length() for s in steps_r])
        groups = [np.flatnonzero(group_of == g) for g in np.unique(group_of)]
    for ids in groups:
        _rans_encode_row_group(
            [syms[r] for r in ids], ids, ns, steps_r, k,
            row_freqs, row_states, row_words, eng=eng,
        )
    native_le = np.little_endian
    parts: dict[int, list[bytes]] = {
        i: [struct.pack("<qQBB", meds[i], qs[i].size,
                        max(1, (int(zzs[i].max()).bit_length() + 7) // 8), k)]
        for i in big
    }
    freqs_all = np.stack(row_freqs)
    present_all = freqs_all > 0
    bitmaps = np.packbits(present_all, axis=1, bitorder="little")
    freqs16 = freqs_all.astype("<u2")
    for r in range(r_count):  # original order: planes ascending per stream
        i, _p = rows[r]
        words = row_words[r]
        parts[i].append(bitmaps[r].tobytes())
        parts[i].append(freqs16[r][present_all[r]].tobytes())
        parts[i].append(row_states[r])
        parts[i].append(struct.pack("<I", words.size))
        parts[i].append(words.tobytes() if native_le else words.astype("<u2").tobytes())
    for i in big:
        out[i] = b"".join(parts[i])
    return out


def _rans_encode_row_group(
    group_syms: list[np.ndarray],
    group_ids: np.ndarray,
    ns: np.ndarray,
    steps_r: np.ndarray,
    k: int,
    row_freqs: list,
    row_states: list,
    row_words: list,
    eng=None,
) -> None:
    """Run the interleaved state machine for one group of (stream, plane)
    rows of ``ns`` symbols and ``steps_r`` steps each; results land in the
    per-row output lists (see ``_rans_encode_batch_ragged`` for the
    grouping/identity-symbol scheme).  When ``eng`` (the device engine) is
    given, the whole group runs as one fused device call at its class's
    shape."""
    r_count = len(group_ids)
    order = np.argsort(-steps_r[group_ids], kind="stable")  # longest first
    steps_sorted = steps_r[group_ids][order]
    max_steps = int(steps_sorted[0])

    # per-row tables with a reserved 257th entry: the identity symbol
    # (freq = M, cum = 0) that padded lane positions carry
    _ID = 256
    counts = np.empty((r_count, 256), dtype=np.int64)
    sym_mat = np.full((r_count, max_steps * k), _ID, dtype=np.uint16)
    for pos, j in enumerate(order):
        sy = group_syms[j]
        counts[pos] = np.bincount(sy, minlength=256)
        sym_mat[pos, : sy.size] = sy
    freqs = _rans_normalize_freqs_rows(counts)
    if eng is not None:
        states_dev, words_list = eng.encode_rows(
            sym_mat, freqs, lengths=ns[group_ids][order]
        )
        states32 = states_dev.astype("<u4")
        for pos, j in enumerate(order):
            r = int(group_ids[j])
            row_freqs[r] = freqs[pos]
            row_states[r] = states32[pos].tobytes()
            row_words[r] = words_list[pos]
        return
    cums = np.zeros_like(freqs)
    np.cumsum(freqs[:, :-1], axis=1, out=cums[:, 1:])
    f_ext = np.full((r_count, 257), _RANS_M, dtype=np.uint32)
    f_ext[:, :256] = freqs
    c_ext = np.zeros((r_count, 257), dtype=np.uint32)
    c_ext[:, :256] = cums
    f_flat, c_flat = f_ext.ravel(), c_ext.ravel()
    row_off = np.arange(r_count, dtype=np.intp)[:, None] * 257
    # rows active at step t form the sorted prefix [:nr_per_t[t]]
    nr_per_t = np.count_nonzero(
        steps_sorted[None, :] > np.arange(max_steps)[:, None], axis=1
    )
    sh16 = np.uint32(16)
    sh_prob = np.uint32(_RANS_PROB_BITS)
    x = np.full((r_count, k), _RANS_L, dtype=np.uint32)
    masks = np.zeros((max_steps, r_count, k), dtype=bool)
    vals = np.zeros((max_steps, r_count, k), dtype=np.uint16)
    for t in range(max_steps - 1, -1, -1):
        nr = int(nr_per_t[t])
        idx = sym_mat[:nr, t * k : (t + 1) * k] + row_off[:nr]
        f = f_flat[idx]
        c = c_flat[idx]
        xa = x[:nr]
        # same uint32-wrap trick as the rectangular machine: f == 2^12 (the
        # identity symbol included) shifts to 0 and the -1 wraps to the
        # uint32 max -> "never renormalize"
        need = xa > (f << np.uint32(32 - _RANS_PROB_BITS)) - np.uint32(1)
        masks[t, :nr] = need
        np.copyto(vals[t, :nr], xa, casting="unsafe")  # truncating low-16 store
        xa = np.where(need, xa >> sh16, xa)
        div, rem = np.divmod(xa, f)
        x[:nr] = (div << sh_prob) + rem + c
    states32 = x.astype("<u4")
    for pos, j in enumerate(order):
        r = int(group_ids[j])
        row_freqs[r] = freqs[pos]
        row_states[r] = states32[pos].tobytes()
        row_words[r] = vals[:, pos, :][masks[:, pos, :]]  # steps asc, lanes asc
    from repro.kernels.calls import note_cells  # lazy, as _rans_device

    # the dense-prefix loop runs each row for its own steps: no padded rows
    note_cells("rans_encode", int(ns[group_ids].sum()), int(steps_sorted.sum()) * k)


def encode_ints_batch(
    qs: np.ndarray | list[np.ndarray], backend: str = "rans"
) -> list[bytes]:
    """Batched ``encode_ints`` over rows qs — an [S, n] array (equal-length
    rows) or a list of 1-D arrays (ragged); each returned blob is
    byte-identical to ``encode_ints(qs[s], backend)``.  ``rans`` runs the
    genuinely fused state machines; ``best`` partitions the batch by the
    cost model's per-stream pick and keeps the rans-bound group on those
    same machines; ``zstd`` shares one compressor context across the
    batch; everything else falls back to a per-row loop."""
    if isinstance(qs, np.ndarray):
        qs = np.ascontiguousarray(qs, dtype=np.int64)
        if qs.ndim != 2:
            raise ValueError(f"expected [S, n], got shape {qs.shape}")
        if backend == "rans":
            tag = bytes([_BACKENDS["rans"]])
            return [tag + blob for blob in _rans_encode_batch(qs)]
        arrs = list(qs)  # row views: contiguous int64 by construction
    else:
        arrs = [
            q
            if isinstance(q, np.ndarray)
            and q.ndim == 1
            and q.dtype == np.int64
            and q.flags.c_contiguous
            else np.ascontiguousarray(np.asarray(q).ravel(), dtype=np.int64)
            for q in qs
        ]
    if not arrs:
        return []
    if backend == "rans":
        n0 = arrs[0].size
        if all(a.size == n0 for a in arrs):  # rectangular in disguise
            return encode_ints_batch(np.stack(arrs), backend=backend)
        tag = bytes([_BACKENDS["rans"]])
        return [tag + blob for blob in _rans_encode_batch_ragged(arrs)]
    if backend == "best":
        return _adaptive_encode_batch(arrs)
    if backend == "zstd" and _zstd is not None:
        ctx = _zstd.ZstdCompressor(level=19)
        tag = bytes([_BACKENDS["zstd"]])
        return [tag + _zstd_encode(q, compressor=ctx) for q in arrs]
    return [encode_ints(q, backend=backend) for q in arrs]


def _rans_decode(data: bytes) -> np.ndarray:
    med, count, nplanes, k = struct.unpack_from("<qQBB", data, 0)
    eng = _rans_device(count * nplanes) if k == _RANS_K else None
    if eng is not None:
        return _rans_decode_device(data, med, count, nplanes, k, eng)
    off = 18
    zz = np.zeros(count, dtype=np.uint64)
    for p in range(nplanes):
        freqs, off = _rans_read_plane_table(data, off)
        cums = np.concatenate(([0], np.cumsum(freqs)[:-1]))
        sym, off = _rans_decode_plane(data, off, count, freqs, cums, k)
        zz |= sym.astype(np.uint64) << np.uint64(8 * p)
    return _unzigzag(zz) + med


def _rans_read_plane_table(data: bytes, off: int) -> tuple[np.ndarray, int]:
    """Read one plane's frequency table (32B presence bitmap + u16 per
    present symbol); returns (freqs int64 [256], new off)."""
    bitmap = np.frombuffer(data, dtype=np.uint8, count=32, offset=off)
    off += 32
    present = np.unpackbits(bitmap, bitorder="little").astype(bool)
    npresent = int(present.sum())
    freqs = np.zeros(256, dtype=np.int64)
    freqs[present] = np.frombuffer(data, dtype="<u2", count=npresent, offset=off)
    off += 2 * npresent
    return freqs, off


def _rans_decode_device(
    data: bytes, med: int, count: int, nplanes: int, k: int, eng
) -> np.ndarray:
    """Device decode: walk every plane's header on the host, then run all
    planes through one fused device scan (planes = machine rows)."""
    freqs_mat = np.empty((nplanes, 256), dtype=np.int64)
    states = np.empty((nplanes, k), dtype=np.uint32)
    words_list: list[np.ndarray] = []
    off = 18
    for p in range(nplanes):
        freqs_mat[p], off = _rans_read_plane_table(data, off)
        states[p] = np.frombuffer(data, dtype="<u4", count=k, offset=off)
        off += 4 * k
        (nwords,) = struct.unpack_from("<I", data, off)
        off += 4
        words_list.append(
            np.frombuffer(data, dtype="<u2", count=nwords, offset=off)
        )
        off += 2 * nwords
    syms = eng.decode_rows(states, freqs_mat, words_list, count)
    zz = np.zeros(count, dtype=np.uint64)
    for p in range(nplanes):
        zz |= syms[p].astype(np.uint64) << np.uint64(8 * p)
    return _unzigzag(zz) + med


def _raw_encode(q: np.ndarray) -> bytes:
    """Minimal-width bit packing (no statistical modelling)."""
    lo = int(q.min()) if q.size else 0
    span = (int(q.max()) - lo + 1) if q.size else 1
    bits = max(1, int(span - 1).bit_length()) if span > 1 else 1
    vals = (q - lo).astype(np.uint64)
    header = struct.pack("<qQB", lo, q.size, bits)
    # pack with numpy: expand to bit matrix
    bitmat = ((vals[:, None] >> np.arange(bits, dtype=np.uint64)) & 1).astype(np.uint8)
    packed = np.packbits(bitmat.reshape(-1))
    return header + packed.tobytes()


def _raw_decode(data: bytes) -> np.ndarray:
    lo, count, bits = struct.unpack_from("<qQB", data, 0)
    off = 17
    packed = np.frombuffer(data, dtype=np.uint8, offset=off)
    bitvec = np.unpackbits(packed)[: count * bits]
    bitmat = bitvec.reshape(count, bits).astype(np.uint64)
    vals = (bitmat << np.arange(bits, dtype=np.uint64)).sum(axis=1)
    return vals.astype(np.int64) + lo


def _bitpack_encode(q: np.ndarray) -> bytes:
    """Tight fixed-width packing: values biased by the stream minimum,
    packed LSB-first at ``span.bit_length()`` bits each.  A constant (or
    empty) stream has width 0 and costs only the 17-byte header, so this
    is never larger than ``raw`` (which always pays >= 1 bit per value)
    and there is no statistical modelling to mispredict."""
    lo = int(q.min()) if q.size else 0
    span = (int(q.max()) - lo) if q.size else 0
    width = span.bit_length()
    header = struct.pack("<qQB", lo, q.size, width)
    if width == 0:
        return header
    vals = (q - lo).astype(np.uint64)  # wraps mod 2^64: exact unsigned bias
    bitmat = ((vals[:, None] >> np.arange(width, dtype=np.uint64)) & 1).astype(np.uint8)
    return header + np.packbits(bitmat.reshape(-1), bitorder="little").tobytes()


def _bitpack_decode(data: bytes) -> np.ndarray:
    if len(data) < 17:
        raise TruncatedArchiveError(
            f"bitpack stream truncated: {len(data)} byte header, need 17"
        )
    lo, count, width = struct.unpack_from("<qQB", data, 0)
    if width > 64:
        raise FormatError(f"bitpack width byte {width} out of range (max 64)")
    nbytes = (count * width + 7) // 8
    if len(data) < 17 + nbytes:
        raise TruncatedArchiveError(
            f"bitpack stream truncated: payload {len(data) - 17} bytes, "
            f"need {nbytes} for {count} values at width {width}"
        )
    if len(data) > 17 + nbytes:
        raise CorruptFrameError(
            f"bitpack stream has {len(data) - 17 - nbytes} trailing bytes"
        )
    if width == 0:
        return np.full(count, lo, dtype=np.int64)
    packed = np.frombuffer(data, dtype=np.uint8, offset=17)
    bitvec = np.unpackbits(packed, bitorder="little")[: count * width]
    bitmat = bitvec.reshape(count, width).astype(np.uint64)
    vals = (bitmat << np.arange(width, dtype=np.uint64)).sum(axis=1, dtype=np.uint64)
    return vals.astype(np.int64) + lo


def _zstd_encode(q: np.ndarray, level: int = 19, compressor=None) -> bytes:
    assert _zstd is not None
    lo = int(q.min()) if q.size else 0
    span = (int(q.max()) - lo) if q.size else 0
    if span < (1 << 8):
        dt, code = np.uint8, 0
    elif span < (1 << 16):
        dt, code = np.uint16, 1
    elif span < (1 << 32):
        dt, code = np.uint32, 2
    else:
        dt, code = np.uint64, 3
    body = (q - lo).astype(dt).tobytes()
    ctx = compressor if compressor is not None else _zstd.ZstdCompressor(level=level)
    comp = ctx.compress(body)
    return struct.pack("<qQB", lo, q.size, code) + comp


def _zstd_decode(data: bytes, decompressor=None) -> np.ndarray:
    if _zstd is None:
        raise RuntimeError(
            "this stream was encoded with the zstd backend; install the "
            "'zstandard' extra to decode it"
        )
    lo, count, code = struct.unpack_from("<qQB", data, 0)
    dt = [np.uint8, np.uint16, np.uint32, np.uint64][code]
    ctx = decompressor if decompressor is not None else _zstd.ZstdDecompressor()
    body = ctx.decompress(data[17:])
    return np.frombuffer(body, dtype=dt).astype(np.int64) + lo


_BACKENDS = {"rc": 0, "zstd": 1, "raw": 2, "rans": 3, "bitpack": 4}
_REV = {v: k for k, v in _BACKENDS.items()}


def available_backends() -> list[str]:
    out = ["rc", "rans", "raw", "bitpack"]
    if _zstd is not None:
        out.insert(2, "zstd")
    return out


def backend_name(tag: int) -> str | None:
    """Backend name for a stream's leading tag byte, or None if unknown."""
    return _REV.get(tag)


# ------------------------------------------------------------------ #
# adaptive dispatch: cost model + per-stream routing
# ------------------------------------------------------------------ #

# rc is excluded from adaptive candidates: it is an O(n)-python oracle, never
# a production route.  zstd (level 19) is much slower than the packers and
# the rANS machine, so it must win the size prediction by a decisive margin
# before the dispatcher sends a stream its way.
_ZSTD_MARGIN = 0.9
# order-0 plane entropy is a lower bound on what the real coder emits (table
# quantization, 16-bit renorm granularity), so the rANS prediction is
# inflated a touch: near-ties then go to the packers, whose closed-form
# predictions are exact and therefore cannot be the wrong pick.
_RANS_PRED_INFLATE = 1.02
_ZSTD_FRAME_OVERHEAD = 13  # magic + frame header + checksum, roughly


def predict_backend_sizes(q: np.ndarray) -> dict[str, int]:
    """Predicted encoded sizes (tag byte included) per backend, from one
    O(n) feature pass: byte-plane histograms of the zigzagged stream (->
    order-0 entropy per plane and the zero-high-plane count), a run-length
    probe, and the max-magnitude bit width.  ``raw`` and ``bitpack`` are
    exact closed forms of their wire layouts; ``rans`` and ``zstd`` are
    estimates (see :func:`choose_backend` for how ties are biased)."""
    q = np.ascontiguousarray(q, dtype=np.int64)
    n = int(q.size)
    lo = int(q.min()) if n else 0
    span = (int(q.max()) - lo) if n else 0
    width = span.bit_length()
    pred = {
        "raw": 1 + 17 + (n * max(1, width) + 7) // 8,
        "bitpack": 1 + 17 + (n * width + 7) // 8,
    }
    med = int(_median_i64(q)) if n else 0
    zz = _zigzag(q - med)
    zmax = int(zz.max()) if n else 0
    nplanes = max(1, (zmax.bit_length() + 7) // 8)
    k = max(1, min(_RANS_K, n))
    rans = 18  # <qQBB header
    info_bits = 0.0
    nlog2n = n * np.log2(n) if n else 0.0
    for p in range(nplanes):
        sym = ((zz >> np.uint64(8 * p)) & np.uint64(0xFF)).astype(np.int64)
        counts = np.bincount(sym)
        nz = counts[counts > 0]
        rans += 32 + 2 * nz.size + 4 * k + 4
        if n:
            info_bits += float(nlog2n - (nz * np.log2(nz)).sum())
    rans += int(info_bits / 8)
    pred["rans"] = 1 + int(rans * _RANS_PRED_INFLATE) + 8
    if _zstd is not None and n:
        wbytes = 1 if width <= 8 else 2 if width <= 16 else 4 if width <= 32 else 8
        runs = int((q[1:] != q[:-1]).sum()) + 1
        # zstd sees the (q - lo) bytes: bounded below by their information
        # content (~ the plane entropies) and by what run-collapsing LZ
        # matches leave behind, whichever bites first
        pred["zstd"] = (
            1 + 17 + _ZSTD_FRAME_OVERHEAD + min(int(info_bits / 8), runs * (wbytes + 2))
        )
    return pred


def choose_backend(q: np.ndarray) -> str:
    """The cost model's pick for one stream.  Pure and deterministic per
    stream, so scalar and batched adaptive paths produce byte-identical
    blobs.  Ties go to the cheapest-to-encode exact-cost backend."""
    pred = predict_backend_sizes(q)
    best = "bitpack"
    for cand in ("rans", "raw"):
        if pred[cand] < pred[best]:
            best = cand
    z = pred.get("zstd")
    if z is not None and z < _ZSTD_MARGIN * pred[best]:
        best = "zstd"
    return best


def encode_ints(q: np.ndarray, backend: str = "best", exhaustive: bool = False) -> bytes:
    """Losslessly encode an int64 array.  Returns tagged bytes.

    ``backend='best'`` routes through the adaptive cost model (one O(n)
    feature pass, then exactly one encode).  ``exhaustive=True`` restores
    the brute-force oracle: encode with every candidate, keep the smallest
    — the compression-ratio ceiling, at ~4x the encode cost."""
    q = np.ascontiguousarray(q, dtype=np.int64)
    if backend == "best":
        if not exhaustive:
            c = choose_backend(q)
            return bytes([_BACKENDS[c]]) + _dispatch_encode(q, c)
        cands = ["rans"]
        # rc is O(n) python — skip it for very large streams; rans/zstd are
        # within a few % of its size at numpy/C speed
        if q.size <= 300_000:
            cands.append("rc")
        if _zstd is not None:
            cands.append("zstd")
        cands.append("raw")
        cands.append("bitpack")
        blobs = [(len(b := _dispatch_encode(q, c)), c, b) for c in cands]
        _, c, b = min(blobs, key=lambda t: t[0])
        return bytes([_BACKENDS[c]]) + b
    if backend not in _BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; have {sorted(_BACKENDS)} or 'best'")
    return bytes([_BACKENDS[backend]]) + _dispatch_encode(q, backend)


def _dispatch_encode(q: np.ndarray, backend: str) -> bytes:
    if backend == "rc":
        return _rc_encode(q)
    if backend == "rans":
        return _rans_encode(q)
    if backend == "zstd":
        if _zstd is None:
            raise RuntimeError("zstandard not available")
        return _zstd_encode(q)
    if backend == "raw":
        return _raw_encode(q)
    if backend == "bitpack":
        return _bitpack_encode(q)
    raise ValueError(f"unknown backend {backend!r}")


def _adaptive_encode_batch(arrs: list[np.ndarray]) -> list[bytes]:
    """``backend='best'`` over a batch: choose per stream with the cost
    model (the same pure per-stream decision the scalar path makes, so
    batch and scalar outputs stay byte-identical), then partition by
    choice — the rans-bound group keeps the fused rect/ragged machines
    (device engine included), the zstd group shares one compressor, and
    the packers loop (each already vectorized per stream)."""
    out: list[bytes] = [b""] * len(arrs)
    groups: dict[str, list[int]] = {}
    for i, q in enumerate(arrs):
        groups.setdefault(choose_backend(q), []).append(i)
    idxs = groups.pop("rans", None)
    if idxs:
        blobs = encode_ints_batch([arrs[i] for i in idxs], backend="rans")
        for i, blob in zip(idxs, blobs):
            out[i] = blob
    idxs = groups.pop("zstd", None)
    if idxs:
        ctx = _zstd.ZstdCompressor(level=19)
        tag = bytes([_BACKENDS["zstd"]])
        for i in idxs:
            out[i] = tag + _zstd_encode(arrs[i], compressor=ctx)
    for c, idxs in groups.items():
        tag = bytes([_BACKENDS[c]])
        for i in idxs:
            out[i] = tag + _dispatch_encode(arrs[i], c)
    return out


def decode_ints(data: bytes) -> np.ndarray:
    if not data:
        raise TruncatedArchiveError("entropy stream is empty (missing tag byte)")
    tag = _REV.get(data[0])
    if tag is None:
        raise FormatError(f"unknown entropy backend tag {data[0]}")
    body = data[1:]
    if tag == "rc":
        return _rc_decode(body)
    if tag == "rans":
        return _rans_decode(body)
    if tag == "zstd":
        return _zstd_decode(body)
    if tag == "bitpack":
        return _bitpack_decode(body)
    return _raw_decode(body)


def decode_ints_batch(blobs: list[bytes]) -> list[np.ndarray]:
    """Batched ``decode_ints``: one shared ``ZstdDecompressor`` serves
    every zstd-tagged stream in the batch (the scalar path pays a fresh
    context per call)."""
    ztag = _BACKENDS["zstd"]
    ctx = None
    out = []
    for data in blobs:
        if data and data[0] == ztag and _zstd is not None:
            if ctx is None:
                ctx = _zstd.ZstdDecompressor()
            out.append(_zstd_decode(data[1:], decompressor=ctx))
        else:
            out.append(decode_ints(data))
    return out
