"""Device engine: the interleaved K-lane rANS coder as one XLA scan.

The device twin of ``core.entropy``'s numpy step machine
(``_rans_machine``, and ``_rans_decode_plane`` for decode): the K
interleaved 32-bit states map to the **lane (vector) dimension**,
independent (stream, plane) rows map to sublanes, and the serial step
axis (symbol i // K) runs as the loop of the jit'd
``ref.rans_encode_ref``/``rans_decode_ref`` ``lax.scan`` machines, which
compile for the TPU as well as the CPU.  Renormalization writes are
compacted per step: each step emits a dense [R, K] (need, low-16-bits)
pair, and ``_compact_words`` packs every row's words to the front of its
row on the device, in wire order (steps ascending, lanes ascending —
decoder order).

``encode_rows``/``decode_rows`` are the host-facing entry points used by
``core.entropy``'s device engine: numpy in, numpy out, with the
identity-symbol padding scheme (symbol 256, freq = M, cum = 0 — the rANS
transform is then exactly ``x -> x`` and the uint32 renorm threshold
wraps to "never") padding step counts and row counts up to a shape bucket
(``_bucket``: at most 8 sizes per octave) so the jit cache sees a bounded
set of shapes; ragged jobs run in fixed blocks of their step class
instead (``ragged_blocks``, ``class_shape``).  Padded cells are byte-exact
no-ops, so the wire format stays identical to the numpy machine's
(golden fixtures unchanged).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import ref
from .calls import note_call, note_cells

__all__ = [
    "encode_rows",
    "encode_cube",
    "decode_rows",
    "class_shape",
    "ragged_blocks",
]

_PROB_BITS = 12
_M = 1 << _PROB_BITS
_L = 1 << 16
_K = 64
_ID = 256  # identity pad symbol (row tables carry a reserved 257th entry)

# jit cache shape bucketing: steps and rows pad up to ``_bucket``, so a
# workload with drifting sizes compiles O(log) scan programs, not O(sizes);
# the unroll factors need not divide the padded step counts
_ENC_UNROLL = 8
_DEC_UNROLL = 4


def _pow2(v: int) -> int:
    return 1 << max(0, int(v - 1).bit_length())


def _bucket(v: int) -> int:
    """``v`` rounded up to a multiple of ``2^(bit_length(v - 1) - 4)``: at
    most 8 sizes per octave, under 1/8 of padding, and ``v`` itself up to
    16."""
    v = max(1, int(v))
    step = 1 << max(0, (v - 1).bit_length() - 4)
    return -(-v // step) * step


# ragged jobs dispatch a fixed set of programs: a row runs in the class of
# its step count, the next power of two and at least _CLASS_MIN_STEPS, and a
# class's rows run in blocks of a fixed row count.  Which lengths meet in
# one job then moves no shape: a stream of backlogs compiles while warming
# up and not while serving.  Blocks hold 256 rows up to 512 steps and fewer
# beyond, so a dispatch stays under _CLASS_CELLS * K cells.
_CLASS_MIN_STEPS = 32
_CLASS_ROWS = 256
_CLASS_CELLS = 512 * _CLASS_ROWS


def class_shape(steps: int) -> tuple[int, int]:
    """(padded steps, rows of a block) of the class that ragged rows of
    ``steps`` steps run in."""
    c = max(_CLASS_MIN_STEPS, _pow2(steps))
    return c, max(8, min(_CLASS_ROWS, _CLASS_CELLS // c))


def ragged_blocks(steps: np.ndarray) -> list[np.ndarray]:
    """The blocks of a ragged job whose rows take ``steps[r]`` steps: each
    is the ids of up to one block of rows of one class, ascending.  The
    engine dispatches a block at its class's shape; the numpy machine runs
    it at its own longest row."""
    distinct, at = np.unique(np.asarray(steps, dtype=np.int64), return_inverse=True)
    shapes = [class_shape(s) for s in distinct.tolist()]
    cls = np.array([c for c, _ in shapes], dtype=np.int64)[at]
    blocks = []
    for c, rows in sorted(set(shapes)):
        ids = np.flatnonzero(cls == c)
        blocks.extend(ids[i : i + rows] for i in range(0, ids.size, rows))
    return blocks


# --------------------------------------------------------------------- #
# The jitted scans: ``bench/kernels.json`` finds them in a device trace by
# their module names, ``rans_encode_ref`` and ``rans_decode_ref``
# --------------------------------------------------------------------- #
_enc_ref_jit = jax.jit(ref.rans_encode_ref, static_argnames=("unroll",))
_dec_ref_jit = jax.jit(ref.rans_decode_ref, static_argnames=("unroll",))


def on_tpu() -> bool:
    """True when the engine's scans run on a TPU.  Callers use this to
    decide how aggressively to batch work onto the engine: on the CPU
    backend the scan only beats the numpy machine above a dispatch-
    amortizing size."""
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------- #
# Host-facing engine (numpy in / numpy out; used by core.entropy)
# --------------------------------------------------------------------- #
def encode_rows(
    sym_mat: np.ndarray,
    freqs: np.ndarray,
    lengths: np.ndarray | None = None,
) -> tuple[np.ndarray, list[np.ndarray]]:
    """Encode R independent symbol rows with per-row normalized tables.

    sym_mat[R, cols] integer symbols in [0, 256] — 256 is the identity pad
    (ragged callers pre-pad short rows with it; any extra padding to a
    step multiple is added here).  freqs[R, 256] int — each row's
    normalized histogram (sum == M) — identity-column and cum tables are
    derived internally.  ``lengths[R]``, for ragged rows, gives each row's
    symbols before its padding: the rows then run at their class's shape
    (``class_shape``; R at most its block), else at ``_bucket`` of R and
    of the steps.  Returns (states[R, K] uint32 — native order, cast with
    ``.astype('<u4')`` for the wire — and the per-row uint16 word streams
    in decoder order).
    """
    r, cols = sym_mat.shape
    steps = max(1, -(-cols // _K))
    if lengths is None:
        steps_p, rp, real = _bucket(steps), _bucket(r), r * cols
    else:
        steps_p, rp = class_shape(steps)
        real = int(np.sum(lengths))
        if r > rp:
            raise ValueError(f"{r} rows exceed the {rp}-row block of {steps_p} steps")
    cube = np.full((rp, steps_p * _K), _ID, dtype=np.int32)
    cube[:r, :cols] = sym_mat
    cube = np.ascontiguousarray(
        cube.reshape(rp, steps_p, _K).transpose(1, 0, 2)
    )
    return encode_cube(cube, freqs, real)


@jax.jit
def _compact_words(need: jax.Array, vals: jax.Array):
    """need/vals[T, R, K] (the scan's renorm mask and low 16 bits) ->
    (counts[R] int32, words[R, T * K] uint16): each row's words packed to
    the front of its row in decoder order (steps ascending, lanes
    ascending), zeros after them.

    A word moves left by the number of cells before it in its row that
    hold no word.  The moves run as one elementwise pass per bit of that
    distance, low bit first, each shifting the words whose distance has
    that bit by 2^bit; no two words ever meet, since a later word's
    distance exceeds an earlier one's by at most the empty cells between
    them.  A scatter does the same work several times slower on the TPU."""
    t, r, k = need.shape
    need = need.transpose(1, 0, 2).reshape(r, t * k)
    vals = vals.transpose(1, 0, 2).reshape(r, t * k)
    # each word's distance; -1 marks a cell that holds no word
    dist = jnp.where(need, jnp.cumsum(~need, axis=1, dtype=jnp.int32), -1)

    def pull(x, step, fill):  # x[:, q + step] at q
        return jnp.pad(x[:, step:], ((0, 0), (0, step)), constant_values=fill)

    for bit in range(max(1, (t * k - 1).bit_length())):
        step = 1 << bit
        moves = (dist >= 0) & ((dist >> bit) & 1 == 1)
        arrives = pull(moves, step, False)
        dist = jnp.where(arrives, pull(dist, step, -1), jnp.where(moves, -1, dist))
        vals = jnp.where(arrives, pull(vals, step, 0), vals)
    return need.sum(axis=1, dtype=jnp.int32), jnp.where(dist >= 0, vals, 0)


def encode_cube(
    cube: np.ndarray | jax.Array,
    freqs: np.ndarray,
    real: int,
) -> tuple[np.ndarray, list[np.ndarray]]:
    """Encode the first R = len(freqs) rows of a padded symbol cube[T, Rp,
    K] int32 (host or device array; padding is the identity symbol) with
    their normalized tables freqs[R, 256]; ``real`` is the symbols it
    carries.  The scan's words are packed on the device
    (``_compact_words``), so one [Rp, T * K] buffer comes back and each
    row's words are a view of its row.  Returns (states[R, K] uint32,
    words) as ``encode_rows``."""
    steps_p, rp, _ = cube.shape
    r = freqs.shape[0]
    f_ext = np.full((rp, 257), _M, dtype=np.uint32)
    c_ext = np.zeros((rp, 257), dtype=np.uint32)
    f_ext[:r, :256] = freqs
    c_ext[:r, 1:256] = np.cumsum(freqs[:, :-1], axis=1)
    with obs.span("device.rans_encode"):
        states, need, vals = _enc_ref_jit(
            jnp.asarray(cube), jnp.asarray(f_ext), jnp.asarray(c_ext),
            unroll=_ENC_UNROLL,
        )
        note_call("rans_encode", states)
        note_cells("rans_encode", real, steps_p * rp * _K)
        counts, words = _compact_words(need, vals)
        states, counts, words = jax.device_get((states, counts, words))
    return states[:r], [words[i, :c] for i, c in enumerate(counts[:r].tolist())]


def decode_rows(
    states: np.ndarray,
    freqs: np.ndarray,
    words: list[np.ndarray],
    n: int,
) -> np.ndarray:
    """Decode R rows of ``n`` symbols each from their final states, tables
    and word streams.  Returns syms[R, n] uint8."""
    r = states.shape[0]
    steps = max(1, -(-n // _K))
    steps_p = _bucket(steps)
    rp = _bucket(r)
    tail = n - (steps - 1) * _K if n else 0
    x0 = np.full((rp, _K), _L, dtype=np.uint32)
    x0[:r] = states
    # every row's freqs sum to M, so one flat repeat builds all the
    # slot -> symbol maps at once
    s2s = np.zeros((rp, _M), dtype=np.int32)
    s2s[:r] = np.repeat(
        np.tile(np.arange(256, dtype=np.int32), r), freqs.reshape(-1)
    ).reshape(r, _M)
    f_tab = np.zeros((rp, 256), dtype=np.uint32)
    c_tab = np.zeros((rp, 256), dtype=np.uint32)
    f_tab[:r] = freqs
    c_tab[:r, 1:] = np.cumsum(freqs[:, :-1], axis=1)
    # the word count drifts from frame to frame: a finer bucket here would
    # compile more programs inside a query window
    maxw = _pow2(max(1, max((w.size for w in words), default=1)))
    words_mat = np.zeros((rp, maxw), dtype=np.uint16)
    for i, w in enumerate(words):
        words_mat[i, : w.size] = w
    act = np.zeros((steps_p, rp, _K), dtype=bool)
    act[:steps, :r, :] = True
    if steps:
        act[steps - 1, :r, tail:] = False
    with obs.span("device.rans_decode"):
        syms = _dec_ref_jit(
            jnp.asarray(x0), jnp.asarray(s2s), jnp.asarray(f_tab),
            jnp.asarray(c_tab), jnp.asarray(words_mat), jnp.asarray(act),
            unroll=_DEC_UNROLL,
        )
        note_call("rans_decode", syms)
        note_cells("rans_decode", r * n, steps_p * rp * _K)
        syms = np.asarray(syms)  # [steps_p, rp, K]
    return np.ascontiguousarray(syms.transpose(1, 0, 2)[:r].reshape(r, -1)[:, :n])
