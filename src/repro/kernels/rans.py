"""Pallas TPU kernel + device engine: the interleaved K-lane rANS coder.

Hardware adaptation of ``core.entropy``'s numpy step machines
(``_rans_encode_plane`` / ``_rans_decode_plane``): the K interleaved
32-bit states map to the **lane (vector) dimension**, independent
(stream, plane) rows map to sublanes, and the serial step axis (symbol
i // K) runs as the sequential grid — the same shape as the cone-scan
kernel, with the coder state carried across grid steps in VMEM scratch.
Renormalization writes are compacted per step: each step emits a dense
[R, K] (need, low-16-bits) pair, and ``_compact_words`` packs every
row's words to the front of its row on the device, in wire order (steps
ascending, lanes ascending — decoder order).

Two execution routes, byte-identical by construction:

* ``route="xla"`` — the jit'd ``ref.rans_encode_ref``/``rans_decode_ref``
  ``lax.scan`` machines.  This is the **production path on every
  backend**: one fused XLA loop over steps instead of ~n/K interpreted
  numpy dispatches.  It compiles for the TPU as well as the CPU.
* ``route="interpret"`` — the Pallas kernels below in ``interpret=True``
  mode: the kernel body as traced JAX ops with the real block/grid
  decomposition.  Too slow for production per-step grids; used by the
  CPU CI parity suite (tests/test_rans_kernel.py) to validate the
  kernels against the oracles and the numpy wire bytes.  The kernels do
  not lower with Mosaic yet (``take_along_axis`` into the per-row
  tables), so they have no compiled route.

``encode_rows``/``decode_rows`` are the host-facing entry points used by
``core.entropy``'s device engine: numpy in, numpy out, with the
identity-symbol padding scheme (symbol 256, freq = M, cum = 0 — the rANS
transform is then exactly ``x -> x`` and the uint32 renorm threshold
wraps to "never") padding step counts and row counts up to a shape bucket
(``_bucket``: at most 8 sizes per octave) so the jit cache sees a bounded
set of shapes; ragged jobs run in fixed blocks of their step class
instead (``ragged_blocks``, ``class_shape``).  Padded cells are byte-exact
no-ops, so the wire format stays identical to the numpy coder for every
route (golden fixtures unchanged).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from . import ref
from .calls import note_call, note_cells

__all__ = [
    "rans_encode_pallas",
    "rans_decode_pallas",
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
    """The dispatches of a ragged job whose rows take ``steps[r]`` steps:
    each is the ids of up to one block of rows of one class, ascending."""
    distinct, at = np.unique(np.asarray(steps, dtype=np.int64), return_inverse=True)
    shapes = [class_shape(s) for s in distinct.tolist()]
    cls = np.array([c for c, _ in shapes], dtype=np.int64)[at]
    blocks = []
    for c, rows in sorted(set(shapes)):
        ids = np.flatnonzero(cls == c)
        blocks.extend(ids[i : i + rows] for i in range(0, ids.size, rows))
    return blocks


# --------------------------------------------------------------------- #
# Pallas kernels
# --------------------------------------------------------------------- #
def _rans_encode_kernel(
    sym_ref,     # (1, R, K) int32 block: this step's symbols
    f_ref,       # (R, 257) uint32: per-row freq tables + identity column
    c_ref,       # (R, 257) uint32: per-row cum tables
    states_ref,  # (R, K) uint32 out: final states (last grid step wins)
    need_ref,    # (1, R, K) int32 out: renorm mask for this step
    val_ref,     # (1, R, K) int32 out: low 16 bits pre-renorm
    x_ref,       # VMEM (R, K) uint32 scratch: the coder state
):
    i = pl.program_id(0)
    r, k = x_ref.shape

    @pl.when(i == 0)
    def _init():
        x_ref[:, :] = jnp.full((r, k), _L, jnp.uint32)

    syms = sym_ref[0, :, :]
    f = jnp.take_along_axis(f_ref[:, :], syms, axis=1).astype(jnp.uint32)
    c = jnp.take_along_axis(c_ref[:, :], syms, axis=1).astype(jnp.uint32)
    x = x_ref[:, :]
    # same uint32 wrap trick as the numpy machine: f == 2^12 -> threshold
    # wraps to the uint32 max -> identity/pad symbols never renormalize
    need = x > (f << jnp.uint32(32 - _PROB_BITS)) - jnp.uint32(1)
    need_ref[0, :, :] = need.astype(jnp.int32)
    val_ref[0, :, :] = (x & jnp.uint32(0xFFFF)).astype(jnp.int32)
    x = jnp.where(need, x >> jnp.uint32(16), x)
    div = x // f
    rem = x - div * f
    x = (div << jnp.uint32(_PROB_BITS)) + rem + c
    x_ref[:, :] = x
    # the grid runs steps in reverse; the final (t == 0) write wins
    states_ref[:, :] = x


@functools.partial(jax.jit, static_argnames=("interpret",))
def rans_encode_pallas(
    sym_cube: jax.Array,
    f_ext: jax.Array,
    c_ext: jax.Array,
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pallas twin of ``ref.rans_encode_ref``: sym_cube[T, R, K] int32,
    f_ext/c_ext[R, 257] uint32 -> (states[R, K] uint32, need[T, R, K]
    bool, vals[T, R, K] uint16).  Grid = T sequential steps walked in
    reverse (encode is LIFO); state carried in VMEM scratch."""
    t, r, k = sym_cube.shape
    rev = lambda i: (t - 1 - i, 0, 0)
    states, need, vals = pl.pallas_call(
        _rans_encode_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, r, k), rev),
            pl.BlockSpec((r, 257), lambda i: (0, 0)),
            pl.BlockSpec((r, 257), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((r, k), lambda i: (0, 0)),
            pl.BlockSpec((1, r, k), rev),
            pl.BlockSpec((1, r, k), rev),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, k), jnp.uint32),
            jax.ShapeDtypeStruct((t, r, k), jnp.int32),
            jax.ShapeDtypeStruct((t, r, k), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((r, k), jnp.uint32)],
        interpret=interpret,
    )(sym_cube, f_ext, c_ext)
    return states, need.astype(bool), vals.astype(jnp.uint16)


def _rans_decode_kernel(
    x0_ref,       # (R, K) uint32: final encoder states
    s2s_ref,      # (R, M) int32: slot -> symbol
    f_ref,        # (R, 256) uint32
    c_ref,        # (R, 256) uint32
    words_ref,    # (R, W) int32: row-padded renorm words
    act_ref,      # (1, R, K) int32 block: live positions this step
    syms_ref,     # (1, R, K) int32 out
    x_ref,        # VMEM (R, K) uint32 scratch
    pos_ref,      # VMEM (1, R) int32 scratch: per-row word cursor
):
    i = pl.program_id(0)
    r, k = x_ref.shape

    @pl.when(i == 0)
    def _init():
        x_ref[:, :] = x0_ref[:, :]
        pos_ref[0, :] = jnp.zeros((r,), jnp.int32)

    a = act_ref[0, :, :] != 0
    x = x_ref[:, :]
    pos = pos_ref[0, :]
    slot = (x & jnp.uint32(_M - 1)).astype(jnp.int32)
    s = jnp.take_along_axis(s2s_ref[:, :], slot, axis=1)
    f = jnp.take_along_axis(f_ref[:, :], s, axis=1).astype(jnp.uint32)
    c = jnp.take_along_axis(c_ref[:, :], s, axis=1).astype(jnp.uint32)
    x2 = f * (x >> jnp.uint32(_PROB_BITS)) + slot.astype(jnp.uint32) - c
    need = (x2 < _L) & a
    # renormalizing lanes consume this row's words in ascending lane order
    kidx = pos[:, None] + jnp.cumsum(need.astype(jnp.int32), axis=1) - 1
    w = jnp.take_along_axis(words_ref[:, :], jnp.clip(kidx, 0, None), axis=1)
    x2 = jnp.where(need, (x2 << jnp.uint32(16)) | w.astype(jnp.uint32), x2)
    x_ref[:, :] = jnp.where(a, x2, x)
    pos_ref[0, :] = pos + need.sum(axis=1, dtype=jnp.int32)
    syms_ref[0, :, :] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def rans_decode_pallas(
    states: jax.Array,
    slot2sym: jax.Array,
    f_tab: jax.Array,
    c_tab: jax.Array,
    words: jax.Array,
    act: jax.Array,
    *,
    interpret: bool,
) -> jax.Array:
    """Pallas twin of ``ref.rans_decode_ref``; act[T, R, K] bool ->
    syms[T, R, K] uint8.  Grid = T sequential steps, forward."""
    t, r, k = act.shape
    fwd = lambda i: (i, 0, 0)
    syms = pl.pallas_call(
        _rans_decode_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((r, k), lambda i: (0, 0)),
            pl.BlockSpec((r, _M), lambda i: (0, 0)),
            pl.BlockSpec((r, 256), lambda i: (0, 0)),
            pl.BlockSpec((r, 256), lambda i: (0, 0)),
            pl.BlockSpec((r, words.shape[1]), lambda i: (0, 0)),
            pl.BlockSpec((1, r, k), fwd),
        ],
        out_specs=[pl.BlockSpec((1, r, k), fwd)],
        out_shape=[jax.ShapeDtypeStruct((t, r, k), jnp.int32)],
        scratch_shapes=[
            pltpu.VMEM((r, k), jnp.uint32),
            pltpu.VMEM((1, r), jnp.int32),
        ],
        interpret=interpret,
    )(states, slot2sym, f_tab, c_tab, words.astype(jnp.int32),
      act.astype(jnp.int32))[0]
    return syms.astype(jnp.uint8)


# --------------------------------------------------------------------- #
# Route dispatch
# --------------------------------------------------------------------- #
_enc_ref_jit = jax.jit(ref.rans_encode_ref, static_argnames=("unroll",))
_dec_ref_jit = jax.jit(ref.rans_decode_ref, static_argnames=("unroll",))


def on_tpu() -> bool:
    """True when the engine's scans run on a TPU.  Callers use this to
    decide how aggressively to batch work onto the engine: on the CPU
    backend the scan only beats the numpy machine above a dispatch-
    amortizing size."""
    return jax.default_backend() == "tpu"


def _dispatch_encode(sym_cube, f_ext, c_ext, route: str):
    if route == "xla":
        return _enc_ref_jit(sym_cube, f_ext, c_ext, unroll=_ENC_UNROLL)
    if route == "interpret":
        return rans_encode_pallas(sym_cube, f_ext, c_ext, interpret=True)
    raise ValueError(f"unknown rans route {route!r}")


def _dispatch_decode(states, slot2sym, f_tab, c_tab, words, act, route: str):
    if route == "xla":
        return _dec_ref_jit(states, slot2sym, f_tab, c_tab, words, act,
                            unroll=_DEC_UNROLL)
    if route == "interpret":
        return rans_decode_pallas(states, slot2sym, f_tab, c_tab, words, act,
                                  interpret=True)
    raise ValueError(f"unknown rans route {route!r}")


# --------------------------------------------------------------------- #
# Host-facing engine (numpy in / numpy out; used by core.entropy)
# --------------------------------------------------------------------- #
def encode_rows(
    sym_mat: np.ndarray,
    freqs: np.ndarray,
    route: str = "xla",
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
    return encode_cube(cube, freqs, real, route)


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
    route: str = "xla",
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
        states, need, vals = _dispatch_encode(
            jnp.asarray(cube), jnp.asarray(f_ext), jnp.asarray(c_ext), route
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
    route: str = "xla",
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
        syms = _dispatch_decode(
            jnp.asarray(x0), jnp.asarray(s2s), jnp.asarray(f_tab),
            jnp.asarray(c_tab), jnp.asarray(words_mat), jnp.asarray(act), route
        )
        note_call("rans_decode", syms)
        note_cells("rans_decode", r * n, steps_p * rp * _K)
        syms = np.asarray(syms)  # [steps_p, rp, K]
    return np.ascontiguousarray(syms.transpose(1, 0, 2)[:r].reshape(r, -1)[:, :n])
