"""Per-device call counts of the device kernels on the codec's path.

Each wrapper that launches device work notes one call against the device
that holds its output, so a run can show which devices did the work (the
chip smoke check and the fleet's per-shard placement read these).  The
cone scan and the rANS encoder also note the cells of each call: the real
ones that carry a sample or a symbol, and the ones dispatched after shape
padding.  Their host machines (the numpy cone scan of ``compress_batch``,
the numpy ragged rANS machine) note theirs under the same names, so the
shares read on any backend.
"""
from __future__ import annotations

import collections

import jax

__all__ = ["note_call", "call_counts", "note_cells", "cell_counts"]

_COUNTS: collections.Counter = collections.Counter()
_REAL_CELLS: collections.Counter = collections.Counter()
_RUN_CELLS: collections.Counter = collections.Counter()


def note_call(name: str, out: jax.Array) -> None:
    """Count one call of kernel ``name`` on the device holding ``out``."""
    for dev in out.devices():
        _COUNTS[(name, dev.platform, dev.id)] += 1


def call_counts() -> dict[tuple[str, str, int], int]:
    """``{(kernel, platform, device id): calls}`` since the process began."""
    return dict(_COUNTS)


def note_cells(name: str, real: int, run: int) -> None:
    """Count ``real`` cells carrying work and ``run`` dispatched cells (real
    plus padding) for one call of kernel ``name``."""
    _REAL_CELLS[name] += real
    _RUN_CELLS[name] += run


def cell_counts() -> dict[str, tuple[int, int]]:
    """``{kernel: (real cells, dispatched cells)}`` since the process began."""
    return {name: (_REAL_CELLS[name], _RUN_CELLS[name]) for name in _RUN_CELLS}
