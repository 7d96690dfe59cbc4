"""Per-device call counts of the device kernels on the codec's path.

Each wrapper that launches device work notes one call against the device
that holds its output, so a run can show which devices did the work (the
chip smoke check and the fleet's per-shard placement read these).
"""
from __future__ import annotations

import collections

import jax

__all__ = ["note_call", "call_counts"]

_COUNTS: collections.Counter = collections.Counter()


def note_call(name: str, out: jax.Array) -> None:
    """Count one call of kernel ``name`` on the device holding ``out``."""
    for dev in out.devices():
        _COUNTS[(name, dev.platform, dev.id)] += 1


def call_counts() -> dict[tuple[str, str, int], int]:
    """``{(kernel, platform, device id): calls}`` since the process began."""
    return dict(_COUNTS)
