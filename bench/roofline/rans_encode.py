"""The rANS encode engine (``kernels/rans.py``, the jitted
``rans_encode_ref`` scan): one state update per real plane symbol, counted
from the frames' own stream headers (symbols times planes), so padded
steps and rows are left out.

Per symbol the step does 15 integer operations (table index, two table
reads, the renormalisation test and shift, the division, the remainder and
the state update) and moves at least 7 bytes: it reads the int32 symbol and
writes the renormalisation flag (1 byte) and the 16-bit word.
"""
OPS_PER_SYMBOL = 15
BYTES_PER_SYMBOL = 7
PEAK = "int8_ops_per_s"  # the chip's published integer peak


def work(run):
    n = run.counters.get("symbols_encoded", 0)
    return (OPS_PER_SYMBOL * n, BYTES_PER_SYMBOL * n) if n else None
