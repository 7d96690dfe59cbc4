"""The cone scan kernel (``kernels/cone_scan.py``): one (row, lane) step per
real sample.  Padded rows and lanes are left out, so the count is the same
whatever shape the wrapper pads a batch to.

Per step the kernel body does 27 float32 vector operations (the two
candidate slopes, the clamps, the break test, the quantised origin and the
state selects) and moves 24 bytes: it reads the sample and its error bound
(4 bytes each) and writes the break flag, origin and both spans (4 bytes
each).
"""
OPS_PER_SAMPLE = 27
BYTES_PER_SAMPLE = 24
PEAK = "bf16_flops_per_s"  # the chip's published float peak; no f32 vector peak is published


def work(run):
    n = run.counters.get("samples_sealed", 0)  # each sealed sample was scanned once
    return (OPS_PER_SAMPLE * n, BYTES_PER_SAMPLE * n) if n else None
